"""PipelineEngine — training engine for PipelineModule models.

Parity: reference ``runtime/pipe/engine.py`` (``PipelineEngine``:
``train_batch:295``, ``eval_batch:380``, ``_exec_schedule:1360``).

TPU-first: the reference subclasses DeepSpeedEngine and replaces the train
step with an imperative instruction interpreter.  Here the subclass only
changes *what gets jitted*: the whole GPipe clock (fill → steady → drain →
reverse/backward → reduce → step) is the single compiled program produced
by ``PipelineModule.loss`` + autodiff (see ``pipe/pipeline.py``), so
``train_batch`` keeps the parent's shape: shard batch, run step, log.

Composition rules match the reference: ZeRO stages 0/1 compose with PP
(``engine.py:1541`` — ZeRO-2/3 do not); grads for body params reduce over
the data axes only (XLA scopes collectives per named axis automatically —
body grads are pp-sharded so no reduction crosses stages, the
``ReduceGrads``/``ReduceTiedGrads`` distinction falls out of the sharding).
"""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.monitor.telemetry import in_setup_span
from deepspeed_tpu.runtime.engine import (DeepSpeedEngine, TrainState,
                                          moq_anneal_step)
from deepspeed_tpu.runtime.pipe.module import PipelineModule
from deepspeed_tpu.runtime.pipe.schedule import TrainSchedule
from deepspeed_tpu.utils.logging import log_dist


class PipelineEngine(DeepSpeedEngine):

    @in_setup_span("setup/engine", kind="train")
    def __init__(self, model, config, **kwargs):
        assert isinstance(model, PipelineModule), \
            "PipelineEngine requires a PipelineModule model"
        if kwargs.get("params") is None:
            raise ValueError("model_parameters (from PipelineModule.init) "
                             "is required")
        # tp_rules default comes from the base engine's auto-TP
        # (DeepSpeedEngine.__init__ pulls model.tp_rules())
        if config.zero_config.offload_param_device != "none":
            raise ValueError(
                "offload_param (param-stream) does not compose with "
                "pipeline parallelism: the pipelined step is one jitted "
                "SPMD scan with no per-layer program boundary to stream "
                "through (the reference draws the same line — ZeRO-3 param "
                "partitioning is incompatible with PP, engine.py:1541).  "
                "Use offload_optimizer (host Adam at the step boundary) "
                "with PP instead.")
        super().__init__(model=model, config=config, **kwargs)
        assert self.zero_stage <= 1, (
            "ZeRO-2/3 is incompatible with pipeline parallelism "
            "(reference engine.py:1541); use stage 0 or 1")
        self.micro_batches = self.gradient_accumulation_steps_
        self.num_stages = model.num_stages
        log_dist(
            f"PipelineEngine: stages={self.num_stages} "
            f"micro_batches={self.micro_batches} "
            f"bubble={(self.num_stages - 1) / (self.micro_batches + self.num_stages - 1):.2f}",
            ranks=[0])
        if self._tel_enabled:
            self._emit_schedule_telemetry()

    def _emit_schedule_telemetry(self):
        """One ``meta`` event per stage describing the schedule phases the
        compiled scan realises (fill/active/drain tick counts plus an
        instruction census from :class:`TrainSchedule`).  The per-phase
        spans *inside* the step are the trace-time ``pipe/*`` named scopes
        (see ``pipe/pipeline.py``) — visible in xprof, not host-timeable,
        because the whole clock is one XLA program."""
        M, P = self.micro_batches, self.num_stages
        ap = self._config.async_pipeline_config
        for s in range(P):
            counts = {}
            for cmds in TrainSchedule(micro_batches=M, stages=P, stage_id=s):
                for c in cmds:
                    k = type(c).__name__
                    counts[k] = counts.get(k, 0) + 1
            self.telemetry.emit(
                "meta", f"pipe/schedule/stage{s}",
                attrs={"stage": s, "stages": P, "micro_batches": M,
                       "fill_ticks": s, "active_ticks": M,
                       "drain_ticks": P - 1 - s,
                       "bubble": (P - 1) / (M + P - 1),
                       "instructions": counts,
                       # whether the microbatch stack arrives prefetched
                       # and how often metric readback syncs the host
                       "async_pipeline": bool(ap.enabled),
                       "prefetch_depth": int(ap.prefetch_depth),
                       "sync_interval": int(ap.sync_interval)})

    # the compiled step: ONE loss call over the microbatch stack — the
    # microbatch dim is the pipeline clock, not a grad-accumulation scan
    def _build_train_step(self, gas: int):
        cfg = self._config
        fp16 = cfg.fp16_enabled

        def train_step(state: TrainState, batch):
            if gas == 1:  # ensure the leading microbatch dim exists
                batch = jax.tree_util.tree_map(lambda x: x[None], batch)
            scale = state.loss_scale.cur_scale if fp16 else jnp.float32(1.0)
            rng, step_rng = jax.random.split(state.rng)
            loss, grads, _ = self._loss_and_grads(
                state.params, scale, batch, step_rng,
                step=state.global_step,
                qstep=moq_anneal_step(state))
            return self._finish_step(state, loss, grads, rng)

        return train_step

    # ZeRO-Offload x PP: the base builder wraps a GAS scan around the loss,
    # but here the microbatch dim IS the pipeline clock — build the grad
    # step from the pipelined loss directly.  The host tail (streamed D2H /
    # C++ Adam / streamed H2D, engine._offload_host_apply) is shared.
    def _get_compiled_offload_grad_step(self, gas: int):
        if gas not in self._compiled_offload_grad:
            from deepspeed_tpu.runtime.engine import (_global_norm_f32,
                                                      constrain,
                                                      has_inf_or_nan)
            fp16 = self._config.fp16_enabled

            def grad_step(state: TrainState, batch):
                if gas == 1:
                    batch = jax.tree_util.tree_map(lambda x: x[None], batch)
                scale = (state.loss_scale.cur_scale if fp16
                         else jnp.float32(1.0))
                rng, step_rng = jax.random.split(state.rng)
                loss, grads, _ = self._loss_and_grads(
                    state.params, scale, batch, step_rng,
                    step=state.global_step, qstep=moq_anneal_step(state))
                grads = constrain(grads, self.plan.grad_specs(state.params),
                                  self.mesh)
                overflow = (has_inf_or_nan(grads) if fp16
                            else jnp.asarray(False))
                grad_norm = _global_norm_f32(grads)
                return loss, grads, overflow, grad_norm, rng
            self._compiled_offload_grad[gas] = self._wrap_compiled(
                jax.jit(grad_step), f"pipe/offload_grad:{gas}")
        return self._compiled_offload_grad[gas]

    def _model_scaled_loss(self, p_c, batch, rng, loss_scale):
        """Scale AT THE SOURCE: the interleaved 1F1B backward runs inside
        module.loss — fp16 cotangents must enter the pipe pre-amplified
        (reference scales the loss before backward; multiplying afterwards
        in the outer vjp would let small fp16 cotangents flush to zero
        inside the scan)."""
        with jax.named_scope("pipe/train_clock"):
            scaled = self.module.loss(p_c, batch, rng, loss_scale=loss_scale)
        return scaled.astype(jnp.float32), scaled / loss_scale

    # the 3-call API is train-schedule-incompatible with pipelining
    # (reference PipelineEngine raises the same way)
    def forward(self, *args, **kwargs):
        raise RuntimeError(
            "PipelineEngine does not support forward(); "
            "use train_batch() / eval_batch() instead")

    def backward(self, *args, **kwargs):
        raise RuntimeError(
            "PipelineEngine does not support backward(); "
            "use train_batch() instead")

    def step(self, *args, **kwargs):
        raise RuntimeError(
            "PipelineEngine does not support step(); "
            "use train_batch() instead")

    # eval_batch is the parent's, with pipelined batch prep: stack a flat
    # batch into an M=1 microbatch dim and keep the leading clock dim
    # (reference ``eval_batch:380``).
    _eval_leading_gas_dim = True

    def _prep_eval_batch(self, batch):
        return self._stack_if_flat(batch)

    def _stack_if_flat(self, batch):
        """Add an M=1 microbatch dim when the caller passed a flat batch."""
        probe = jax.tree_util.tree_leaves(batch)[0]
        ids_ndim = 2  # [B, S] token batches
        if np.ndim(probe) <= ids_ndim:
            return jax.tree_util.tree_map(lambda x: np.asarray(x)[None], batch)
        return batch

    # parity introspection ------------------------------------------------
    def is_pipe_parallel(self):
        return self.num_stages > 1

    def train_schedule(self, stage_id: int = 0) -> TrainSchedule:
        """The instruction stream the compiled program realises for one
        stage (introspection/debugging parity)."""
        return TrainSchedule(micro_batches=self.micro_batches,
                             stages=self.num_stages, stage_id=stage_id)
