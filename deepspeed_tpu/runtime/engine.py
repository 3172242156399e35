"""DeepSpeedEngine — the training engine.

Parity: reference ``runtime/engine.py:189`` (``DeepSpeedEngine``:
``forward:1780``, ``backward:1931``, ``step:2142``, ``_take_model_step:2074``,
``_configure_optimizer:1260``, ``save_checkpoint:3084``, ``load_checkpoint:2724``).

TPU-first redesign
------------------
The reference engine is an imperative coordinator: it wraps ``nn.Module``,
installs gradient hooks, manages buckets/streams, and mutates optimizer state
in place.  Here the whole training step — forward, backward, gradient
accumulation (``lax.scan``), ZeRO collectives, loss-scale automaton, optimizer
update — is ONE jitted SPMD program over the device mesh.  ZeRO placement is
declared by ``ZeroShardingPlan`` and the XLA partitioner materialises the
same all-gather/reduce-scatter schedule the reference hand-codes.

The user-visible API keeps DeepSpeed shape:

    engine, tx, dataloader, lr_sched = deepspeed_tpu.initialize(
        model=loss_fn, model_parameters=params, config=cfg)
    loss = engine(batch)          # forward (computes grads too — functional)
    engine.backward(loss)         # accumulates
    engine.step()                 # applies at gradient-accumulation boundary

or the fused fast path:  ``loss = engine.train_batch(data_iter)``.

The model contract is functional: ``model`` is a callable
``loss_fn(params, batch, rng) -> scalar loss`` (or an object with a
``.loss`` method of the same signature, e.g. our model zoo classes).
"""

import contextlib
import math
import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm as dist
from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.comm.quantize import CommQuantizer
from deepspeed_tpu.monitor.monitor import MonitorMaster
from deepspeed_tpu.monitor.telemetry import (MetricsDrain, get_telemetry,
                                             in_setup_span,
                                             register_compiled)
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import FSDP_AXIS, build_mesh
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.loss_scaler import (HostLossScale, LossScaleState,
                                               dynamic_loss_scale_state,
                                               has_inf_or_nan,
                                               static_loss_scale_state,
                                               update_scale)
from deepspeed_tpu.runtime.lr_schedules import (LRScheduler, build_schedule,
                                                one_cycle_mom)
from deepspeed_tpu.runtime.optimizers import build_optimizer
from deepspeed_tpu.runtime.resilience import (CheckpointTransaction,
                                              CheckpointCorruptError,
                                              DivergenceError,
                                              DivergenceSentinel,
                                              FaultInjector,
                                              PreemptionHandler, RetryPolicy,
                                              TrainingPreempted, COMMITTED,
                                              LEGACY, atomic_write_text,
                                              build_manifest, gc_tags,
                                              poison_tree, retry_io,
                                              scan_tags, validate_tag,
                                              verify_restored)
from deepspeed_tpu.runtime.zero.stage_plan import (OverlapContext,
                                                   ZeroShardingPlan,
                                                   constrain,
                                                   device_put_global,
                                                   overlap_scope,
                                                   plan_reduce_buckets)
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (BACKWARD_GLOBAL_TIMER,
                                       FORWARD_GLOBAL_TIMER,
                                       STEP_GLOBAL_TIMER,
                                       SynchronizedWallClockTimer,
                                       ThroughputTimer)

MEMORY_OPT_ALLREDUCE_SIZE = 500_000_000


@struct.dataclass
class TrainState:
    """The entire mutable training state as one pytree, so a step is a pure
    ``state -> state`` function (the reference spreads this across engine,
    optimizer and scaler objects)."""
    params: Any              # fp32 master params (sharded per plan)
    opt_state: Any           # optax state (sharded per plan)
    loss_scale: LossScaleState
    global_step: jnp.ndarray     # i32
    skipped_steps: jnp.ndarray   # i32
    rng: jax.Array


@struct.dataclass
class StepMetrics:
    loss: jnp.ndarray
    grad_norm: jnp.ndarray
    lr: jnp.ndarray
    loss_scale: jnp.ndarray
    overflow: jnp.ndarray
    # what the model counted on the device this step (int32 vector, one
    # entry a name of ``model.train_counters``; None for a model that
    # counts nothing): the ``train/moe/*`` gauges
    counters: Any = None


def _global_norm_f32(grads) -> jnp.ndarray:
    """``optax.global_norm`` with the square-sum accumulated in fp32 —
    bf16 grad trees (data_types.grad_accum_dtype) would otherwise sum
    millions of squares at 8 mantissa bits.  XLA fuses the cast into the
    reduction; nothing materializes."""
    return optax.global_norm(jax.tree_util.tree_map(
        lambda g: g.astype(jnp.float32), grads))


def moq_anneal_step(state: "TrainState") -> jnp.ndarray:
    """The MoQ anneal clock: the *successful*-step counter.  The reference
    Quantizer only advances qsteps/ratio on non-overflow steps; every
    quantizer.transform call site (train, eval, pipeline) must use this one
    definition or their quantization bits desynchronize."""
    return state.global_step - state.skipped_steps


def _batch_token_count(batch):
    """Tokens per global batch: the size of the first integer leaf (token
    ids).  Dense/regression batches have no integer leaf — returns None and
    throughput telemetry falls back to samples/s."""
    for leaf in jax.tree_util.tree_leaves(batch):
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            return int(np.prod(leaf.shape))
    return None


class DeepSpeedEngine:

    @in_setup_span("setup/engine", kind="train")
    def __init__(self,
                 model: Callable,
                 config: DeepSpeedConfig,
                 params: Any = None,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 lr_scheduler=None,
                 mesh=None,
                 tp_rules=None,
                 dont_change_device=False,
                 collate_fn=None,
                 training_data=None):
        self.module = model
        self.loss_fn = self._resolve_loss_fn(model)
        # what the model counts on the device beside its loss
        # (``model.loss(counted=True)``; models/transformer.py
        # TRAIN_COUNTERS): outputs of the fused train step
        self._train_counters = tuple(getattr(model, "train_counters", ()))
        self._config = config
        self.accelerator = get_accelerator()

        dist.init_distributed()
        dist.configure(config)

        # ---- mesh / topology -----------------------------------------
        if mesh is None:
            mesh = groups.initialize_mesh(config.mesh_config)
        else:
            groups.initialize_mesh(mesh=mesh)
        self.mesh = mesh

        # ---- precision ----------------------------------------------
        if config.bfloat16_enabled:
            self.compute_dtype = jnp.bfloat16
        elif config.fp16_enabled:
            self.compute_dtype = jnp.float16
        else:
            self.compute_dtype = jnp.float32
        # grad tree / GAS-carry dtype (reference data_types.grad_accum_dtype,
        # runtime/config.py:943).  bf16 halves grad HBM; norms and the Adam
        # math still run fp32 (optimizers._scale_by_adam_dtyped upcasts).
        self.grad_accum_dtype = jnp.dtype(
            config.grad_accum_dtype or "float32")

        # ---- ZeRO plan ----------------------------------------------
        # auto-TP: a model that ships its own sharding rules (the whole
        # model zoo does) gets them applied without the caller plumbing
        # them through — the reference's module_inject auto-TP behaviour
        if tp_rules is None and hasattr(model, "tp_rules"):
            tp_rules = model.tp_rules()
        zc = config.zero_config
        self.zero_stage = zc.stage
        self.plan = ZeroShardingPlan(
            mesh, stage=zc.stage, tp_rules=tp_rules,
            param_persistence_threshold=(zc.param_persistence_threshold
                                         if zc.stage >= 3 else 0),
            offload_optimizer=zc.offload_optimizer_device != "none",
            offload_param=zc.offload_param_device != "none")

        # explicit comm/compute overlap (zero_optimization.overlap):
        # stage-3 forward gather pipeline (layer_scan, installed around
        # step tracing by _overlap_scope) + bucketed grad reduce-scatter
        # (_reduce_grads).  Disabled configs route through the exact
        # serial code — bit-for-bit the seed step.
        ov = getattr(zc, "overlap", None)
        self._overlap_cfg = ov
        self._overlap_enabled = bool(ov is not None and ov.enabled)
        self._overlap_ctx = None
        if self._overlap_enabled and zc.stage >= 3:
            self._overlap_ctx = OverlapContext(
                gather_prefetch_depth=ov.gather_prefetch_depth,
                param_persistence_threshold=(
                    self.plan.param_persistence_threshold),
                spec_fn=self.plan._tp_spec_for,
                on_gather=self._census_param_gather)
        self._rs_buckets = 0

        # ---- optimizer ----------------------------------------------
        self.client_optimizer = optimizer
        self.optimizer_name_ = (config.optimizer_config.type.lower()
                                if config.optimizer_config and config.optimizer_config.type
                                else None)
        self.tx, self._base_lr, self._schedule_fn = self._configure_optimizer(
            optimizer, lr_scheduler)
        self.lr_scheduler = (lr_scheduler if not callable(self._schedule_fn) or
                             isinstance(lr_scheduler, LRScheduler) else None)
        if self.lr_scheduler is None and self._schedule_fn is not None:
            self.lr_scheduler = LRScheduler(self._schedule_fn)

        # ---- state init / placement ---------------------------------
        if params is None:
            raise ValueError("model_parameters (a params pytree) is required")
        self.state = self._init_state(params)

        # ---- host-side bookkeeping ----------------------------------
        self.micro_steps = 0
        self.global_steps = int(self.state.global_step)
        self.skipped_steps = 0
        self.gradient_accumulation_steps_ = config.gradient_accumulation_steps
        self._cached = None  # (loss, grads, overflow) from forward
        self._accum_grads = None
        self._accum_count = 0
        self._step_applied = False
        self._global_grad_norm = 0.0

        # activation checkpointing knobs (reference _configure_checkpointing)
        from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
        checkpointing.configure(deepspeed_config=config)

        # curriculum seqlen (reference engine.py:1820-1826) + PLD (:1646)
        self.curriculum_scheduler_ = None
        cl_cfg = config.curriculum_learning_config
        if cl_cfg.get("enabled", False):
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler \
                import CurriculumScheduler
            self.curriculum_scheduler_ = CurriculumScheduler(cl_cfg)
        self.progressive_layer_drop = None
        pld_cfg = config.progressive_layer_drop_config
        if pld_cfg.get("enabled", False):
            from deepspeed_tpu.runtime.progressive_layer_drop import \
                ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=pld_cfg.get("theta", 0.5),
                gamma=pld_cfg.get("gamma", 0.001))

        # compression (reference engine.py:1401 compression_scheduler hookup)
        self._compression = None
        self.compression_scheduler = None
        # MoQ: step-time annealed weight quantization (reference
        # engine.py:1319 _configure_quantization + :1799 quantize call)
        self.quantizer = None
        if config.compression_config:
            from deepspeed_tpu.compression import (CompressionScheduler,
                                                   init_compression)
            from deepspeed_tpu.runtime.quantize import \
                build_quantizer_from_config
            self.quantizer = build_quantizer_from_config(
                config.compression_config)
            if self.quantizer is not None:
                self.quantizer.attach(self.state.params,
                                      self.quantizer.groups_cfg or None)
            spec = init_compression(model, config,
                                    tp_rules=self.plan.tp_rules,
                                    mesh=self.mesh)
            if self.quantizer is not None:
                # MoQ owns weight quantization: drop it from the in-forward
                # compression path so weights aren't quantized twice
                from deepspeed_tpu.compression.config import \
                    WEIGHT_QUANTIZATION
                spec.groups = [g for g in spec.groups
                               if g.method != WEIGHT_QUANTIZATION]
            if spec.config.enabled and spec.groups:
                self._compression = spec
                self.compression_scheduler = CompressionScheduler(spec)

        # async step pipeline (config "async_pipeline"): prefetched input
        # feed + deferred metric readback.  When on, nothing in the steady
        # hot loop may block on the device — the throughput timer trusts
        # host wall-clock instead of issuing a per-step barrier.
        ap = config.async_pipeline_config
        self._async_enabled = bool(ap.enabled)
        self._prefetcher = None       # engine-owned DevicePrefetchIterator
        self._prefetch_source = None  # the caller iterator it wraps
        self._default_iter = None     # persistent no-arg train_batch iter
        self._host_lr_cache = None    # (step, float lr)
        fc = config.fp16_config
        if config.fp16_enabled and config.dynamic_loss_scale:
            self._host_ls = HostLossScale(
                config.initial_dynamic_scale, dynamic=True,
                scale_window=fc.loss_scale_window,
                min_scale=fc.min_loss_scale, hysteresis=fc.hysteresis)
        else:
            self._host_ls = HostLossScale(
                config.loss_scale if config.fp16_enabled else 1.0,
                dynamic=False)

        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size,
            steps_per_output=config.steps_per_print,
            sync=not self._async_enabled)
        # unified telemetry spine (monitor/telemetry.py): configure the
        # process-global sink BEFORE MonitorMaster so its JSONL fourth
        # writer attaches to the same stream
        tc = config.telemetry_config
        self.telemetry = get_telemetry().configure(tc)
        self._tel_enabled = self.telemetry.enabled
        # quantized-collective wire codec (comm/quantize.py, the
        # "comm.quantization" block): policy for the ZeRO grad reduction;
        # world-size and per-leaf gating happen at trace time
        self.comm_quant = CommQuantizer.from_config(
            getattr(config, "comm_quantization", None))
        # deferred metric readback: device scalars queue here; readback is
        # one batched device_get per sync_interval (or a drainer thread)
        self._metrics_drain = None
        if self._tel_enabled:
            self._metrics_drain = MetricsDrain(
                self._drain_emit,
                sync_interval=ap.sync_interval if self._async_enabled else 1,
                use_thread=self._async_enabled and ap.drain_thread)
        # profiling plane (monitor/profiling.py): compile tracing + HBM
        # attribution + live roofline; None unless telemetry.profiling.enabled
        self._profiling = self.telemetry.profiling
        # the telemetry's watchdog judges every step and samples a late one
        # whether or not telemetry is enabled (monitor/telemetry.py "the
        # steps that run long"); enabled, it also gives the hang verdict on
        # the heartbeats and, in distributed mode, runs the cross-rank
        # straggler sweep over the shard aggregator (rank 0 owns one)
        hangs = bool(self._tel_enabled and tc.stall_watchdog)
        watchdog = self.telemetry.watchdog.configure(
            hangs=hangs, stall_factor=tc.stall_factor,
            poll_interval_secs=tc.stall_poll_secs,
            min_stall_secs=tc.stall_min_secs,
            cluster=self.telemetry.cluster).start()
        self._watchdog = watchdog if hangs else None
        self._last_batch_tokens = None
        # the flash kernels' plan of one step (``train/attn/*`` gauges):
        # None until the first batch shows its shape, then a dict, empty
        # for a model without one
        self._attn_plan = None
        # live MFU: analytic per-step model flops (set once the flops
        # profiler has run) / measured step time / device-peak ceiling
        self._analytic_step_flops = None
        self._analytic_step_bytes = None
        self._mfu_peak_flops = None
        # fault-tolerance layer (config "resilience", runtime/resilience.py):
        # durable checkpoint transactions + retry policy are always wired
        # (rc.enabled gates the durable protocol); preemption handler and
        # divergence sentinel are opt-in.  The fault injector is explicit
        # plumbing — engine-owned, handed to the prefetch worker and the
        # checkpoint paths — never process-global, so tests stay isolated.
        rc = config.resilience_config
        self._resilience = rc
        self._injector = FaultInjector.from_config(rc.fault_injection)
        self._retry_policy = RetryPolicy.from_config(rc)
        self._last_good_ckpt = None   # (dir, tag) of last committed/loaded
        self._preempt = None
        if rc.preemption_handler:
            self._preempt = PreemptionHandler(
                telemetry=self.telemetry).install()
        self._sentinel = None
        if rc.divergence_sentinel:
            self._sentinel = DivergenceSentinel(
                max_consecutive_skips=rc.max_consecutive_skips,
                interval=rc.sentinel_interval,
                action=rc.on_divergence,
                telemetry=self.telemetry)
        # resolve the process checkpoint engine from config (sync orbax vs
        # async Nebula-style) — save/load then use whatever is current, so
        # set_checkpoint_engine() overrides still stick
        from deepspeed_tpu.runtime.checkpoint_engine import \
            get_checkpoint_engine
        get_checkpoint_engine(config)
        self.monitor = MonitorMaster(config.monitor_config)
        if self._tel_enabled:
            self.telemetry.emit(
                "meta", "engine/init",
                attrs={"zero_stage": self.zero_stage,
                       "dtype": self.compute_dtype.__name__,
                       "mesh": {k: int(v) for k, v in self.mesh.shape.items()},
                       "micro_batch": config.train_micro_batch_size_per_gpu,
                       "gas": config.gradient_accumulation_steps,
                       "train_batch": config.train_batch_size})

        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(
                training_data, collate_fn=collate_fn)

        # compiled-step caches are keyed on gas: a later call with a
        # different gas must not silently reuse a closure over a stale one
        self._compiled_train_step = {}
        self._compiled_offload_grad = {}
        self._compiled_fwd_bwd = None
        self._compiled_apply = None
        self._batch_ndim = None

        log_dist(
            f"DeepSpeedEngine ready: zero_stage={self.zero_stage} "
            f"dtype={self.compute_dtype.__name__} mesh={dict(self.mesh.shape)} "
            f"micro_batch={config.train_micro_batch_size_per_gpu} "
            f"gas={config.gradient_accumulation_steps} "
            f"train_batch={config.train_batch_size}", ranks=[0])

    # ------------------------------------------------------------------
    # setup helpers
    # ------------------------------------------------------------------
    @staticmethod
    def _resolve_loss_fn(model):
        if hasattr(model, "loss") and callable(model.loss):
            return model.loss
        if callable(model):
            return model
        raise TypeError(
            "model must be callable loss_fn(params, batch, rng) or expose "
            "a .loss method")

    def _configure_optimizer(self, client_optimizer, client_scheduler):
        """Parity: reference ``_configure_optimizer:1260`` /
        ``_configure_basic_optimizer:1321`` — config-named optimizer takes
        precedence; a client optax transform is used as-is."""
        cfg = self._config
        schedule_fn = None
        base_lr = 0.0
        if cfg.scheduler_config and cfg.scheduler_config.type:
            schedule_fn = build_schedule(cfg.scheduler_config.type,
                                         cfg.scheduler_config.params)
        elif isinstance(client_scheduler, LRScheduler):
            schedule_fn = client_scheduler.schedule_fn
        elif callable(client_scheduler):
            schedule_fn = client_scheduler

        config_opt_name = (cfg.optimizer_config.type
                           if cfg.optimizer_config else None)
        if config_opt_name:
            opt_params = dict(cfg.optimizer_config.params)
            base_lr = opt_params.get("lr", 1e-3)
            if schedule_fn is not None:
                opt_params["lr"] = schedule_fn
            # 1Cycle momentum cycling (reference OneCycle cycles optimizer
            # momentum inversely to lr) — adam-family only
            if (cfg.scheduler_config and cfg.scheduler_config.type ==
                    "OneCycle" and config_opt_name.lower() in
                    ("adam", "adamw", "fusedadam", "cpuadam")):
                mom_fn = one_cycle_mom(cfg.scheduler_config.params)
                if mom_fn is not None:
                    opt_params["_b1_schedule"] = mom_fn
            try:
                tx = build_optimizer(config_opt_name, opt_params)
            except ValueError:
                if client_optimizer is None:
                    raise
                logger.warning(
                    f"optimizer '{config_opt_name}' is not built in; using "
                    "the client-supplied optax transform instead")
                tx = client_optimizer
        elif client_optimizer is not None:
            tx = client_optimizer
            if schedule_fn is not None and cfg.scheduler_config:
                logger.warning("scheduler config ignored: client optimizer "
                               "owns its learning rate")
        else:
            # reference requires an optimizer for training; default AdamW so
            # inference-ish uses of the engine still construct
            tx = optax.adamw(1e-3)
            base_lr = 1e-3

        if self._config.gradient_clipping and self._config.gradient_clipping > 0:
            clip = float(self._config.gradient_clipping)

            def clip_f32(updates, state, params=None):
                del params
                norm = _global_norm_f32(updates)   # fp32 even for bf16 grads
                coef = jnp.minimum(1.0, clip / (norm + 1e-6))
                return jax.tree_util.tree_map(
                    lambda g: (g * coef.astype(g.dtype)), updates), state
            tx = optax.chain(
                optax.GradientTransformation(
                    lambda _: optax.EmptyState(), clip_f32), tx)
        if schedule_fn is None:
            schedule_fn = lambda step: jnp.asarray(base_lr, jnp.float32)  # noqa: E731
        return tx, base_lr, schedule_fn

    @in_setup_span("setup/engine/state")
    def _init_state(self, params) -> TrainState:
        cfg = self._config
        zc = cfg.zero_config
        # ZeRO-Offload / ZeRO-Infinity: optimizer lives on the host (and
        # optionally NVMe); device keeps compute-dtype params only.
        # With offload_param the PARAMS live on the host too and stream
        # per-layer (runtime/zero/param_stream.py) — the full model never
        # resides in HBM.
        self._offload = None
        self._param_stream = None
        if zc.offload_param_device != "none":
            return self._init_param_stream_state(params)
        if zc.offload_optimizer_device != "none":
            return self._init_offload_state(params)
        # master params in fp32 (reference: fp16/bf16 optimizers keep fp32
        # master copies; we ONLY store the master and cast per-step).
        # jnp.array (copy) rather than asarray: the train step donates the
        # state, and an aliased no-copy view would delete the caller's arrays.
        params = jax.tree_util.tree_map(
            lambda x: jnp.array(x, jnp.float32), params)

        if cfg.fp16_enabled:
            if cfg.dynamic_loss_scale:
                ls = dynamic_loss_scale_state(
                    cfg.fp16_config.initial_scale_power,
                    hysteresis=cfg.fp16_config.hysteresis)
            else:
                ls = static_loss_scale_state(cfg.loss_scale)
        else:
            ls = static_loss_scale_state(1.0)

        param_sh = self.plan._to_sharding(self.plan.master_param_specs(params))
        with self.mesh:
            params = device_put_global(params, param_sh)
            opt_state = jax.jit(
                self.tx.init,
                out_shardings=self.plan.opt_state_shardings(self.tx, params),
            )(params)
        repl = self.plan.replicated_sharding()
        seed = cfg.seed
        with self.mesh:
            # jit (not device_put): builds replicated state on multi-host
            # meshes where device_put can't target non-addressable devices
            rng, step0, skip0 = jax.jit(
                lambda: (jax.random.key(seed), jnp.asarray(0, jnp.int32),
                         jnp.asarray(0, jnp.int32)),
                out_shardings=repl)()
        ls = device_put_global(
            ls, jax.tree_util.tree_map(lambda _: repl, ls))
        return TrainState(
            params=params, opt_state=opt_state, loss_scale=ls,
            global_step=step0, skipped_steps=skip0, rng=rng)

    def _init_param_stream_state(self, params) -> TrainState:
        """ZeRO-Infinity parameter offload: host master params + moments,
        double-buffered per-layer device streaming
        (``runtime/zero/param_stream.py``).  Max trainable params/chip is
        bounded by HOST memory, not HBM — the reference's
        ``zero.Init(remote_device="cpu"/"nvme")`` capability
        (``partition_parameters.py:539``)."""
        from deepspeed_tpu.runtime.zero.param_stream import ParamStreamRunner
        cfg = self._config
        if jax.process_count() > 1:
            # multi-host: the host store is REPLICATED per process (grads
            # come back fully-replicated from the layer programs — XLA
            # all-reduces over ICI — so every process lands identical
            # grads and applies the identical deterministic update).
            # Host RAM cost is the full model per host; the reference
            # shards its CPU partitions instead, a documented trade.
            log_dist("param-stream multi-host: host master/moments are "
                     "replicated per process (full model per host)",
                     ranks=[0])
        if cfg.compression_config:
            raise NotImplementedError(
                "compression/MoQ does not compose with offload_param "
                "streaming yet")
        opt_name = self.optimizer_name_ or "adamw"
        supported = {"adam", "adamw", "fusedadam", "cpuadam", "adagrad"}
        if opt_name not in supported:
            raise ValueError(
                f"offload_param supports {sorted(supported)}; got "
                f"'{opt_name}' (reference: ZeRO-Offload requires "
                "DeepSpeedCPUAdam/Adagrad)")
        opt_params = (dict(cfg.optimizer_config.params)
                      if cfg.optimizer_config else {})
        self._param_stream = ParamStreamRunner(
            self.module, params, cfg, self.mesh, self.plan,
            compute_dtype=self.compute_dtype,
            grad_accum_dtype=self.grad_accum_dtype,
            opt_name=opt_name, opt_params=opt_params)
        log_dist(
            f"param-stream offload: {self._param_stream.store.num_params():,}"
            f" params host-resident, {self._param_stream.n_layers} layers "
            f"streamed ({self._param_stream.resident_layers} pinned), "
            f"device={cfg.zero_config.offload_param_device}", ranks=[0])
        if cfg.fp16_enabled and cfg.dynamic_loss_scale:
            ls = dynamic_loss_scale_state(
                cfg.fp16_config.initial_scale_power,
                hysteresis=cfg.fp16_config.hysteresis)
        elif cfg.fp16_enabled:
            ls = static_loss_scale_state(cfg.loss_scale)
        else:
            ls = static_loss_scale_state(1.0)
        repl = self.plan.replicated_sharding()
        seed = cfg.seed
        with self.mesh:
            rng, step0, skip0 = jax.jit(
                lambda: (jax.random.key(seed), jnp.asarray(0, jnp.int32),
                         jnp.asarray(0, jnp.int32)),
                out_shardings=repl)()
        return TrainState(
            params=(), opt_state=(),
            loss_scale=device_put_global(
                ls, jax.tree_util.tree_map(lambda _: repl, ls)),
            global_step=step0, skipped_steps=skip0, rng=rng)

    def _init_offload_state(self, params) -> TrainState:
        """ZeRO-Offload mode state: host master + moments (see
        ``runtime/zero/offload.py``), device params in compute dtype."""
        from deepspeed_tpu.runtime.zero.offload import (HostOffloadOptimizer,
                                                        ShardedFlatLayout)
        cfg = self._config
        multihost = jax.process_count() > 1
        if multihost and cfg.zero_config.stage < 3:
            raise NotImplementedError(
                "multi-host offload_optimizer needs ZeRO stage 3: each "
                "process updates only the fsdp shards it can address, which "
                "requires params and grads to share the fsdp partition")
        opt_name = self.optimizer_name_ or "adamw"
        supported = {"adam", "adamw", "fusedadam", "cpuadam", "adagrad"}
        if opt_name not in supported:
            raise ValueError(
                f"offload_optimizer supports {sorted(supported)}; got "
                f"'{opt_name}' (reference: ZeRO-Offload requires "
                "DeepSpeedCPUAdam/Adagrad)")
        opt_params = (dict(cfg.optimizer_config.params)
                      if cfg.optimizer_config else {})
        if multihost:
            # per-host partition: fp32 copy placed with the GRAD sharding
            # (== param sharding at stage 3); each process's master covers
            # exactly its addressable shards (reference: per-DP-rank fp32
            # flat partitions, stage3.py).  The fp32 tree stays on HOST —
            # device_put_global's callback hands each device its slice, so
            # the full unsharded fp32 model never lands on one chip.
            def _host_fp32(x):
                h = np.asarray(jax.device_get(x))
                return h.astype(np.float32) \
                    if jnp.issubdtype(h.dtype, jnp.floating) else h
            fp32 = jax.tree_util.tree_map(_host_fp32, params)
            grad_sh = self.plan._to_sharding(self.plan.grad_specs(fp32))
            with self.mesh:
                fp32 = device_put_global(fp32, grad_sh)
            self._offload = HostOffloadOptimizer(
                fp32, cfg.zero_config, opt_name=opt_name,
                opt_params=opt_params, layout=ShardedFlatLayout(fp32),
                rank=jax.process_index(), world_size=jax.process_count())
            self._offload_sharded = True
            del fp32
        else:
            host_params = jax.tree_util.tree_map(
                lambda x: (np.asarray(x, np.float32)
                           if jnp.issubdtype(np.asarray(x).dtype,
                                             jnp.floating)
                           else np.asarray(x)), params)
            self._offload = HostOffloadOptimizer(
                host_params, cfg.zero_config, opt_name=opt_name,
                opt_params=opt_params,
                rank=jax.process_index(), world_size=jax.process_count())
            self._offload_sharded = False

        if cfg.fp16_enabled and cfg.dynamic_loss_scale:
            ls = dynamic_loss_scale_state(
                cfg.fp16_config.initial_scale_power,
                hysteresis=cfg.fp16_config.hysteresis)
        elif cfg.fp16_enabled:
            ls = static_loss_scale_state(cfg.loss_scale)
        else:
            ls = static_loss_scale_state(1.0)

        dev_params = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, self.compute_dtype)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
            else jnp.asarray(x), params)
        param_sh = self.plan._to_sharding(self.plan.param_specs(dev_params))
        with self.mesh:
            dev_params = device_put_global(dev_params, param_sh)
        self._offload_param_sh = param_sh
        repl = self.plan.replicated_sharding()
        seed = cfg.seed
        with self.mesh:
            rng, step0, skip0 = jax.jit(
                lambda: (jax.random.key(seed), jnp.asarray(0, jnp.int32),
                         jnp.asarray(0, jnp.int32)),
                out_shardings=repl)()
        return TrainState(
            params=dev_params, opt_state=(),
            loss_scale=device_put_global(
                ls, jax.tree_util.tree_map(lambda _: repl, ls)),
            global_step=step0, skipped_steps=skip0, rng=rng)

    # ------------------------------------------------------------------
    # the compiled step
    # ------------------------------------------------------------------
    def _loss_and_grads(self, params, loss_scale, batch, rng, step=None,
                        qstep=None):
        """value_and_grad of the (possibly loss-scaled) compute-dtype loss:
        ``(loss, grads, counters)``, the last the model's own counters of
        the micro-batch (``model.loss(counted=True)``), None for a model
        that counts nothing.

        ``qstep`` is the MoQ anneal clock — the *successful*-step counter
        (global_step - skipped_steps), because the reference Quantizer skips
        qsteps/ratio advancement on fp16 overflow steps (its quantize() is
        only called from a non-overflow step path).  Compression scheduling
        stays on the raw global step like the reference scheduler."""
        if qstep is None:
            qstep = step

        # step-phase scopes (telemetry.op_scopes reads them back from the
        # compiled text): everything traced under ``fwd`` is the forward;
        # JAX itself marks its backward (``transpose(jvp(..))``) and its
        # recompute (``rematted_computation``)
        def scaled_loss(p):
            with jax.named_scope("fwd"):
                p_c = self._transformed_compute_params(p, rng, step, qstep)
                if self._train_counters:
                    loss, counters = self.loss_fn(p_c, batch, rng,
                                                  counted=True)
                    return (loss * loss_scale).astype(jnp.float32), \
                        (loss, counters)
                scaled, loss = self._model_scaled_loss(p_c, batch, rng,
                                                       loss_scale)
                return scaled, (loss, None)

        (_, (loss, counters)), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params)
        # unscale in fp32, then store at grad_accum_dtype (XLA fuses the
        # round-trip; bf16 storage halves the grad tree / GAS carry)
        with jax.named_scope("bwd"):
            grads = jax.tree_util.tree_map(
                lambda g: (g.astype(jnp.float32) / loss_scale).astype(
                    self.grad_accum_dtype), grads)
        return loss, grads, counters

    def _transformed_compute_params(self, p, rng, step, qstep):
        """Compute-dtype view of the params with the cast-site transforms
        (compression STE, MoQ straight-through) applied."""
        p_c = jax.tree_util.tree_map(
            lambda x: x.astype(self.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, p)
        if self._compression is not None and step is not None:
            p_c = self._compression.transform(p_c, step)
        if self.quantizer is not None and step is not None:
            # MoQ: forward sees Q(w) from the schedule_offset step on —
            # the cast-site equivalent of the reference's post-step
            # quantization of the fp16 weight copy (engine.py:1799).
            # Straight-through: the reference evaluates grads at Q(w) but
            # applies them to the unquantized master, i.e. identity
            # backward — without this, d(round)/dx = 0 kills training.
            q_c = self.quantizer.transform(
                p_c, qstep, rng=jax.random.fold_in(rng, 0x4D6F51),
                schedule_offset=self.quantizer.schedule_offset)
            p_c = jax.tree_util.tree_map(
                lambda x, q: x + jax.lax.stop_gradient(q - x), p_c, q_c)
        return p_c

    def _model_scaled_loss(self, p_c, batch, rng, loss_scale):
        """Hook: (scaled fp32 loss, unscaled loss).  PipelineEngine
        overrides this to scale AT THE SOURCE inside the interleaved 1F1B
        backward — fp16 cotangents must ride the pipe pre-amplified, like
        the reference scales the loss before backward."""
        loss = self.loss_fn(p_c, batch, rng)
        return (loss * loss_scale).astype(jnp.float32), loss

    @jax.named_scope("optimizer")
    def _apply_update(self, state: TrainState, grads, overflow):
        """Shared optimizer-update tail: clip (inside tx), skip-on-overflow,
        re-constrain placements, loss-scale automaton.  Used by both the fused
        train step and the 3-call ``step()`` so the semantics cannot diverge.
        (Reference analogue: ``_take_model_step:2074`` +
        ``_overflow_check_and_loss_scale_update:1840``.)"""
        cfg = self._config
        grad_norm = _global_norm_f32(grads)
        updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)

        def pick(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(overflow, o, n), new, old)
        new_params = pick(new_params, state.params)
        new_opt = pick(new_opt, state.opt_state)
        new_params = constrain(new_params,
                               self.plan.master_param_specs(state.params),
                               self.mesh)
        new_ls = update_scale(
            state.loss_scale, overflow,
            dynamic=cfg.fp16_enabled and cfg.dynamic_loss_scale,
            scale_window=cfg.fp16_config.loss_scale_window,
            min_scale=cfg.fp16_config.min_loss_scale,
            hysteresis=cfg.fp16_config.hysteresis)
        new_state = TrainState(
            params=new_params, opt_state=new_opt, loss_scale=new_ls,
            global_step=state.global_step + 1,
            skipped_steps=state.skipped_steps + overflow.astype(jnp.int32),
            rng=state.rng)
        return new_state, grad_norm

    def _census_grad_reduce(self, grads, bytes_saved=0):
        """Trace-time comm census for the ZeRO gradient reduction.

        The engine never calls a ``dist.*`` verb for grad sync — the
        grad-spec constraint makes the XLA partitioner insert the
        cross-device reduction — so without this record the single
        largest communicator in training is invisible to the comm plane
        (ROADMAP item 3's bytes-saved gauges hook in exactly here).
        Payload bytes are dtype-TRUE: ``size * itemsize`` at the grad
        tree's actual dtypes (works on tracers — aval shape/dtype), never
        an element count.  Stage >= 2 shards the reduction
        (reduce-scatter semantics); stages 0/1 land replicated grads
        (all-reduce).  Runs at trace time like every comm census.

        Quantized runs (``comm.quantization``) pass ``bytes_saved`` so
        the record books the reduced WIRE payload (int8 codes + fp32
        scales) with ``wire_dtype="int8"`` — the busbw tables then show
        the saved traffic instead of misreporting full-precision bytes."""
        if not self._tel_enabled:
            return
        world = groups.get_data_parallel_world_size()
        if world <= 1:
            return
        leaves = jax.tree_util.tree_leaves(grads)
        nbytes = sum(int(g.size) * np.dtype(g.dtype).itemsize for g in leaves)
        op = "reduce_scatter" if self.zero_stage >= 2 else "all_reduce"
        saved = int(bytes_saved)
        dist.comms_logger.append(op, nbytes - saved if saved else nbytes,
                                 "fsdp",
                                 dtype=str(leaves[0].dtype) if leaves else None,
                                 world=world,
                                 wire_dtype="int8" if saved else None,
                                 bytes_saved=saved if saved else None)

    def _quantize_grad_wire(self, grads):
        """Apply the ``comm.quantization`` wire codec to the ZeRO grad
        reduction at trace level.  The engine never calls a ``dist.*``
        verb here — XLA inserts the physical collective from the grad
        spec — so the codec is modelled as a blockwise int8 QDQ of the
        reduced gradient (exactly the phase-2 re-quantization of the
        two-phase EQuARX collective in comm/quantize.py; the phase-1
        per-rank error averages down by 1/world).  Returns
        ``(grads, bytes_saved)``; disabled configs return the tree
        untouched (bit-for-bit the unquantized path)."""
        q = self.comm_quant
        if not q.active():
            return grads, 0
        if groups.get_data_parallel_world_size() <= 1:
            return grads, 0
        op = "reduce_scatter" if self.zero_stage >= 2 else "all_reduce"
        return q.qdq_tree(grads, op)

    def _census_param_gather(self, nbytes, n_layers):
        """Trace-time comm census for the layer_scan gather pipeline: the
        explicit per-layer all-gathers of the stage-3 forward, booked once
        per traced scan (``n_layers`` layer working sets, ``nbytes``
        total) like every comm census.  Without this the overlap layer's
        dominant forward collective would be invisible to the busbw
        tables that the exposed-comm win is booked through."""
        if not self._tel_enabled:
            return
        world = int(self.mesh.shape.get(FSDP_AXIS, 1))
        if world <= 1:
            return
        dist.comms_logger.append("all_gather", int(nbytes), "fsdp",
                                 world=world)

    def _overlap_scope(self):
        """Context installing the gather-pipeline OverlapContext for the
        duration of a step-builder call.  The with-block runs at TRACE
        time inside jit, so wrapping the step body covers every trace and
        retrace; serial configs get a null context and the models'
        ``layer_scan`` collapses to the seed ``jax.lax.scan``."""
        if self._overlap_ctx is None:
            return contextlib.nullcontext()
        return overlap_scope(self._overlap_ctx)

    def _reduce_grads(self, grads, params):
        """The ZeRO gradient reduction: placement constraint (XLA lowers
        it to reduce-scatter / all-reduce), optional wire quantization,
        comm census.  One site for all three step builders so the
        semantics cannot diverge.

        Serial (``overlap.enabled=false``): whole-tree constrain + QDQ +
        one census record — exactly the seed lines, bit-for-bit.

        Overlapped: the tree is flushed in ``rs_bucket_bytes`` buckets in
        REVERSE flatten order (last layers' grads are final first during
        backward), an ``optimization_barrier`` chain pinning each
        bucket's reduction after the previous one, so the reductions
        issue under the backward tail instead of piling up after it.
        Constraint and codec are per-leaf in both paths, so bucketing
        changes collective ISSUE ORDER and census granularity only —
        values are bit-identical to the serial reduction.  Composes with
        ``comm.quantization``: each bucket rides the int8 wire, so the
        quantized window is the one being overlapped."""
        ov = self._overlap_cfg
        if not self._overlap_enabled:
            grads = constrain(grads, self.plan.grad_specs(params), self.mesh)
            grads, wire_saved = self._quantize_grad_wire(grads)
            self._census_grad_reduce(grads, bytes_saved=wire_saved)
            return grads
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        spec_leaves = treedef.flatten_up_to(self.plan.grad_specs(params))
        buckets = plan_reduce_buckets(leaves, ov.rs_bucket_bytes)
        self._rs_buckets = len(buckets)
        q = self.comm_quant
        quantize = (q.active()
                    and groups.get_data_parallel_world_size() > 1)
        op = "reduce_scatter" if self.zero_stage >= 2 else "all_reduce"
        out = list(leaves)
        prev = None
        for bucket in buckets:
            sub = [jax.lax.with_sharding_constraint(
                out[i], NamedSharding(self.mesh, spec_leaves[i]))
                for i in bucket]
            if prev is not None:
                # data-dependence chain: this bucket's reduction may not
                # be hoisted ahead of the previous (later-layer) bucket's
                tied = jax.lax.optimization_barrier(tuple(sub) + prev)
                sub = list(tied[:len(sub)])
            saved = 0
            if quantize:
                sub, saved = q.qdq_tree(sub, op)
                sub = list(sub)
            self._census_grad_reduce(sub, bytes_saved=saved)
            for j, i in enumerate(bucket):
                out[i] = sub[j]
            prev = tuple(sub)
        return jax.tree_util.tree_unflatten(treedef, out)

    def _finish_step(self, state: TrainState, loss, grads, rng,
                     counters=None):
        """Shared train-step tail: grad placement constraint, overflow
        check, optimizer update, metrics.  Used by both the dense and the
        pipeline engines so their semantics cannot diverge."""
        with jax.named_scope("grad_reduce"):
            grads = self._reduce_grads(grads, state.params)
        fp16 = self._config.fp16_enabled
        with jax.named_scope("optimizer"):
            overflow = has_inf_or_nan(grads) if fp16 else jnp.asarray(False)
        new_state, grad_norm = self._apply_update(
            state.replace(rng=rng), grads, overflow)
        with jax.named_scope("optimizer"):
            metrics = StepMetrics(
                loss=loss.astype(jnp.float32),
                grad_norm=grad_norm.astype(jnp.float32),
                lr=jnp.asarray(self._schedule_fn(state.global_step),
                               jnp.float32),
                loss_scale=new_state.loss_scale.cur_scale,
                overflow=overflow, counters=counters)
        return new_state, metrics

    def _forward_grads(self, params, scale, step_rng, batch, gas: int,
                       step=None, qstep=None):
        """GAS microbatch accumulation (``lax.scan``) shared by the fused and
        the offload step builders (reference: one grad-accumulation semantic,
        ``backward:1931`` scaling by 1/GAS): ``(loss, grads, counters)``,
        the model's counters merged over the micro-batches, or None."""
        if gas > 1:
            def micro(carry, inp):
                idx, mb = inp
                acc, rloss, counters = carry
                mb_rng = jax.random.fold_in(step_rng, idx)
                loss, grads, counted = self._loss_and_grads(
                    params, scale, mb, mb_rng, step=step, qstep=qstep)
                with jax.named_scope("bwd"):
                    acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                if counted is not None:
                    counters = self.module.merge_train_counters(counters,
                                                                counted)
                return (acc, rloss + loss, counters), None

            zeros = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, self.grad_accum_dtype), params)
            uncounted = jnp.zeros((len(self._train_counters),), jnp.int32) \
                if self._train_counters else None
            (gsum, lsum, counters), _ = jax.lax.scan(
                micro, (zeros, jnp.float32(0.0), uncounted),
                (jnp.arange(gas), batch))
            with jax.named_scope("bwd"):
                grads = jax.tree_util.tree_map(lambda g: g / gas, gsum)
            return lsum / gas, grads, counters
        return self._loss_and_grads(params, scale, batch, step_rng, step=step,
                                    qstep=qstep)

    def _build_train_step(self, gas: int):
        cfg = self._config
        fp16 = cfg.fp16_enabled

        def train_step(state: TrainState, batch):
            # the with-block runs at trace time, so the gather pipeline
            # is live for exactly this trace (and every retrace)
            with self._overlap_scope():
                scale = (state.loss_scale.cur_scale if fp16
                         else jnp.float32(1.0))
                rng, step_rng = jax.random.split(state.rng)
                # a model that counts its own work (``train_counters``)
                # hands the counts out beside the loss: outputs of this
                # program, read with the step's other metrics
                loss, grads, counters = self._forward_grads(
                    state.params, scale, step_rng, batch, gas,
                    step=state.global_step,
                    qstep=moq_anneal_step(state))
                # ZeRO grad placement: stage>=2 spec is fsdp-sharded → XLA
                # lowers the DP reduction as reduce-scatter (reference
                # average_tensor / __reduce_and_partition_ipg_grads)
                return self._finish_step(state, loss, grads, rng, counters)

        return train_step

    def _wrap_compiled(self, fn, site):
        """Register a jitted entry point under its site name
        (``telemetry.op_scopes(site)`` reads the compiled program's
        instruction names by phase), and with the profiling plane on route
        it through the CompileWatcher so cache misses (recompiles) are
        timed and emitted as ``compile/*`` events."""
        fn = register_compiled(fn, site, mesh=self.mesh)
        if self._profiling is None:
            return fn
        return self._profiling.wrap(fn, site,
                                    step_fn=lambda: self.global_steps)

    def _prof_track(self, span):
        """HBM attribution context for a top-level span; no-op without the
        profiling plane (or off-TPU, where memory_stats() is unavailable)."""
        if self._profiling is None:
            return contextlib.nullcontext()
        return self._profiling.track(span)

    def _get_compiled_train_step(self, gas: int):
        if gas not in self._compiled_train_step:
            step = self._build_train_step(gas)
            self._compiled_train_step[gas] = self._wrap_compiled(
                jax.jit(step, donate_argnums=(0,)), f"engine/train_step:{gas}")
        return self._compiled_train_step[gas]

    # ------------------------------------------------------------------
    # ZeRO-Offload step path: device computes grads, host applies Adam
    # ------------------------------------------------------------------
    def _get_compiled_offload_grad_step(self, gas: int):
        if gas not in self._compiled_offload_grad:
            fp16 = self._config.fp16_enabled

            def grad_step(state: TrainState, batch):
                with self._overlap_scope():
                    scale = (state.loss_scale.cur_scale if fp16
                             else jnp.float32(1.0))
                    rng, step_rng = jax.random.split(state.rng)
                    loss, grads, _ = self._forward_grads(
                        state.params, scale, step_rng, batch, gas,
                        step=state.global_step,
                        qstep=moq_anneal_step(state))
                    grads = self._reduce_grads(grads, state.params)
                    overflow = (has_inf_or_nan(grads) if fp16
                                else jnp.asarray(False))
                    grad_norm = _global_norm_f32(grads)
                    return loss, grads, overflow, grad_norm, rng
            self._compiled_offload_grad[gas] = self._wrap_compiled(
                jax.jit(grad_step), f"engine/offload_grad:{gas}")
        return self._compiled_offload_grad[gas]

    def _offload_host_apply(self, grads, overflow, grad_norm):
        """Host tail of the offload step: stream grads D2H, fused C++ Adam on
        the flat master (NVMe-swapped moments under ZeRO-Infinity), stream
        updated params H2D, run the loss-scale automaton."""
        cfg = self._config
        # bf16/fp32 runs never overflow-skip: the flag is a traced constant
        # False, and fetching it would serialize the host on the whole
        # device step before the grad D2H stream even starts
        overflow_b = (bool(jax.device_get(overflow))
                      if cfg.fp16_enabled else False)
        if not overflow_b:
            # schedule evaluated on the HOST step counter: no sync against
            # the in-flight device step
            lr = float(jax.device_get(
                jnp.asarray(self._schedule_fn(self.global_steps))))
            coef = None
            if cfg.gradient_clipping and cfg.gradient_clipping > 0:
                gn = float(jax.device_get(grad_norm))
                clip = cfg.gradient_clipping
                if gn > clip:
                    coef = clip / (gn + 1e-6)
            if self._offload_sharded:
                # multi-host: streamed D2H/Adam, then assemble the global
                # device tree from each process's local master shards
                self._offload.step_streamed(grads, lr=lr, clip_coef=coef)
                with self.mesh:
                    new_params = self._offload.device_params(
                        self._offload_param_sh, dtype=self.compute_dtype)
            else:
                # fully pipelined: per-leaf D2H / per-subgroup C++ Adam /
                # per-leaf H2D of the updated master all overlap (no
                # whole-tree host cast + serial upload tail)
                with self.mesh:
                    new_params = self._offload.step_streamed(
                        grads, lr=lr, clip_coef=coef,
                        upload_shardings=self._offload_param_sh,
                        upload_dtype=np.dtype(
                            jnp.dtype(self.compute_dtype).name))
            self.state = self.state.replace(params=new_params)
        new_ls = update_scale(
            self.state.loss_scale, jnp.asarray(overflow_b),
            dynamic=cfg.fp16_enabled and cfg.dynamic_loss_scale,
            scale_window=cfg.fp16_config.loss_scale_window,
            min_scale=cfg.fp16_config.min_loss_scale,
            hysteresis=cfg.fp16_config.hysteresis)
        self.state = self.state.replace(
            loss_scale=new_ls,
            global_step=self.state.global_step + 1,
            skipped_steps=self.state.skipped_steps + int(overflow_b))
        return overflow_b

    # ------------------------------------------------------------------
    # DeepSpeed-parity 3-call API
    # ------------------------------------------------------------------
    def forward(self, batch, rng=None):
        """Computes loss (and, functionally, gradients — cached for
        ``backward``).  Returns the unscaled loss."""
        with self.telemetry.span("engine/forward", step=self.global_steps), \
                self._prof_track("fwd"):
            return self._forward_inner(batch, rng)

    def _forward_inner(self, batch, rng=None):
        if self._param_stream is not None:
            raise NotImplementedError(
                "offload_param streaming runs whole optimizer steps; use "
                "train_batch() (the 3-call API would re-stream the model "
                "per call)")
        self.timers(FORWARD_GLOBAL_TIMER).start()
        if self._compiled_fwd_bwd is None:
            def fwd_bwd(state, batch):
                with self._overlap_scope():
                    scale = (state.loss_scale.cur_scale
                             if self._config.fp16_enabled
                             else jnp.float32(1.0))
                    rng, step_rng = jax.random.split(state.rng)
                    loss, grads, _ = self._loss_and_grads(
                        state.params, scale, batch, step_rng,
                        step=state.global_step,
                        qstep=moq_anneal_step(state))
                    grads = self._reduce_grads(grads, state.params)
                    overflow = (has_inf_or_nan(grads)
                                if self._config.fp16_enabled
                                else jnp.asarray(False))
                    return loss, grads, overflow, rng
            self._compiled_fwd_bwd = self._wrap_compiled(
                jax.jit(fwd_bwd), "engine/fwd_bwd")
        batch = self._shard_batch(batch)
        with self.mesh:
            loss, grads, overflow, rng = self._compiled_fwd_bwd(self.state, batch)
        self.state = self.state.replace(rng=rng)
        self._cached = (loss, grads, overflow)
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients=True, release_loss=False):
        """Accumulates the gradients computed by the latest ``forward``.
        Parity: reference ``backward:1931`` (scaling by 1/GAS happens here)."""
        with self.telemetry.span("engine/backward", step=self.global_steps), \
                self._prof_track("bwd"):
            return self._backward_inner(loss)

    def _backward_inner(self, loss=None):
        assert self._cached is not None, "backward() called before forward()"
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        _, grads, overflow = self._cached
        gas = self.gradient_accumulation_steps_
        scaled = jax.tree_util.tree_map(lambda g: g / gas, grads)
        if self._accum_grads is None:
            self._accum_grads = scaled
            self._accum_overflow = overflow
        else:
            self._accum_grads = jax.tree_util.tree_map(
                jnp.add, self._accum_grads, scaled)
            self._accum_overflow = jnp.logical_or(self._accum_overflow, overflow)
        self._accum_count += 1
        self.micro_steps += 1
        self._cached = None
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    def is_gradient_accumulation_boundary(self):
        return self._accum_count >= self.gradient_accumulation_steps_

    def step(self):
        """Applies the optimizer update at the GAS boundary.
        Parity: reference ``step:2142`` → ``_take_model_step:2074``."""
        with self.telemetry.span("engine/step", step=self.global_steps), \
                self._prof_track("step"):
            self._step_inner()
        if self._tel_enabled and self._step_applied:
            self._emit_step_telemetry()

    def _step_inner(self):
        self._step_applied = False
        if not self.is_gradient_accumulation_boundary():
            return
        self.timers(STEP_GLOBAL_TIMER).start()
        if self._offload is not None:
            grad_norm = optax.global_norm(self._accum_grads)
            self._offload_host_apply(self._accum_grads,
                                     self._accum_overflow, grad_norm)
        else:
            if self._compiled_apply is None:
                self._compiled_apply = self._wrap_compiled(
                    jax.jit(self._apply_update, donate_argnums=(0, 1)),
                    "engine/apply")
            with self.mesh:
                self.state, grad_norm = self._compiled_apply(
                    self.state, self._accum_grads, self._accum_overflow)
        # kept as a device scalar: get_global_grad_norm() floats on demand,
        # so the 3-call API doesn't serialize dispatch every step either
        self._global_grad_norm = grad_norm
        self._accum_grads = None
        self._accum_count = 0
        self._step_applied = True
        self.global_steps += 1
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._write_monitor()
        self.timers(STEP_GLOBAL_TIMER).stop()
        if self._config.wall_clock_breakdown and \
                self.global_steps % self._config.steps_per_print == 0:
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                             STEP_GLOBAL_TIMER])

    # ------------------------------------------------------------------
    # fused fast path
    # ------------------------------------------------------------------
    def train_batch(self, data_iter=None, batch=None):
        """One full training step (GAS microbatches) as a single compiled
        program.  Parity with ``PipelineEngine.train_batch`` semantics: returns
        the mean loss over the global batch."""
        if self._preempt is not None and self._preempt.requested:
            self._handle_preemption()
        # one path, telemetry on or off: the span is recorded in the
        # span ring either way (call to return; the loss comes back as a
        # device value, so this is host time unless a step blocks inside)
        t0 = time.perf_counter()
        with self.telemetry.step_span("engine/train_batch",
                                      step=self.global_steps, period=True,
                                      owner=self), \
                self._prof_track("train_batch"):
            loss = self._train_batch_inner(data_iter, batch)
        if self._tel_enabled:
            self._emit_step_telemetry(step_secs=time.perf_counter() - t0,
                                      metrics=self._last_metrics)
        # step-boundary fault-tolerance hooks: divergence sentinel first
        # (its restore path clears state a preemption save would persist),
        # then preemption — a signal delivered mid-step is honored here
        # rather than a full step later
        if self._sentinel is not None:
            self._handle_sentinel()
        if self._preempt is not None and self._preempt.requested:
            self._handle_preemption()
        return loss

    def _train_batch_inner(self, data_iter=None, batch=None):
        gas = self.gradient_accumulation_steps_
        presharded = False
        if batch is None:
            owns_iter = data_iter is None
            if owns_iter:
                assert self.training_dataloader is not None, \
                    "train_batch needs data_iter, batch=, or training_data"
                data_iter = self._default_data_iter()
            if self._async_enabled:
                data_iter = self._wrap_prefetch(data_iter)
            from deepspeed_tpu.runtime.dataloader import DevicePrefetchIterator
            if isinstance(data_iter, DevicePrefetchIterator):
                # the worker already collated, gas-stacked, curriculum-
                # transformed and sharded this batch — just pop it
                try:
                    with self.telemetry.span(
                            "engine/input_wait", step=self.global_steps,
                            attrs={"queued": data_iter.qsize()}):
                        batch = next(data_iter)
                except StopIteration:
                    if owns_iter:
                        self._default_iter = None
                    self._release_prefetcher(data_iter)
                    raise
                presharded = True
            else:
                micro_batches = [next(data_iter) for _ in range(gas)]
                if gas > 1:
                    batch = jax.tree_util.tree_map(
                        lambda *xs: np.stack(xs), *micro_batches)
                else:
                    batch = micro_batches[0]
        self.tput_timer.start()
        if self.compression_scheduler is not None:
            self.compression_scheduler.check(self.global_steps)
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        if not presharded:
            # host input prep: curriculum truncation and placement of the
            # batch on the mesh (the prefetch worker did both already for
            # a presharded batch; its wait is engine/input_wait)
            with self.telemetry.span("engine/input",
                                     step=self.global_steps):
                if self.curriculum_scheduler_ is not None:
                    batch = self._apply_curriculum(
                        batch, leading_gas_dim=gas > 1)
                batch = self._shard_batch(batch, leading_gas_dim=gas > 1)
        if self._tel_enabled:
            self._last_batch_tokens = _batch_token_count(batch)
            if self._attn_plan is None:
                self._attn_plan = self._plan_attention(batch, gas)
        if self._injector is not None and \
                self._injector.poison_grads(self.global_steps):
            # deterministic divergence trigger: NaN the float batch inputs
            # (falling back to params when the batch is all-integer, e.g.
            # token ids) so this step's gradients go non-finite
            batch, n_poisoned = poison_tree(batch)
            if n_poisoned == 0:
                self.state = self.state.replace(
                    params=poison_tree(self.state.params)[0])
            logger.warning(f"fault injector: poisoned gradients at step "
                           f"{self.global_steps}")
        self._maybe_profile_flops(batch, gas)
        if self._param_stream is not None:
            cfg = self._config
            fp16 = cfg.fp16_enabled
            rng, step_rng = jax.random.split(self.state.rng)
            # lr from the HOST step counter and scale from the host
            # loss-scale mirror: neither reads the in-flight device state,
            # so this host-orchestrated path stops paying two device
            # round-trips per step just to learn values it already knows
            lr_now = self._host_schedule_value(self.global_steps)
            scale = self._host_ls.cur_scale if fp16 else 1.0
            loss_f, gnorm, overflow_b = self._param_stream.train_step(
                batch, gas, lr_now, scale, fp16,
                cfg.gradient_clipping, step_rng)
            # device automaton stays updated in lockstep (checkpoint parity)
            new_ls = update_scale(
                self.state.loss_scale, jnp.asarray(overflow_b),
                dynamic=fp16 and cfg.dynamic_loss_scale,
                scale_window=cfg.fp16_config.loss_scale_window,
                min_scale=cfg.fp16_config.min_loss_scale,
                hysteresis=cfg.fp16_config.hysteresis)
            self._host_ls.update(bool(overflow_b))
            self.state = self.state.replace(
                rng=rng, loss_scale=new_ls,
                global_step=self.state.global_step + 1,
                skipped_steps=(self.state.skipped_steps +
                               int(overflow_b)))
            metrics = StepMetrics(
                loss=jnp.float32(loss_f), grad_norm=jnp.float32(gnorm),
                lr=jnp.asarray(lr_now, jnp.float32),
                loss_scale=self.state.loss_scale.cur_scale,
                overflow=jnp.asarray(overflow_b))
        elif self._offload is not None:
            grad_fn = self._get_compiled_offload_grad_step(gas)
            with self.telemetry.span("engine/dispatch",
                                     step=self.global_steps), self.mesh:
                loss, grads, overflow, grad_norm, rng = grad_fn(
                    self.state, batch)
            self.state = self.state.replace(rng=rng)
            lr_now = self._schedule_fn(self.state.global_step)
            self._offload_host_apply(grads, overflow, grad_norm)
            metrics = StepMetrics(
                loss=loss.astype(jnp.float32),
                grad_norm=grad_norm.astype(jnp.float32),
                lr=jnp.asarray(lr_now, jnp.float32),
                loss_scale=self.state.loss_scale.cur_scale,
                overflow=overflow)
        else:
            step_fn = self._get_compiled_train_step(gas)
            with self.telemetry.span("engine/dispatch",
                                     step=self.global_steps), self.mesh:
                self.state, metrics = step_fn(self.state, batch)
        self.global_steps += 1
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        if self._sentinel is not None:
            # device references only — the sentinel batches its own
            # device_get every `interval` pushes, keeping the hot loop
            # sync-free
            self._sentinel.push(self.global_steps, loss=metrics.loss,
                                overflow=metrics.overflow)
        self._last_metrics = metrics
        self._global_grad_norm = metrics.grad_norm
        self.tput_timer.stop(global_step=True)
        self._write_monitor(metrics)
        return metrics.loss

    def _apply_curriculum(self, batch, leading_gas_dim=False, step=None):
        """Truncate sequences to the curriculum difficulty (reference
        ``engine.py:1820-1826`` curriculum_seqlen slicing).  Each difficulty
        milestone is a new static shape → one recompile, amortised over the
        steps at that difficulty.  ``step`` overrides the difficulty clock
        for the prefetch worker, which transforms batches ahead of time."""
        seqlen = self.curriculum_scheduler_.update_difficulty(
            self.global_steps if step is None else step)
        dim = 2 if leading_gas_dim else 1

        def trunc(x):
            if np.ndim(x) > dim and x.shape[dim] > seqlen:
                slicer = [slice(None)] * np.ndim(x)
                slicer[dim] = slice(0, seqlen)
                return x[tuple(slicer)]
            return x
        return jax.tree_util.tree_map(trunc, batch)

    def pld_enabled(self):
        return self.progressive_layer_drop is not None

    def pld_theta(self):
        return (self.progressive_layer_drop.get_theta()
                if self.progressive_layer_drop else 1.0)

    # subclass hooks: PipelineEngine preps (stacks) the batch and runs with
    # a leading microbatch dim — everything else is shared here.
    _eval_leading_gas_dim = False

    def _prep_eval_batch(self, batch):
        return batch

    def eval_batch(self, batch, rng=None):
        if self._param_stream is not None:
            batch = self._prep_eval_batch(batch)
            batch = self._shard_batch(
                batch, leading_gas_dim=self._eval_leading_gas_dim)
            return jnp.float32(
                self._param_stream.eval_loss(batch, rng=self.state.rng))
        if not hasattr(self, "_compiled_eval"):
            def ev(state, batch):
                p_c = jax.tree_util.tree_map(
                    lambda x: x.astype(self.compute_dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x,
                    state.params)
                # eval must see the same weights the training forward sees
                # (reference quantizes the fp16 copies in place, so its eval
                # path is quantized/compressed too)
                if self._compression is not None:
                    p_c = self._compression.transform(p_c, state.global_step)
                if self.quantizer is not None:
                    # same successful-step anneal clock as the training
                    # forward, or eval sees further-annealed bits after
                    # any overflow step
                    p_c = self.quantizer.transform(
                        p_c, moq_anneal_step(state),
                        schedule_offset=self.quantizer.schedule_offset)
                return self.loss_fn(p_c, batch, state.rng)
            self._compiled_eval = self._wrap_compiled(
                jax.jit(ev), "engine/eval")
        batch = self._prep_eval_batch(batch)
        batch = self._shard_batch(batch,
                                  leading_gas_dim=self._eval_leading_gas_dim)
        with self.mesh:
            return self._compiled_eval(self.state, batch)

    # ------------------------------------------------------------------
    def _shard_batch(self, batch, leading_gas_dim=False):
        multihost = jax.process_count() > 1

        def put(x):
            x = np.asarray(x) if not isinstance(x, jax.Array) else x
            ndim = x.ndim
            if leading_gas_dim:
                spec = self.plan.batch_spec(ndim - 1)
                spec = P(*([None] + list(spec)))
            else:
                spec = self.plan.batch_spec(ndim)
            sharding = NamedSharding(self.mesh, spec)
            if multihost:
                # each process holds its local slice of the global batch
                # (dataloader is process-strided); assemble the global array
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)
        return jax.tree_util.tree_map(put, batch)

    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None,
                     num_local_io_workers=None, data_sampler=None,
                     route=None):
        """Parity: reference ``deepspeed_io:1678`` — builds the distributed
        dataloader (global batches; sharding happens at device_put).
        ``num_local_io_workers`` sizes the host-side sample-fetch pool
        (falls back to ``async_pipeline.io_workers``); with the async
        pipeline enabled the loader is wrapped so iteration yields
        pre-sharded device batches from a background prefetcher."""
        from deepspeed_tpu.runtime.dataloader import (DeepSpeedDataLoader,
                                                      PrefetchingDataLoader)
        ap = self._config.async_pipeline_config
        if batch_size is None:
            batch_size = (self.train_micro_batch_size_per_gpu() *
                          groups.get_data_parallel_world_size())
        io_workers = (num_local_io_workers if num_local_io_workers is not None
                      else ap.io_workers)
        loader = DeepSpeedDataLoader(dataset, batch_size=batch_size,
                                     collate_fn=collate_fn,
                                     seed=self._config.seed,
                                     num_workers=io_workers)
        if self._async_enabled:
            return PrefetchingDataLoader(loader, self._make_prefetcher)
        return loader

    # -- async input feed ----------------------------------------------
    def _default_data_iter(self):
        """The iterator behind no-arg ``train_batch()``.  Sync path keeps
        the historical fresh-``iter()``-per-call behavior; async keeps ONE
        persistent iterator so a single prefetch worker spans steps (a
        fresh prefetcher per call could never run ahead)."""
        if not self._async_enabled:
            return iter(self.training_dataloader)
        if self._default_iter is None:
            self._default_iter = iter(self.training_dataloader)
        return self._default_iter

    def _wrap_prefetch(self, data_iter):
        """Wrap a host-batch iterator in the engine-owned prefetcher
        (identity-cached: repeated calls with the same iterator reuse the
        running worker; a new iterator retires the old prefetcher)."""
        from deepspeed_tpu.runtime.dataloader import DevicePrefetchIterator
        if isinstance(data_iter, DevicePrefetchIterator):
            return data_iter
        if self._prefetch_source is not data_iter:
            if self._prefetcher is not None:
                self._prefetcher.close()
            self._prefetcher = self._make_prefetcher(data_iter)
            self._prefetch_source = data_iter
        return self._prefetcher

    def _make_prefetcher(self, source):
        from deepspeed_tpu.runtime.dataloader import DevicePrefetchIterator
        ap = self._config.async_pipeline_config
        rc = self._resilience
        return DevicePrefetchIterator(
            source, gas=self.gradient_accumulation_steps_,
            shard_fn=self._shard_batch,
            transform=(self._prefetch_transform
                       if self.curriculum_scheduler_ is not None else None),
            depth=ap.prefetch_depth,
            start_index=self.global_steps,
            max_retries=rc.dataloader_max_retries,
            retry_backoff_secs=rc.dataloader_retry_backoff_secs,
            injector=self._injector,
            telemetry=self.telemetry)

    def _prefetch_transform(self, batch, index, leading_gas_dim):
        # runs on the prefetch worker: curriculum difficulty is keyed to
        # the step the batch will be CONSUMED at, not the current step
        return self._apply_curriculum(batch, leading_gas_dim=leading_gas_dim,
                                      step=index)

    def _release_prefetcher(self, prefetcher):
        prefetcher.close()
        if self._prefetcher is prefetcher:
            self._prefetcher = None
            self._prefetch_source = None

    def _host_schedule_value(self, step):
        """lr at host ``step`` as a python float, cached per step.  The
        schedule runs on a concrete int, so any device work is a tiny
        fresh computation — never a sync against the in-flight train step."""
        if self._host_lr_cache is None or self._host_lr_cache[0] != step:
            val = self._schedule_fn(step)
            self._host_lr_cache = (step, float(jax.device_get(val)))
        return self._host_lr_cache[1]

    # ------------------------------------------------------------------
    # monitor / introspection parity accessors
    # ------------------------------------------------------------------
    def _plan_attention(self, batch, gas):
        """The model's ``attention_plan`` for a step of ``gas``
        micro-batches like ``batch`` (static, from shapes), and what its
        remat policy keeps of a flash call: set as the ``train/attn/*``
        gauges, once."""
        plan_of = getattr(self.module, "attention_plan", None)
        ids = batch.get("input_ids") if isinstance(batch, dict) else batch
        shape = getattr(ids, "shape", ())
        if plan_of is None or len(shape) < 2:
            return {}
        sequences, positions = math.prod(shape[:-1]) // gas, shape[-1]
        plan = plan_of(sequences, positions) or {}
        for name, value in plan.items():
            self.telemetry.gauge(f"train/attn/{name}", float(value * gas),
                                 step=self.global_steps)
        if plan:
            # what a layer keeps of one micro-batch's flash call under
            # the model's remat policy (not a step's sum: no ``gas``)
            self.telemetry.gauge(
                "train/attn/saved_residual_bytes",
                float(self.module.saved_attention_bytes(
                    sequences, positions,
                    jnp.dtype(self.compute_dtype).itemsize)),
                step=self.global_steps)
        return plan

    def _emit_step_telemetry(self, step_secs=None, metrics=None):
        """Per-step telemetry tail (telemetry-enabled runs only): heartbeat
        for the stall watchdog, loss/grad-norm/loss-scale + throughput
        gauges, and device-memory gauges with peak tracking.

        Sync-free by construction: the heartbeat and throughput gauges are
        host-clock, HBM gauges read allocator stats, and the device metric
        scalars go through :class:`MetricsDrain` — readback happens on the
        ``sync_interval`` boundary (or a drainer thread), not here."""
        tel = self.telemetry
        step = self.global_steps
        if self._watchdog is not None:
            self._watchdog.beat(step)
        elif getattr(tel, "attribution", None) is not None:
            # no watchdog heartbeat to close the attribution window —
            # beat the plane directly (same beat-to-beat step_ms contract)
            tel.attribution.beat(step)
        if self._overlap_enabled:
            # overlap effectiveness gauges (the frozen comm/overlap/*
            # vocabulary): exposure split from the attribution plane's
            # latest window, bucket counts from the trace-time planners
            plane = getattr(tel, "attribution", None)
            if plane is not None and plane.history:
                rec = plane.history[-1]
                comm_ms = float(rec.get("comm_ms", 0.0))
                exposed = float(rec.get("exposed_comm_ms", 0.0))
                tel.gauge("comm/overlap/exposed_ms", exposed, step=step)
                tel.gauge("comm/overlap/overlapped_ms",
                          max(0.0, comm_ms - exposed), step=step)
            if self._rs_buckets:
                tel.gauge("comm/overlap/rs_buckets",
                          float(self._rs_buckets), step=step)
            ctx = self._overlap_ctx
            if ctx is not None and ctx.layers:
                # one gather "bucket" per pipelined layer working set
                tel.gauge("comm/overlap/gather_buckets",
                          float(ctx.layers), step=step)
                tel.gauge("comm/overlap/prefetch_depth",
                          float(ctx.gather_prefetch_depth), step=step)
        if metrics is not None:
            vals = {"engine/loss": metrics.loss,
                    "engine/grad_norm": metrics.grad_norm}
            if self._config.fp16_enabled:
                vals["engine/loss_scale"] = metrics.loss_scale
            if metrics.counters is not None:
                # the expert layers' own account of the step, summed over
                # layers and micro-batches (docs/telemetry.md)
                for i, name in enumerate(self._train_counters):
                    vals[f"train/moe/{name}"] = metrics.counters[i]
            self._metrics_drain.push(step, vals)
        elif self._global_grad_norm is not None:
            self._metrics_drain.push(
                step, {"engine/grad_norm": self._global_grad_norm})
        if step_secs is not None and step_secs > 0:
            tel.gauge("engine/samples_per_sec",
                      self._config.train_batch_size / step_secs, step=step)
            if self._last_batch_tokens:
                tel.gauge("engine/tokens_per_sec",
                          self._last_batch_tokens / step_secs, step=step)
            if self._analytic_step_flops:
                flops_per_sec = self._analytic_step_flops / step_secs
                tel.gauge("train/model_flops_per_sec", flops_per_sec,
                          step=step)
                if self._mfu_peak_flops:
                    tel.gauge("train/mfu",
                              flops_per_sec / self._mfu_peak_flops,
                              step=step)
        if self._profiling is not None:
            self._profiling.on_step(step)
            if step_secs is not None and step_secs > 0:
                # live roofline: achieved fraction of peak compute and HBM
                # bandwidth for the whole train_batch span (analytic
                # numerators from the flops profiler, table denominators)
                self._profiling.roofline(
                    "train_batch", step_secs,
                    flops=self._analytic_step_flops,
                    bytes_moved=self._analytic_step_bytes,
                    peak_flops=self._mfu_peak_flops, step=step)
        if self._config.telemetry_config.hbm_gauges:
            self._emit_hbm_gauges(step)

    def _drain_emit(self, step, host_vals):
        """MetricsDrain callback: host floats for one step, in step order."""
        for name, value in host_vals.items():
            self.telemetry.gauge(name, value, step=step)

    def flush_telemetry(self):
        """Force readback + emit of any metrics still queued in the drain
        (checkpoint boundaries, end of training, tests)."""
        if self._metrics_drain is not None:
            self._metrics_drain.flush()

    def _emit_hbm_gauges(self, step):
        """HBM pressure gauges from ``jax.Device.memory_stats()`` (None on
        backends without allocator stats — skip quietly)."""
        try:
            stats = jax.local_devices()[0].memory_stats()
        except Exception:
            stats = None
        if not stats:
            return
        for key in ("bytes_in_use", "peak_bytes_in_use",
                    "largest_alloc_size", "bytes_limit"):
            if key in stats:
                self.telemetry.gauge(f"hbm/{key}", float(stats[key]),
                                     step=step)

    def _write_monitor(self, metrics=None):
        if not self.monitor.enabled:
            return
        # a monitor takes host floats, so this WAITS for the step's device
        # work: with a monitor on (``telemetry.enabled`` attaches the JSONL
        # one) ``engine/train_batch`` is the whole step, and the host's own
        # part of it is that span less this one
        with self.telemetry.span("engine/monitor", step=self.global_steps):
            self._write_monitor_events(metrics)

    def _write_monitor_events(self, metrics=None):
        events = []
        if metrics is not None:
            events = [
                ("Train/Samples/train_loss", float(metrics.loss),
                 self.global_samples()),
                ("Train/Samples/lr", float(metrics.lr), self.global_samples()),
            ]
            if self._config.fp16_enabled:
                events.append(("Train/Samples/loss_scale",
                               float(metrics.loss_scale), self.global_samples()))
        self.monitor.write_events(events)

    def _maybe_profile_flops(self, batch, gas):
        """Parity: reference ``engine.py:1792,1810`` — run the flops profiler
        at ``flops_profiler.profile_step`` and print the model profile.

        Profiles the *forward* loss function on one microbatch (reference
        counts forward MACs via module hooks), inside the mesh context so
        sharding constraints trace the same as the executed program.  No XLA
        recompile — analytic jaxpr counting only."""
        fpc = self._config.flops_profiler_config
        if not fpc.enabled or self.global_steps != fpc.profile_step:
            return
        if self._param_stream is not None:
            return   # params live on host; no device tree to trace
        from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler
        micro = batch
        if gas > 1:
            micro = jax.tree_util.tree_map(lambda x: x[0], batch)
        rng = self.state.rng

        def fwd(params, mb):
            p_c = jax.tree_util.tree_map(
                lambda x: x.astype(self.compute_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
            return self.loss_fn(p_c, mb, rng)

        prof = FlopsProfiler()
        prof.start_profile()
        with self.mesh:
            prof.profile(fwd, self.state.params, micro,
                         measure_time=False, xla_analysis=False)
        if dist.get_rank() == 0:
            prof.print_model_profile(profile_step=fpc.profile_step,
                                     module_depth=fpc.module_depth,
                                     top_modules=fpc.top_modules,
                                     detailed=fpc.detailed,
                                     output_file=fpc.output_file)
        prof.end_profile()
        self.flops_profiler = prof
        # wire the analytic count into live telemetry: a train step is
        # fwd+bwd (~3x forward flops) over `gas` microbatches; every step
        # from here on emits train/model_flops_per_sec, and train/mfu when
        # a per-device peak is known (config peak_tflops, else chip table)
        if prof.total_flops:
            self._analytic_step_flops = 3.0 * float(prof.total_flops) * gas
            # analytic HBM traffic for the bandwidth roofline: same 3x
            # fwd+bwd approximation over the jaxpr's operand/result bytes
            try:
                from deepspeed_tpu.profiling.flops_profiler import \
                    jaxpr_hbm_bytes
                with self.mesh:
                    fwd_bytes = jaxpr_hbm_bytes(fwd, self.state.params, micro)
                self._analytic_step_bytes = (3.0 * float(fwd_bytes) * gas
                                             if fwd_bytes else None)
            except Exception:
                self._analytic_step_bytes = None
            peak = (float(fpc.peak_tflops) * 1e12
                    if float(getattr(fpc, "peak_tflops", 0.0) or 0.0) > 0
                    else None)
            if peak is None:
                from deepspeed_tpu.comm.topology_model import \
                    device_peak_flops
                peak = device_peak_flops()
            self._mfu_peak_flops = (peak * jax.device_count()
                                    if peak else None)

    def global_samples(self):
        return self.global_steps * self._config.train_batch_size

    def get_global_grad_norm(self):
        return float(self._global_grad_norm)

    def get_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.get_lr()
        return [self._base_lr]

    def get_loss_scale(self):
        return float(self.state.loss_scale.cur_scale)

    @property
    def cur_scale(self):
        return self.get_loss_scale()

    def was_step_applied(self):
        return self._step_applied

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def train_batch_size(self):
        return self._config.train_batch_size

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def quantize_training(self):
        """MoQ config tuple (reference ``engine.py:698`` — in_forward,
        enabled, groups, fp16_mixed, change_ratio, type, rounding, verbose,
        kernel).  Reads the live Quantizer so the report can't drift from
        what actually runs."""
        from deepspeed_tpu.runtime.quantize import quantizer_from_shared
        wq = (self._config.compression_config or {}).get(
            "weight_quantization", {})
        shared = wq.get("shared_parameters", {})
        in_forward = shared.get("quantize_weight_in_forward", False)
        enabled = bool(shared.get("enabled", False)
                       or shared.get("quantize_enabled", False))
        q = self.quantizer or quantizer_from_shared(shared)
        return (in_forward, enabled, q.q_groups, q.q_mixed_fp16,
                q.q_change_ratio, q.q_type, q.q_rounding, q.q_verbose,
                q.use_quantizer_kernel)

    def zero_optimization_stage(self):
        return self.zero_stage

    def zero_optimization(self):
        return self.zero_stage > 0

    def fp16_enabled(self):
        return self._config.fp16_enabled

    def bfloat16_enabled(self):
        return self._config.bfloat16_enabled

    def get_params(self):
        return self.state.params

    def module_state_dict(self):
        """Full (un-sharded, host) params — reference ``module_state_dict`` /
        ``_zero3_consolidated_16bit_state_dict:3432`` rolled into one: orbax
        handles gather-on-save, so consolidation is just a replicated
        device_get."""
        if self._param_stream is not None:
            return self._param_stream.params_tree()
        if self._offload is not None:
            if self._offload_sharded:
                # multi-host: the host master is shard-local; consolidate
                # from the (compute-dtype) device params instead
                return jax.device_get(self._replicate_gather(
                    self.state.params))
            return self._offload.params_tree()
        return jax.device_get(self._replicate_gather(self.state.params))

    def _replicate_gather(self, tree):
        """All-gather a sharded tree to replicated via jit (works on
        multi-host meshes where a plain device_put cannot re-target
        non-addressable devices)."""
        repl = self.plan.replicated_sharding()
        with self.mesh:
            return jax.jit(lambda x: x, out_shardings=repl)(tree)

    # ------------------------------------------------------------------
    # fault tolerance (runtime/resilience.py)
    # ------------------------------------------------------------------
    def _shutdown_workers(self):
        """Drain the engine's worker threads cleanly: close the prefetcher
        (its daemon worker exits on the queue sentinel) and flush any
        device metrics still queued in the drain."""
        if self._prefetcher is not None:
            self._release_prefetcher(self._prefetcher)
        self._default_iter = None
        self.flush_telemetry()

    def _handle_preemption(self):
        """Step-boundary response to SIGTERM/SIGINT: emergency checkpoint
        (when ``resilience.ckpt_dir`` is set), clean worker drain, then
        :class:`TrainingPreempted` so the caller unwinds instead of being
        killed mid-write."""
        rc = self._resilience
        tag = f"emergency_step{self.global_steps}" if rc.ckpt_dir else None
        if tag is not None:
            try:
                self.save_checkpoint(rc.ckpt_dir, tag=tag)
            except Exception as exc:
                logger.error(f"emergency checkpoint failed: {exc!r}")
                tag = None
        self._shutdown_workers()
        self.telemetry.fault("fault/preempted", step=self.global_steps,
                             attrs={"tag": tag, "dir": rc.ckpt_dir or None})
        self._preempt.uninstall()
        self._preempt.clear()
        where = f"; emergency checkpoint {rc.ckpt_dir}/{tag}" if tag else ""
        raise TrainingPreempted(
            f"training preempted at step {self.global_steps}{where}")

    def _handle_sentinel(self):
        """Act on a tripped divergence sentinel: auto-restore from the last
        good checkpoint when configured (and one exists), else drain and
        halt with :class:`DivergenceError`."""
        action = self._sentinel.poll()
        if action is None:
            return
        if action == "restore" and self._last_good_ckpt is not None:
            load_dir, tag = self._last_good_ckpt
            logger.warning(
                f"divergence ({self._sentinel.reason} at step "
                f"{self._sentinel.trip_step}): auto-restoring {load_dir}/{tag}")
            self.load_checkpoint(load_dir, tag=tag)
            self.telemetry.fault("fault/auto_restore", step=self.global_steps,
                                 attrs={"dir": load_dir, "tag": tag,
                                        "reason": self._sentinel.reason})
            self._sentinel.reset()
            return
        reason, step = self._sentinel.reason, self._sentinel.trip_step
        self._shutdown_workers()
        raise DivergenceError(
            f"training diverged at step {step}: {reason} "
            f"(no checkpoint to restore)" if action == "restore" else
            f"training diverged at step {step}: {reason}")

    # ------------------------------------------------------------------
    # checkpointing (parity: save_checkpoint:3084 / load_checkpoint:2724)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        from deepspeed_tpu.runtime.checkpoint_engine import get_checkpoint_engine
        eng = get_checkpoint_engine()
        tag = tag or f"global_step{self.global_steps}"
        client_state = dict(client_state or {})
        client_state.update({
            "global_steps": self.global_steps,
            "skipped_steps": int(self.state.skipped_steps),
            "micro_steps": self.micro_steps,
            "lr_scheduler": (self.lr_scheduler.state_dict()
                             if self.lr_scheduler else None),
        })
        rc = self._resilience
        if not rc.enabled:
            # legacy in-place path: no tmp dir, no marker, no retries
            eng.save(self.state, save_dir, tag, client_state=client_state)
            if self._param_stream is not None:
                self._param_stream.save(save_dir, tag)
            if self._offload is not None:
                self._offload.save(save_dir, tag)
            if save_latest and jax.process_index() == 0:
                with open(os.path.join(save_dir, "latest"), "w") as f:
                    f.write(tag)
            dist.barrier()
            return True
        # durable protocol: every writer (orbax engine, param-stream host
        # store, offload host shards) targets the dot-prefixed tmp tag —
        # invisible to tag scans — then commit() fsyncs and atomically
        # renames it into place with a manifest + marker.  The whole
        # attempt (including the rename) sits under the retry policy; the
        # injector's "ckpt_save" site is consumed by the same retries.
        txn = CheckpointTransaction(
            save_dir, tag,
            is_coordinator=jax.process_index() == 0,
            barrier_fn=dist.barrier if jax.process_count() > 1 else None)

        def _attempt():
            txn.begin()
            eng.save(self.state, save_dir, txn.tmp_tag,
                     client_state=client_state)
            if self._param_stream is not None:
                self._param_stream.save(save_dir, txn.tmp_tag)
            if self._offload is not None:
                self._offload.save(save_dir, txn.tmp_tag)
            # async (Nebula-style) engines flush their background write
            # here — the commit marker must never precede the payload
            eng.commit(txn.tmp_tag)
            return txn.commit(build_manifest(self.state, tag,
                                             self.global_steps,
                                             checksum=rc.checksum))

        retry_io(_attempt, self._retry_policy, telemetry=self.telemetry,
                 op=f"ckpt_save[{tag}]", injector=self._injector,
                 site="ckpt_save", cleanup=txn.abort)
        self._last_good_ckpt = (save_dir, tag)
        if save_latest and jax.process_index() == 0:
            retry_io(
                lambda: atomic_write_text(
                    os.path.join(save_dir, "latest"), tag),
                self._retry_policy, telemetry=self.telemetry,
                op=f"latest[{tag}]", injector=self._injector, site="fs")
        if rc.keep_last > 0 and jax.process_index() == 0:
            gc_tags(save_dir, rc.keep_last, protect=(tag,),
                    telemetry=self.telemetry)
        self.telemetry.emit("meta", "ckpt/committed",
                            attrs={"dir": os.path.abspath(save_dir),
                                   "tag": tag, "step": self.global_steps})
        dist.barrier()
        return True

    def _load_candidates(self, load_dir, tag):
        """Ordered list of loadable tags ``[(tag, status, manifest,
        is_fallback)]``.  An explicit ``tag`` is honored or rejected — no
        silent substitution; ``tag=None`` resolves the ``latest`` pointer
        and falls back to the newest COMMITTED tag when the pointed-to
        checkpoint is missing, torn, or corrupt."""
        if tag is not None:
            status, manifest = validate_tag(os.path.join(load_dir, tag))
            if status == LEGACY:
                logger.warning(f"checkpoint {load_dir}/{tag} predates the "
                               "durable-commit protocol; loading unvalidated")
            elif status != COMMITTED:
                raise CheckpointCorruptError(
                    f"checkpoint {load_dir}/{tag} failed validation: "
                    f"{status}")
            return [(tag, status, manifest, False)]
        latest_tag = None
        latest = os.path.join(load_dir, "latest")
        if os.path.exists(latest):
            with open(latest) as f:
                latest_tag = f.read().strip()
        tags = scan_tags(load_dir)
        by_tag = {t: (s, m) for t, s, m in tags}
        out = []
        if latest_tag:
            status, manifest = by_tag.get(latest_tag, (None, None))
            if status is None:
                status, manifest = validate_tag(
                    os.path.join(load_dir, latest_tag))
            if status in (COMMITTED, LEGACY):
                out.append((latest_tag, status, manifest, False))
            else:
                logger.error(f"latest checkpoint {load_dir}/{latest_tag} is "
                             f"{status}; scanning for newest valid tag")
        for t, s, m in tags:
            if s == COMMITTED and t != latest_tag:
                out.append((t, s, m, True))
        return out

    def load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True,
                        load_lr_scheduler_states=True,
                        load_module_strict=True, load_module_only=False):
        from deepspeed_tpu.runtime.checkpoint_engine import get_checkpoint_engine
        eng = get_checkpoint_engine()
        rc = self._resilience
        if not rc.enabled:
            if tag is None:
                latest = os.path.join(load_dir, "latest")
                if not os.path.exists(latest):
                    logger.warning(f"no 'latest' file at {load_dir}")
                    return None, {}
                with open(latest) as f:
                    tag = f.read().strip()
            candidates = [(tag, LEGACY, None, False)]
        else:
            candidates = self._load_candidates(load_dir, tag)
            if not candidates:
                logger.warning(f"no loadable checkpoint under {load_dir}")
                return None, {}
        state = client_state = None
        chosen = None
        last_exc = None
        for cand_tag, status, manifest, is_fallback in candidates:
            if is_fallback:
                self.telemetry.fault(
                    "fault/ckpt_fallback",
                    attrs={"dir": os.path.abspath(load_dir),
                           "to": cand_tag,
                           "step": (manifest or {}).get("global_step")})
                logger.warning(f"falling back to checkpoint {cand_tag}")
            try:
                def _attempt():
                    return eng.load(
                        self.state, load_dir, cand_tag, self.mesh,
                        load_optimizer_states=load_optimizer_states,
                        load_module_only=load_module_only)
                if rc.enabled:
                    state, client_state = retry_io(
                        _attempt, self._retry_policy,
                        telemetry=self.telemetry,
                        op=f"ckpt_load[{cand_tag}]",
                        injector=self._injector, site="ckpt_load")
                else:
                    state, client_state = _attempt()
                verify_restored(state, manifest)
                chosen = cand_tag
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as exc:
                last_exc = exc
                logger.error(f"loading checkpoint {load_dir}/{cand_tag} "
                             f"failed: {exc!r}")
                state = client_state = None
        if chosen is None:
            if last_exc is not None:
                raise last_exc
            logger.warning(f"no loadable checkpoint under {load_dir}")
            return None, {}
        tag = chosen
        self.state = state
        if self._param_stream is not None:
            if not self._param_stream.load(
                    load_dir, tag,
                    load_optimizer_states=load_optimizer_states):
                logger.warning(
                    "no param-stream host state in checkpoint "
                    f"{load_dir}/{tag}; host params unchanged")
        if self._offload is not None:
            restored = load_optimizer_states and self._offload.load(load_dir,
                                                                    tag)
            if restored:
                with self.mesh:
                    if self._offload_sharded:
                        new_params = self._offload.device_params(
                            self._offload_param_sh,
                            dtype=self.compute_dtype)
                    else:
                        new_params = device_put_global(
                            jax.tree_util.tree_map(
                                lambda x: jnp.asarray(
                                    x.astype(self.compute_dtype)
                                    if jnp.issubdtype(x.dtype, jnp.floating)
                                    else x),
                                self._offload.params_tree()),
                            self._offload_param_sh)
                    self.state = self.state.replace(params=new_params)
            else:
                # no host shard restored (fresh fp32 weights or
                # load_optimizer_states=False): resync the host master from
                # the just-loaded device params so the next step doesn't
                # revert them to construction-time weights
                if self._offload_sharded:
                    # loaded device params share the grad/fsdp sharding at
                    # stage 3: flatten local shards directly (fp32 cast in
                    # the shard fetch)
                    self._offload.layout.flatten(
                        self.state.params, out=self._offload.master)
                else:
                    loaded = jax.device_get(
                        self._replicate_gather(self.state.params))
                    self._offload.layout.flatten(loaded,
                                                 out=self._offload.master)
        self.global_steps = client_state.get("global_steps", 0)
        self.micro_steps = client_state.get("micro_steps", 0)
        # resync the host loss-scale mirror from the restored device
        # automaton (one-time device_get at a checkpoint boundary)
        ls = jax.device_get(self.state.loss_scale)
        self._host_ls.load(ls.cur_scale, ls.cur_hysteresis,
                           ls.last_overflow_iter, ls.iteration)
        self._host_lr_cache = None
        if load_lr_scheduler_states and self.lr_scheduler is not None and \
                client_state.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
        if rc.enabled:
            self._last_good_ckpt = (load_dir, tag)
        return load_dir, client_state
