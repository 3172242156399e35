"""The autotuner.

Parity: reference ``autotuning/autotuner.py:39`` (``Autotuner``: profile the
model (``:707`` model-info run), prune ZeRO stages by a memory model, tune
micro-batch size per stage from measured short runs, write
``autotuning_results/`` and report the best config; entered from the
launcher ``runner.py:351``).

TPU design: phase 1 searches (zero stage × micro-batch size); phase 2
runs coordinate descent over the winning stage's template knobs
(``config_templates.py``: gradient-accumulation steps, optimizer offload
device, remat policy, Pallas attention tile sizes — the knobs round-2's
hand tuning actually moved).  Memory feasibility uses the ZeRO memory
model (params/grads/optimizer bytes per chip given the fsdp degree)
against the accelerator's reported HBM, seeded by the phase-1 winner's
measured ``n_params`` (the reference's model-info run); each trial builds
a real engine and measures steady-state samples/sec over
``end_profile_step - start_profile_step`` fused steps.
"""

import itertools
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from deepspeed_tpu.autotuning.config import (AUTOTUNING,
                                             AUTOTUNING_METRIC_THROUGHPUT,
                                             AutotuningConfig)
from deepspeed_tpu.autotuning.scheduler import Experiment, ResourceManager
from deepspeed_tpu.utils.logging import logger

BYTES_PER_PARAM_BF16 = 2
# Adam: fp32 master + m + v
BYTES_OPTIM_PER_PARAM = 12
BYTES_GRAD_PER_PARAM = 4


def model_memory_per_chip(num_params: int, stage: int, dp: int,
                          offload_optimizer: bool = False) -> int:
    """ZeRO memory model (reference ``autotuner.py`` stage pruning):
    bytes/chip of params + grads + optimizer states."""
    p = num_params * BYTES_PER_PARAM_BF16
    g = num_params * BYTES_GRAD_PER_PARAM
    o = 0 if offload_optimizer else num_params * BYTES_OPTIM_PER_PARAM
    if stage >= 3:
        p //= dp
    if stage >= 2:
        g //= dp
    if stage >= 1:
        o //= dp
    return p + g + o


def gather_buffer_bytes(num_params: int, n_layers: int,
                        prefetch_depth: int) -> int:
    """HBM cost of the ``zero_optimization.overlap`` gather pipeline:
    ``prefetch_depth + 1`` per-layer gathered (UNsharded) working sets
    ride the scan carry, so deeper prefetch buys overlap with layer-sized
    slabs of HBM.  The per-layer size is the stacked model's params
    spread evenly over its layers — the right scale for the transformer
    stacks ``layer_scan`` pipelines."""
    per_layer = (int(num_params) // max(1, int(n_layers))) \
        * BYTES_PER_PARAM_BF16
    return (int(prefetch_depth) + 1) * per_layer


class Autotuner:

    def __init__(self, ds_config: Dict[str, Any],
                 model_num_params: Optional[int] = None,
                 hbm_bytes: Optional[int] = None,
                 active_resources: Optional[Dict[str, Any]] = None):
        if not isinstance(ds_config, dict):
            # launcher entry (runner.py): an argparse Namespace carrying
            # --deepspeed_config; reference Autotuner(args, resource_pool)
            path = getattr(ds_config, "deepspeed_config", None) or \
                getattr(ds_config, "ds_config", None)
            if isinstance(path, str):
                with open(path) as f:
                    ds_config = json.load(f)
            elif isinstance(path, dict):
                ds_config = path
            else:
                raise ValueError(
                    "Autotuner needs a ds_config dict or an args namespace "
                    "with --deepspeed_config")
        self.active_resources = active_resources
        self.base_config = {k: v for k, v in ds_config.items()
                            if k != AUTOTUNING}
        self.at_config = AutotuningConfig(ds_config.get(AUTOTUNING, {}))
        self.model_num_params = model_num_params
        if hbm_bytes is None:
            try:
                from deepspeed_tpu.accelerator import get_accelerator
                hbm_bytes = get_accelerator().total_memory()
            except Exception:
                hbm_bytes = 16 << 30
        self.hbm_bytes = hbm_bytes
        self.rm = ResourceManager(self.at_config.results_dir,
                                  metric=self.at_config.metric,
                                  overwrite=self.at_config.overwrite)
        # set by tune(): path of the persisted best config (reference
        # ds_config_optimal.json; consumed by `deepspeed --autotuning run`)
        self.optimal_config_path: Optional[str] = None

    # ------------------------------------------------------------------
    def feasible_stages(self, dp: int) -> List[int]:
        if self.model_num_params is None:
            return [0, 1, 2, 3]
        stages = [s for s in (0, 1, 2, 3)
                  if model_memory_per_chip(self.model_num_params, s, dp)
                  < self.hbm_bytes * 0.9]
        # always consider the most-sharded stage even if the model says no
        # (offload may rescue it)
        return stages or [3]

    def candidate_micro_batches(self) -> List[int]:
        at = self.at_config
        out, m = [], max(1, at.min_train_micro_batch_size_per_gpu)
        while m <= at.max_train_micro_batch_size_per_gpu and \
                len(out) < at.num_tuning_micro_batch_sizes:
            out.append(m)
            m *= 2
        return out

    def tuning_space(self, dp: int) -> List[Dict[str, Any]]:
        space = []
        for stage, micro in itertools.product(self.feasible_stages(dp),
                                              self.candidate_micro_batches()):
            cfg = dict(self.base_config)
            zo = dict(cfg.get("zero_optimization", {}))
            zo["stage"] = stage
            cfg["zero_optimization"] = zo
            cfg["train_micro_batch_size_per_gpu"] = micro
            cfg.pop("train_batch_size", None)
            space.append(cfg)
        return space

    # ------------------------------------------------------------------
    def _default_runner(self, make_batch: Callable[[int], Any],
                        model, params) -> Callable[[Experiment], Dict]:
        at = self.at_config

        def run(exp: Experiment) -> Dict[str, Any]:
            import deepspeed_tpu
            from deepspeed_tpu.autotuning.trial_worker import timed_trial
            from deepspeed_tpu.parallel import groups
            groups.reset_mesh()
            engine, *_ = deepspeed_tpu.initialize(
                model=model,
                model_parameters=jax.tree_util.tree_map(np.asarray, params),
                config=exp.ds_config)
            gas = engine.gradient_accumulation_steps_

            def batch():
                b = make_batch(engine.train_batch_size())
                if gas > 1:   # fused GAS steps consume [gas, micro*dp, ...]
                    b = jax.tree_util.tree_map(
                        lambda x: np.asarray(x).reshape(
                            (gas, -1) + np.shape(x)[1:]), b)
                return b
            return timed_trial(engine, batch,
                               at.start_profile_step, at.end_profile_step)
        return run

    def _subprocess_runner(self, model_spec: Dict[str, Any], seq: int,
                           timeout: float = 900.0,
                           cpu: bool = False) -> Callable[[Experiment], Dict]:
        """Each experiment as its OWN OS process (reference
        ``autotuning/scheduler.py`` ``ResourceManager.run_job``: trials are
        separate jobs, so one trial's OOM / allocator state / XLA live
        buffers cannot distort the next trial's measurement)."""
        import subprocess
        import sys

        at = self.at_config

        def run(exp: Experiment) -> Dict[str, Any]:
            spec = {"model": model_spec, "ds_config": exp.ds_config,
                    "model_overrides": exp.model_overrides,
                    "seq": seq, "cpu": cpu,
                    "start_profile_step": at.start_profile_step,
                    "end_profile_step": at.end_profile_step}
            out = subprocess.run(
                [sys.executable, "-m",
                 "deepspeed_tpu.autotuning.trial_worker", json.dumps(spec)],
                capture_output=True, text=True, timeout=timeout)
            if out.returncode != 0:
                raise RuntimeError(
                    f"trial {exp.name} failed (rc={out.returncode}): "
                    f"{(out.stderr or '')[-800:]}")
            for line in reversed(out.stdout.strip().splitlines()):
                try:
                    parsed = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(parsed, dict):   # stray scalar prints are
                    return parsed              # not trial results
            raise RuntimeError(f"trial {exp.name}: no JSON in worker output")
        return run

    def tune(self, model=None, params=None,
             make_batch: Optional[Callable[[int], Any]] = None,
             run_fn: Optional[Callable[[Experiment], Dict]] = None,
             model_spec: Optional[Dict[str, Any]] = None,
             seq: int = 256, trial_timeout: float = 900.0,
             trial_cpu: bool = False) -> Dict[str, Any]:
        """Run the search; returns the best ds_config.

        Three trial modes, most isolated first:
        * ``model_spec=`` — each trial in a fresh OS process (the
          reference's separate-job semantics; required for trustworthy
          OOM boundaries);
        * ``model=/params=/make_batch=`` — in-process trials (arbitrary
          non-serialisable models; measurements share one XLA heap);
        * ``run_fn=`` — caller-supplied runner.
        """
        if model_spec is None and run_fn is None and model is None:
            # launcher-driven tuning: the model spec rides in the
            # autotuning config ("model_spec": {"kind": ..., "config": ...}).
            # Resolved FIRST so the dp probe below sees subprocess mode.
            spec_cfg = getattr(self.at_config, "model_spec", None)
            if spec_cfg:
                model_spec = dict(spec_cfg)
        if model_spec is not None and not trial_cpu:
            # do NOT initialise the TPU backend in the parent: libtpu is
            # exclusive per process, and a parent holding the device would
            # starve every trial subprocess.  Probe the count out of line;
            # a probe that fails is an error, not "one device".
            import subprocess
            import sys
            out = subprocess.run(
                [sys.executable, "-c",
                 "import jax; print(jax.device_count())"],
                capture_output=True, text=True, timeout=180)
            if out.returncode != 0:
                raise RuntimeError("device-count probe failed: "
                                   + out.stderr.strip()[-500:])
            dp = int(out.stdout.strip().splitlines()[-1])
        else:
            dp = max(1, jax.device_count())
        # only the in-process default runner cannot apply model-knob
        # overrides (its model object is fixed); subprocess AND caller
        # run_fn modes both see exp.model_overrides
        model_knobs = True
        if run_fn is None and model_spec is not None:
            run_fn = self._subprocess_runner(model_spec, seq,
                                             timeout=trial_timeout,
                                             cpu=trial_cpu)
        if run_fn is None:
            if model is None or params is None or make_batch is None:
                raise ValueError(
                    "tune() needs model_spec=, model/params/make_batch, "
                    "run_fn=, or an autotuning.model_spec config entry")
            run_fn = self._default_runner(make_batch, model, params)
            model_knobs = False

        # ---- model info (reference autotuner.py:707) -----------------
        # seeds the memory model BEFORE the space is built, so stage
        # pruning can actually prune.  In-process: count the params pytree
        # directly (free).  Subprocess: one profiled trial at the most-
        # sharded stage (the worker reports n_params).  Caller run_fn:
        # skipped — the runner may not know the model at all.
        info_exp = None
        if self.model_num_params is None and params is not None:
            leaves = jax.tree_util.tree_leaves(params)
            self.model_num_params = int(sum(np.size(l) for l in leaves))
        if self.model_num_params is None and model_spec is not None:
            micro = self.candidate_micro_batches()[0]
            cfg = dict(self.base_config)
            cfg["zero_optimization"] = dict(
                cfg.get("zero_optimization", {}), stage=3)
            cfg["train_micro_batch_size_per_gpu"] = micro
            cfg.pop("train_batch_size", None)
            info_exp = Experiment(f"z3_mbs{micro}", cfg)
            self.rm.schedule_experiments([info_exp])
            self.rm.run(run_fn)
            if info_exp.done() and info_exp.result.get("n_params"):
                self.model_num_params = int(info_exp.result["n_params"])

        # ---- phase 1: ZeRO stage × micro-batch ------------------------
        space = self.tuning_space(dp)
        exps = []
        for c in space:
            name = (f"z{c['zero_optimization']['stage']}_"
                    f"mbs{c['train_micro_batch_size_per_gpu']}")
            if info_exp is not None and name == info_exp.name:
                # the model-info run already measured this point: it joins
                # the space instead of re-running.  (Outside the space it
                # stays a profile-only run and does NOT compete for best.)
                exps.append(info_exp)
                continue
            exps.append(Experiment(name, c))
        logger.info(f"autotuning: phase 1 — {len(exps)} experiments "
                    f"(stages×micro-batches), metric={self.at_config.metric}")
        self.rm.schedule_experiments(
            [e for e in exps if e is not info_exp])
        self.rm.run(run_fn)
        best = ResourceManager.best_of(exps, self.at_config.metric)
        assert best is not None, "no experiment finished"

        # ---- phase 2: per-stage template knobs around the winner ------
        # (reference config_templates/template_zero*.json; coordinate
        # descent — one knob at a time — keeps trials linear)
        if self.at_config.template_tuning:
            best = self._tune_templates(best, run_fn,
                                        model_knobs=model_knobs,
                                        model_spec=model_spec)
        logger.info(f"autotuning: best = {best.name} "
                    f"({self.at_config.metric}="
                    f"{best.result.get(self.at_config.metric):.2f})")
        out = dict(best.ds_config)
        if best.model_overrides:
            # surfaced so callers can apply the model-side winners too
            out["autotuning_model_overrides"] = dict(best.model_overrides)
        # persist for --autotuning run (reference ds_config_optimal.json)
        self.optimal_config_path = os.path.join(
            self.at_config.results_dir, "ds_config_optimal.json")
        with open(self.optimal_config_path, "w") as f:
            json.dump(out, f, indent=1)
        return out

    @staticmethod
    def skip_template_knob(path: str, ds_config: Dict) -> bool:
        """A template knob is skipped when every candidate would be a no-op
        re-measurement of the incumbent under a new name: moment_dtype is
        read only by the Adam family, and the param-stream dials only
        exist when the base config actually streams params (the engine
        enables param-stream at ANY stage when offload_param is set)."""
        opt_type = str((ds_config.get("optimizer") or {})
                       .get("type", "adamw")).lower()
        if path == "optimizer/params/moment_dtype" and \
                opt_type not in ("adam", "adamw"):
            return True
        if path.startswith("zero_optimization/offload_param/"):
            ps_device = str(((ds_config.get("zero_optimization") or {})
                             .get("offload_param") or {})
                            .get("device", "none"))
            if ps_device in ("none", "None"):
                return True
        return False

    def _tune_templates(self, best: Experiment, run_fn,
                        model_knobs: bool = True,
                        model_spec=None) -> Experiment:
        """Coordinate descent over the winning stage's template knobs."""
        from deepspeed_tpu.autotuning.config_templates import (
            KNOB_DEFAULTS, TEMPLATES, get_ds_path, model_overrides_for,
            set_ds_path)
        stage = int(best.ds_config.get("zero_optimization", {})
                    .get("stage", 0))
        tmpl = TEMPLATES.get(stage, {"ds": {}, "model": {}})
        spec_cfg = (model_spec or {}).get("config", {})

        def pick(best, exps):
            return ResourceManager.best_of([best] + exps,
                                           self.at_config.metric) or best

        for path, candidates in tmpl["ds"].items():
            if self.skip_template_knob(path, best.ds_config):
                continue
            exps = []
            for v in candidates:
                if v == get_ds_path(best.ds_config, path):
                    continue      # the incumbent value: already measured
                cfg = set_ds_path(best.ds_config, path, v)
                tag = (str(v).replace(" ", "").replace("'", "")
                       .replace("{", "").replace("}", "").replace(":", "-"))
                exps.append(Experiment(
                    f"{best.name}_{path.split('/')[-1]}-{tag}", cfg,
                    model_overrides=best.model_overrides))
            self.rm.schedule_experiments(exps)
            self.rm.run(run_fn)
            best = pick(best, exps)
        if model_knobs and (model_spec is None or
                            model_spec.get("kind", "causal_lm")
                            == "causal_lm"):
            # the template model knobs are TransformerConfig fields; other
            # model kinds (bert, ...) would TypeError in every trial
            for knob, candidates in tmpl["model"].items():
                exps = []
                for v in candidates:
                    delta = model_overrides_for(knob, v)
                    current = {
                        k: best.model_overrides.get(
                            k, spec_cfg.get(
                                k, model_overrides_for(
                                    knob, KNOB_DEFAULTS.get(knob)).get(k)))
                        for k in delta}
                    if delta == current:
                        continue   # effective incumbent: already measured
                    ov = dict(best.model_overrides, **delta)
                    tag = str(v).replace(" ", "").replace("(", "") \
                        .replace(")", "").replace(",", "x")
                    exps.append(Experiment(f"{best.name}_{knob}-{tag}",
                                           best.ds_config,
                                           model_overrides=ov))
                self.rm.schedule_experiments(exps)
                self.rm.run(run_fn)
                best = pick(best, exps)
        return best

    # parity aliases ----------------------------------------------------
    def run_autotuning(self, *a, **kw):
        return self.tune(*a, **kw)
