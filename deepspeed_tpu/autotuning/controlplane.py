"""Closed-loop autotuner: the observability planes turned into a control
plane.

The legacy :class:`~deepspeed_tpu.autotuning.autotuner.Autotuner`
re-enters the launcher and ranks trials on wall-clock throughput alone.
This driver instead sweeps a declared :class:`~deepspeed_tpu.autotuning
.knobs.KnobSpace`, prunes infeasible points *before* spending a trial
(the ZeRO memory model plus the measured ``mem/<span>/peak_bytes``
gauges), and scores every surviving trial from the
``Telemetry.snapshot()`` taken at trial end — SLO histograms, roofline
fractions, attainment counters — through a weighted
:class:`~deepspeed_tpu.autotuning.objective.Objective`.

Trials execute through the SAME journaled trial runner as the legacy
tuner (``ResourceManager.run_one``), so crash/resume and skip-finished
semantics are shared.  Every trial appends ``{run: "tune-<id>", bench,
metric, value}`` rows to its ``ledger_path`` (row schema:
``scripts/check_telemetry_schema.py --ledger``), and the winner
persists as a provenance-stamped config overlay
(:mod:`~deepspeed_tpu.autotuning.overlay`) consumed at
``deepspeed.initialize()`` / ``create_serving_engine()`` time.

The control plane speaks a FROZEN ``tune/*`` event vocabulary
(:data:`TUNE_EVENTS`) through the telemetry layer; the schema checker
(``scripts/check_telemetry_schema.py``) carries the byte-identical twin
and a tier-1 test diffs the two.
"""

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

from deepspeed_tpu.autotuning.autotuner import (gather_buffer_bytes,
                                                model_memory_per_chip)
from deepspeed_tpu.autotuning.knobs import KnobSpace
from deepspeed_tpu.autotuning.objective import Objective
from deepspeed_tpu.autotuning.overlay import (OVERLAY_BASENAME, deep_merge,
                                              snapshot_hash, write_overlay)
from deepspeed_tpu.autotuning.scheduler import Experiment, ResourceManager
from deepspeed_tpu.monitor.telemetry import JsonlEventSink, Telemetry
from deepspeed_tpu.utils.logging import logger

# FROZEN vocabulary of tune-kind event names — must stay byte-identical
# to ``TUNE_EVENTS`` in scripts/check_telemetry_schema.py (the tier-1
# test diffs the two).
TUNE_EVENTS = (
    "tune/trial_start", "tune/trial_result", "tune/trial_pruned",
    "tune/overlay_written",
)


def _fresh_telemetry(out_dir: Optional[str] = None) -> Telemetry:
    """An enabled process-local Telemetry.  With ``out_dir`` it owns a
    JSONL sink there; without, it is registry-only (emit() no-ops) — the
    cheap per-trial measurement surface."""
    tel = Telemetry()
    tel.enabled = True
    if out_dir:
        tel.sink = JsonlEventSink(out_dir)
    return tel


class ControlPlane:
    """Search driver over a declared knob space.

    ``trial_fn(trial_config, telemetry) -> extra_metrics_or_None`` is the
    workload harness: it builds/runs the trial under ``trial_config``
    (the base config deep-merged with the point's fragment), records into
    the *fresh per-trial* ``telemetry`` it is handed, and may return
    directly-measured extras (e.g. ``{"tokens_per_sec": …}``).  Scoring
    happens here, from the snapshot — never inside the harness.
    """

    def __init__(self, base_config: Optional[Dict[str, Any]] = None,
                 knob_space: Optional[KnobSpace] = None,
                 objective: Optional[Objective] = None,
                 results_dir: str = "autotuning_results",
                 telemetry: Optional[Telemetry] = None,
                 hbm_bytes: Optional[int] = None,
                 model_num_params: Optional[int] = None,
                 model_num_layers: Optional[int] = None,
                 baseline_snapshot: Optional[Dict[str, Any]] = None,
                 ledger_path: Optional[str] = None,
                 bench: str = "autotune",
                 overlay_path: Optional[str] = None,
                 overwrite: bool = False,
                 max_trials: Optional[int] = None):
        self.base_config = dict(base_config or {})
        at = self.base_config.get("autotuning") or {}
        self.space = knob_space if knob_space is not None else \
            KnobSpace.from_config(at.get("knobs"), domain=at.get("domain"))
        self.objective = objective if objective is not None else \
            Objective.from_config(at.get("objective"))
        self.results_dir = results_dir
        # the control plane's own event stream (tune/* events) lands
        # under results_dir so the --tune gate can validate it alongside
        # the trial journals and the overlay
        self.telemetry = telemetry if telemetry is not None else \
            _fresh_telemetry(results_dir)
        self.hbm_bytes = hbm_bytes
        self.model_num_params = model_num_params
        self.model_num_layers = model_num_layers
        self.baseline_snapshot = baseline_snapshot
        self.ledger_path = ledger_path
        self.bench = bench
        self.overlay_path = overlay_path or at.get("overlay_path") or \
            os.path.join(results_dir, OVERLAY_BASENAME)
        self.max_trials = max_trials if max_trials is not None else \
            at.get("max_trials")
        # trials rank on the snapshot-scored objective, THROUGH the
        # legacy tuner's journaled runner (shared crash/resume semantics)
        self.rm = ResourceManager(results_dir, metric="objective",
                                  overwrite=overwrite)
        self.trials: List[Dict[str, Any]] = []
        self.pruned: List[Dict[str, Any]] = []
        self.ledger_rows_written = 0

    # -- feasibility pruning -------------------------------------------
    def _observed_peak_bytes(self) -> Optional[float]:
        """Worst measured ``mem/<span>/peak_bytes`` across spans in the
        baseline snapshot — the activation/runtime residual the analytic
        state model can't predict."""
        snap = self.baseline_snapshot
        if not snap:
            return None
        peaks = [g.get("peak", g.get("value"))
                 for name, g in snap.get("gauges", {}).items()
                 if name.startswith("mem/") and
                 name.endswith("/peak_bytes") and isinstance(g, dict)]
        peaks = [p for p in peaks if isinstance(p, (int, float))]
        return max(peaks) if peaks else None

    def prune_reason(self, trial_cfg: Dict[str, Any]) -> Optional[str]:
        """None when the point is feasible; otherwise a short reason.

        * serving: the paged allocator requires ``num_draft_tokens + 1``
          slots per page, so a draft length >= page size can never run;
        * training: analytic ZeRO state bytes
          (:func:`model_memory_per_chip`) plus the baseline snapshot's
          measured ``mem/<span>/peak_bytes`` must fit ``hbm_bytes``;
        * overlap: the gather pipeline's ``prefetch_depth + 1``
          per-layer buffers (:func:`gather_buffer_bytes`) are priced
          into the same budget — a depth whose double-buffered working
          sets don't fit is pruned before execution, like the other
          ZeRO-memory-model knobs (needs ``model_num_layers``).
        """
        serving = trial_cfg.get("serving") or {}
        page = serving.get("page_size")
        spec = (serving.get("scheduler") or {}).get("speculative") or {}
        draft = spec.get("num_draft_tokens")
        if isinstance(page, int) and isinstance(draft, int) and \
                draft + 1 > page:
            return f"draft_exceeds_page (draft={draft}, page={page})"
        mem = trial_cfg.get("memory") or {}
        placement = mem.get("placement_policy")
        if placement == "nvme" and not mem.get("nvme_dir"):
            return ("nvme_placement_no_dir (memory.placement_policy="
                    "'nvme' needs memory.nvme_dir)")
        if placement == "host" and self.model_num_params:
            # tiered host state is fp32 master + 2 Adam moments (16 B per
            # param with grads); a budget it cannot fit needs the NVMe
            # spill tier behind it
            budget = int(mem.get("host_budget_bytes") or 0)
            state_bytes = 16 * int(self.model_num_params)
            if budget and state_bytes > budget and not mem.get("nvme_dir"):
                return (f"host_budget (tiered state {state_bytes} > "
                        f"host budget {budget}, no nvme spill dir)")
        if self.hbm_bytes and self.model_num_params:
            zero = trial_cfg.get("zero_optimization") or {}
            stage = int(zero.get("stage", 0))
            dp = max(1, int(trial_cfg.get("dp", 1)))
            # a host/nvme tier placement moves optimizer state off the
            # chip exactly like offload_optimizer for the HBM model
            offload = bool(zero.get("offload_optimizer")) or \
                placement in ("host", "nvme")
            est = model_memory_per_chip(self.model_num_params, stage, dp,
                                        offload_optimizer=offload)
            observed = self._observed_peak_bytes()
            if observed:
                est += int(observed)
            overlap = zero.get("overlap") or {}
            buffers = 0
            depth = int(overlap.get("gather_prefetch_depth", 1) or 1)
            if overlap.get("enabled") and stage >= 3 and \
                    self.model_num_layers:
                buffers = gather_buffer_bytes(
                    self.model_num_params, self.model_num_layers, depth)
            if est + buffers > self.hbm_bytes:
                if buffers and est <= self.hbm_bytes:
                    return (f"overlap_depth_hbm (gather buffers {buffers} "
                            f"push {est} over hbm {self.hbm_bytes}, "
                            f"depth={depth})")
                return (f"zero_mem_model ({est + buffers} > hbm "
                        f"{self.hbm_bytes}, stage={stage})")
        return None

    # -- ledger --------------------------------------------------------
    def _append_ledger(self, run: str, metrics: Dict[str, float]):
        if not self.ledger_path:
            return
        ts = round(time.time(), 6)
        try:
            with open(self.ledger_path, "a") as f:
                for metric, value in sorted(metrics.items()):
                    f.write(json.dumps(
                        {"ts": ts, "run": run, "bench": self.bench,
                         "metric": metric, "value": float(value)}) + "\n")
                    self.ledger_rows_written += 1
        except OSError as e:  # the ledger is best-effort, never fatal
            logger.warning(f"autotuning: ledger append failed: {e}")

    # -- the sweep -----------------------------------------------------
    def tune(self, trial_fn: Callable[[Dict[str, Any], Telemetry],
                                      Optional[Dict[str, float]]]) \
            -> Dict[str, Any]:
        """Sweep the knob space, score each surviving trial from its
        end-of-trial snapshot, persist the winning overlay.  Returns a
        summary dict (``best``/``overlay_path``/``trials``/``pruned``)."""
        tel = self.telemetry
        experiments: List[Experiment] = []
        points: Dict[str, Dict[str, Any]] = {}
        fragments: Dict[str, Dict[str, Any]] = {}
        n = 0
        for point in self.space.grid():
            if self.max_trials is not None and n >= int(self.max_trials):
                logger.info(
                    f"autotuning: max_trials={self.max_trials} reached; "
                    f"remaining grid points not searched")
                break
            trial_id = f"tune-{n:04d}"
            n += 1
            fragment = self.space.fragment_for(point)
            trial_cfg = deep_merge(self.base_config, fragment)
            trial_cfg.pop("autotuning", None)
            reason = self.prune_reason(trial_cfg)
            if reason is not None:
                self.pruned.append({"trial": trial_id, "knobs": point,
                                    "reason": reason})
                tel.tune("tune/trial_pruned",
                         attrs={"trial": trial_id, "reason": reason,
                                "knobs": json.dumps(point, default=str)})
                continue
            overrides = trial_cfg.pop("autotuning_model_overrides", None)
            exp = Experiment(trial_id, trial_cfg, model_overrides=overrides)
            experiments.append(exp)
            points[trial_id] = point
            fragments[trial_id] = fragment
        self.rm.schedule_experiments(experiments)

        for exp in experiments:
            point = points[exp.name]
            tel.tune("tune/trial_start",
                     attrs={"trial": exp.name,
                            "knobs": json.dumps(point, default=str)})

            def run_fn(e: Experiment) -> Dict[str, Any]:
                trial_tel = _fresh_telemetry()
                cfg = deep_merge(e.ds_config, {} if not e.model_overrides
                                 else {"autotuning_model_overrides":
                                       dict(e.model_overrides)})
                extra = trial_fn(cfg, trial_tel) or {}
                snap = trial_tel.snapshot()
                vec = self.objective.metrics(snap, extra)
                return {"objective": self.objective.score(vec),
                        "metrics": vec,
                        "snapshot_hash": snapshot_hash(snap)}

            result = self.rm.run_one(exp, run_fn)
            vec = result.get("metrics") or {}
            score = float(result.get("objective", 0.0))
            row = {"trial": exp.name, "knobs": point, "objective": score,
                   "metrics": vec, "error": result.get("error"),
                   "wall_s": result.get("wall_s")}
            self.trials.append(row)
            self._append_ledger(exp.name, dict(vec, objective=score))
            tel.tune("tune/trial_result",
                     attrs={"trial": exp.name, "objective": score,
                            "snapshot_hash":
                                result.get("snapshot_hash", ""),
                            "metrics": json.dumps(vec, default=str)})

        best = self.rm.best_experiment()
        summary: Dict[str, Any] = {
            "trials": len(self.trials), "pruned": len(self.pruned),
            "ledger_rows": self.ledger_rows_written, "best": None,
            "overlay_path": None,
        }
        if best is None:
            logger.warning("autotuning: no successful trials; "
                           "no overlay written")
            return summary
        payload = {
            "overlay": fragments[best.name],
            "provenance": {
                "trial": best.name,
                "snapshot_hash": best.result.get("snapshot_hash",
                                                 "sha256:unjournaled"),
                "objective": float(best.result.get("objective", 0.0)),
                "ts": round(time.time(), 6),
                "knobs": dict(points[best.name]),
            },
        }
        write_overlay(self.overlay_path, payload)
        tel.tune("tune/overlay_written",
                 attrs={"trial": best.name, "path": self.overlay_path,
                        "snapshot_hash":
                            payload["provenance"]["snapshot_hash"]})
        summary["best"] = {"trial": best.name, "knobs": points[best.name],
                           "objective": payload["provenance"]["objective"]}
        summary["overlay_path"] = self.overlay_path
        return summary
