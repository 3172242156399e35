"""Top-level config.

Parity: reference ``deepspeed/runtime/config.py`` (``DeepSpeedConfig``,
``_batch_assertion:956`` batch-size triangle).  One JSON dict/file configures
everything; subsystem configs are typed models.

TPU extension: a ``"mesh"`` section ``{"dp":1,"fsdp":-1,"tp":1,"pp":1,"sp":1,
"ep":1}`` choosing the parallel topology; absent → all devices on the fsdp
axis (pure ZeRO-style data parallelism), matching the reference default where
the DP group is the world.
"""

import json
import os
from typing import Any, Dict, Union

from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.config_utils import (DeepSpeedConfigModel,
                                                get_scalar_param)
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.parallel.topology import TopologyConfig
from deepspeed_tpu.utils.logging import logger


class DeepSpeedConfigError(Exception):
    pass


class FP16Config(DeepSpeedConfigModel):
    enabled = C.FP16_ENABLED_DEFAULT
    loss_scale = C.FP16_LOSS_SCALE_DEFAULT
    initial_scale_power = C.FP16_INITIAL_SCALE_POWER_DEFAULT
    loss_scale_window = C.FP16_LOSS_SCALE_WINDOW_DEFAULT
    hysteresis = C.FP16_HYSTERESIS_DEFAULT
    min_loss_scale = C.FP16_MIN_LOSS_SCALE_DEFAULT
    fp16_master_weights_and_grads = C.FP16_MASTER_WEIGHTS_AND_GRADS_DEFAULT
    auto_cast = False


class BF16Config(DeepSpeedConfigModel):
    enabled = C.BFLOAT16_ENABLED_DEFAULT


class CommsConfig(DeepSpeedConfigModel):
    enabled = False
    verbose = False
    prof_all = True
    debug = False
    prof_ops = []


class CommQuantizationConfig(DeepSpeedConfigModel):
    """``"comm.quantization"`` block: the blockwise-int8 wire codec for
    bandwidth-bound collectives (``comm/quantize.py``, EQuARX-style).
    Applies to the verbs listed in ``verbs``; integer tensors and tensors
    under ``min_tensor_bytes`` always pass through unquantized."""
    enabled = False
    scheme = "int8_block"           # none | int8_block | onebit
    dtype = "int8"                  # wire dtype (int8 is the only codec)
    block_size = 256                # elements per absmax scale block
    min_tensor_bytes = 1024         # smaller tensors ride full precision
    verbs = []                      # [] -> all of QUANTIZABLE_VERBS

    def _validate(self):
        from deepspeed_tpu.comm.quantize import (QUANT_SCHEMES,
                                                 QUANTIZABLE_VERBS)
        if self.scheme not in QUANT_SCHEMES:
            raise ValueError(
                f"comm.quantization.scheme must be one of {QUANT_SCHEMES}, "
                f"got {self.scheme!r}")
        if str(self.dtype) != "int8":
            raise ValueError(
                "comm.quantization.dtype: only 'int8' is implemented, got "
                f"{self.dtype!r}")
        if int(self.block_size) < 8:
            raise ValueError("comm.quantization.block_size must be >= 8")
        if int(self.min_tensor_bytes) < 0:
            raise ValueError(
                "comm.quantization.min_tensor_bytes must be >= 0")
        self.verbs = list(self.verbs or QUANTIZABLE_VERBS)
        for v in self.verbs:
            if v not in QUANTIZABLE_VERBS:
                raise ValueError(
                    f"comm.quantization.verbs: {v!r} is not quantizable "
                    f"(expected a subset of {QUANTIZABLE_VERBS})")


class MemoryConfig(DeepSpeedConfigModel):
    """``"memory"`` top-level block: the tiered-memory engine
    (``runtime/tiered_store.py``, ZeRO-Infinity-style HBM ⇄ pinned host
    ⇄ NVMe).  ``placement_policy`` picks the default tier for tensors
    above ``persistence_threshold`` numel (smaller ones stay
    device-resident); ``quantize_tiers`` stores float host/NVMe payloads
    as the PR 15 blockwise-int8 codec with fp32 scale sidecars.  Budgets
    are bytes; 0 / None disables the bound."""
    placement_policy = "host"       # resident | host | nvme
    nvme_dir = None                 # required when any placement is nvme
    host_budget_bytes = 0           # spill host -> nvme past this
    hbm_budget_bytes = 0            # evict staged device copies past this
    persistence_threshold = 0       # numel <= threshold pins to hbm
    quantize_tiers = False          # int8 payloads on host/nvme tiers
    quant_block = 256               # codec block (elements per scale)
    overrides = {}                  # name-prefix -> tier
    aio = {}                        # AsyncIOHandle kwargs

    def _validate(self):
        tiers = ("resident", "hbm", "host", "nvme")
        if self.placement_policy not in tiers:
            raise ValueError(
                f"memory.placement_policy must be one of {tiers}, got "
                f"{self.placement_policy!r}")
        # "resident" is the user-facing alias for the hbm tier
        if self.placement_policy == "resident":
            self.placement_policy = "hbm"
        for k in ("host_budget_bytes", "hbm_budget_bytes",
                  "persistence_threshold"):
            if int(getattr(self, k) or 0) < 0:
                raise ValueError(f"memory.{k} must be >= 0")
        if int(self.quant_block) < 8:
            raise ValueError("memory.quant_block must be >= 8")
        if self.placement_policy == "nvme" and not self.nvme_dir:
            raise ValueError(
                "memory.placement_policy 'nvme' needs memory.nvme_dir")
        for name, tier in dict(self.overrides or {}).items():
            t = "hbm" if tier == "resident" else tier
            if t not in ("hbm", "host", "nvme"):
                raise ValueError(
                    f"memory.overrides[{name!r}]: unknown tier {tier!r}")
            self.overrides[name] = t


class CommConfig(DeepSpeedConfigModel):
    """``"comm"`` top-level block (reference accepts ``comm_*`` sections;
    here it holds the wire-codec policy)."""
    quantization = {}

    def _validate(self):
        if not isinstance(self.quantization, CommQuantizationConfig):
            self.quantization = CommQuantizationConfig(
                self.quantization or {})


class MonitorConfig(DeepSpeedConfigModel):
    enabled = False
    output_path = ""
    job_name = "DeepSpeedJobName"


class TensorBoardConfig(MonitorConfig):
    pass


class WandbConfig(DeepSpeedConfigModel):
    enabled = False
    group = None
    team = None
    project = "deepspeed_tpu"


class CSVConfig(MonitorConfig):
    pass


class TelemetryExportConfig(DeepSpeedConfigModel):
    """``"telemetry.export"`` block: the pull-based metrics exporter
    (``monitor/export.py``) — a rank-0 background HTTP thread serving the
    live registry as Prometheus text (``/metrics``) and a JSON snapshot
    (``/metrics.json``).  Off by default; port 0 binds an ephemeral port
    (the bound address is logged via the ``telemetry/export`` meta
    event)."""
    enabled = False
    host = "127.0.0.1"              # bind address (loopback by default)
    port = 9866                     # 0 -> ephemeral

    def _validate(self):
        if not (0 <= int(self.port) <= 65535):
            raise ValueError("telemetry.export.port must be in [0, 65535]")


class TelemetryDistributedConfig(DeepSpeedConfigModel):
    """``"telemetry.distributed"`` block: per-rank telemetry shards and
    cross-rank aggregation (``monitor/aggregate.py``).  Enabled, EVERY
    process writes its own ``events.rank{N}.jsonl`` shard (rank stamped
    into each record) and rank 0 aggregates the shards into step-time
    skew, per-collective arrival spread, comm bandwidth, and a straggler
    verdict — served on the exporter's ``/cluster`` endpoint and folded
    into the stall watchdog and ``health()``."""
    enabled = False
    shard_dir = ""                  # "" -> <output_path>/<job_name>
    skew_threshold = 2.0            # straggler = beyond this multiple of
    #                                 the cross-rank median step time
    straggler_window = 32           # aligned steps in the verdict window

    def _validate(self):
        if float(self.skew_threshold) <= 1.0:
            raise ValueError(
                "telemetry.distributed.skew_threshold must be > 1.0 "
                "(a multiple of the median; <= 1 flags healthy ranks)")
        if int(self.straggler_window) < 1:
            raise ValueError(
                "telemetry.distributed.straggler_window must be >= 1")


class TelemetryProfilingConfig(DeepSpeedConfigModel):
    """``"telemetry.profiling"`` block: the performance observability
    plane (``monitor/profiling.py``) — compile tracing with a
    recompile-storm verdict, per-span HBM attribution with a
    monotonic-growth leak detector, and the live roofline gauges.  Off
    by default; enabled it costs the hot path host-side fingerprinting
    and periodic allocator-stat reads, never a device sync."""
    enabled = False
    snapshot_interval = 8           # steps between HBM live-buffer samples
    storm_threshold = 3             # jit misses within the window -> storm
    storm_window_s = 60.0           # sliding storm window (seconds)
    leak_window = 8                 # consecutive growing samples -> leak
    peak_hbm_gbps = 0.0             # bandwidth-roofline peak override;
    #                                 0 -> chip table (comm/topology_model)

    def _validate(self):
        if int(self.snapshot_interval) < 1:
            raise ValueError(
                "telemetry.profiling.snapshot_interval must be >= 1")
        if int(self.storm_threshold) < 1:
            raise ValueError(
                "telemetry.profiling.storm_threshold must be >= 1")
        if float(self.storm_window_s) <= 0:
            raise ValueError(
                "telemetry.profiling.storm_window_s must be > 0")
        if int(self.leak_window) < 2:
            raise ValueError(
                "telemetry.profiling.leak_window must be >= 2 "
                "(growth needs at least two samples)")


class TelemetryIncidentsConfig(DeepSpeedConfigModel):
    """``"telemetry.incidents"`` block: the incident plane
    (``monitor/incidents.py``) — an always-on flight-recorder ring over
    recent telemetry events, a multi-window SLO burn-rate alerter, and a
    bundle writer that every verdict source (stall, recompile storm,
    straggler, leak, replica kill/fence, SLO burn) triggers.  Off by
    default; enabled it costs one deque append per emitted event."""
    enabled = False
    ring_capacity = 2048            # flight-recorder events kept
    ring_max_age_s = 600.0          # ...and no older than this at dump
    burn_windows = []               # [[window_s, miss_rate], ...];
    #                                 [] -> ((60, 0.5), (300, 0.1))
    burn_min_requests = 8           # SLO terminals needed per window
    cooldown_s = 60.0               # per-trigger-kind bundle cooldown
    bundle_dir = ""                 # "" -> <telemetry out dir>/incidents
    max_bundles = 16                # oldest bundle dirs pruned past this

    def _validate(self):
        if int(self.ring_capacity) < 1:
            raise ValueError(
                "telemetry.incidents.ring_capacity must be >= 1")
        if float(self.ring_max_age_s) <= 0:
            raise ValueError(
                "telemetry.incidents.ring_max_age_s must be > 0")
        if int(self.burn_min_requests) < 1:
            raise ValueError(
                "telemetry.incidents.burn_min_requests must be >= 1")
        if float(self.cooldown_s) < 0:
            raise ValueError(
                "telemetry.incidents.cooldown_s must be >= 0")
        if int(self.max_bundles) < 1:
            raise ValueError(
                "telemetry.incidents.max_bundles must be >= 1")
        for w in (self.burn_windows or []):
            try:
                pair = ((w.get("window_s"), w.get("threshold"))
                        if isinstance(w, dict) else tuple(w))
                ok = (len(pair) == 2 and float(pair[0]) > 0 and
                      0.0 < float(pair[1]) <= 1.0)
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    "telemetry.incidents.burn_windows entries must be "
                    "[window_s > 0, 0 < miss_rate <= 1] pairs")


class TelemetryAttributionConfig(DeepSpeedConfigModel):
    """``"telemetry.attribution"`` block: the time-attribution plane
    (``monitor/attribution.py``) — per-step exposed-comm decomposition
    into the frozen ``step/attr/*`` gauges (compute / exposed collective
    / input wait / host sync / compile, headline
    ``exposed_comm_frac``) plus the exporter's ``GET /attribution``
    snapshot of recent step decompositions and serving critical paths.
    Off by default; enabled it costs one interval append per
    span/comm/compile event."""
    enabled = False
    history = 64                    # per-step decompositions retained
    serve_history = 256             # serving critical paths retained

    def _validate(self):
        if int(self.history) < 1:
            raise ValueError(
                "telemetry.attribution.history must be >= 1")
        if int(self.serve_history) < 1:
            raise ValueError(
                "telemetry.attribution.serve_history must be >= 1")


class TelemetryConfig(DeepSpeedConfigModel):
    """``"telemetry"`` block: the unified JSONL event stream
    (``monitor/telemetry.py``) plus the step-stall watchdog and the
    optional pull-based metrics exporter."""
    enabled = False
    output_path = ""                # dir for events.jsonl ("" -> ./telemetry)
    job_name = "DeepSpeedJobName"
    max_file_mb = 64                # size-based rotation threshold
    max_files = 4                   # rotated generations kept
    hbm_gauges = True               # per-step device memory_stats() gauges
    stall_watchdog = True
    stall_factor = 10.0             # stall when gap > factor * median step
    stall_min_secs = 1.0            # floor on the stall threshold
    stall_poll_secs = 1.0           # watchdog poll interval
    export = {}                     # TelemetryExportConfig sub-block
    distributed = {}                # TelemetryDistributedConfig sub-block
    profiling = {}                  # TelemetryProfilingConfig sub-block
    incidents = {}                  # TelemetryIncidentsConfig sub-block
    attribution = {}                # TelemetryAttributionConfig sub-block

    def _validate(self):
        if not isinstance(self.export, TelemetryExportConfig):
            self.export = TelemetryExportConfig(self.export or {})
        if not isinstance(self.distributed, TelemetryDistributedConfig):
            self.distributed = TelemetryDistributedConfig(
                self.distributed or {})
        if not isinstance(self.profiling, TelemetryProfilingConfig):
            self.profiling = TelemetryProfilingConfig(self.profiling or {})
        if not isinstance(self.incidents, TelemetryIncidentsConfig):
            self.incidents = TelemetryIncidentsConfig(self.incidents or {})
        if not isinstance(self.attribution, TelemetryAttributionConfig):
            self.attribution = TelemetryAttributionConfig(
                self.attribution or {})


class AsyncPipelineConfig(DeepSpeedConfigModel):
    """``"async_pipeline"`` block: keeps the step loop's host side off the
    dispatch critical path — a background thread prefetches + shards batch
    n+k while step n runs, and metric readback is deferred to a
    ``sync_interval`` boundary (or a drainer thread) instead of a per-step
    device sync."""
    enabled = False
    prefetch_depth = 2     # device batches parked ahead of the consumer
    sync_interval = 1      # steps between batched metric readbacks
    io_workers = 0         # host-side sample-fetch threads (collate pool)
    drain_thread = False   # drain metrics from a thread instead of on-interval

    def _validate(self):
        if int(self.prefetch_depth) < 1:
            raise ValueError("async_pipeline.prefetch_depth must be >= 1")
        if int(self.sync_interval) < 1:
            raise ValueError("async_pipeline.sync_interval must be >= 1")
        if int(self.io_workers) < 0:
            raise ValueError("async_pipeline.io_workers must be >= 0")


class ResilienceConfig(DeepSpeedConfigModel):
    """``"resilience"`` block: the fault-tolerance layer
    (``runtime/resilience.py``) — durable atomic checkpoints with
    validation + fallback, retry policy for checkpoint/host-fs I/O,
    preemption handling, the divergence sentinel, and the deterministic
    fault-injection harness."""
    enabled = True                  # durable ckpt protocol + retries
    max_retries = 3                 # checkpoint/fs I/O retry budget
    retry_backoff_secs = 0.5        # first-retry backoff
    retry_backoff_max_secs = 30.0   # backoff cap
    retry_jitter = 0.25             # jitter fraction on each delay
    keep_last = 0                   # committed tags retained (0 = all)
    checksum = False                # per-leaf crc32 in the manifest
    preemption_handler = False      # hook SIGTERM/SIGINT
    ckpt_dir = ""                   # emergency-save / auto-restore dir
    divergence_sentinel = False     # watch loss / overflow streaks
    max_consecutive_skips = 8       # fp16 skip streak that counts as divergence
    sentinel_interval = 1           # steps between sentinel host readbacks
    on_divergence = "halt"          # "halt" | "restore"
    dataloader_max_retries = 2      # prefetch-worker transient retry budget
    dataloader_retry_backoff_secs = 0.05
    fault_injection = {}            # deterministic FaultInjector spec

    def _validate(self):
        if int(self.max_retries) < 0:
            raise ValueError("resilience.max_retries must be >= 0")
        if int(self.keep_last) < 0:
            raise ValueError("resilience.keep_last must be >= 0")
        if self.on_divergence not in ("halt", "restore"):
            raise ValueError("resilience.on_divergence must be 'halt' or "
                             "'restore'")
        if int(self.sentinel_interval) < 1:
            raise ValueError("resilience.sentinel_interval must be >= 1")
        if int(self.dataloader_max_retries) < 0:
            raise ValueError("resilience.dataloader_max_retries must be >= 0")


class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled = False
    profile_step = 1
    module_depth = -1
    top_modules = 1
    detailed = True
    output_file = None
    # per-device peak TFLOP/s for the live train/mfu gauge; 0 -> look up
    # the chip table (comm/topology_model.py) from the device kind.  The
    # gauge emits only when a peak is known (set this on CPU/test runs).
    peak_tflops = 0.0


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    partition_activations = False
    contiguous_memory_optimization = False
    cpu_checkpointing = False
    number_checkpoints = None
    synchronize_checkpoint_boundary = False
    profile = False
    # TPU extension: remat policy name passed to jax.checkpoint as
    # ``jax.checkpoint_policies`` has it (this Megatron-style surface lists
    # no names: a flash call inside the checkpointed function is computed
    # again in the backward pass whatever the policy keeps).  A
    # ``CausalTransformerLM``'s own ``remat_policy`` differs: one that
    # keeps matrix products also keeps the flash call's result and lse,
    # B·S·H·D of the compute dtype + B·H·S·4 bytes a layer, and
    # ``nothing_saveable`` keeps neither (docs/training.md)
    policy = "nothing_saveable"


class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation = "Warn"
    load_universal = False
    use_node_local_storage = False
    parallel_write = {}
    # which checkpoint engine backs save/load: "sync" (blocking orbax
    # StandardCheckpointer) or "async"/"nebula" (orbax AsyncCheckpointer —
    # the reference NebulaCheckpointEngine's background-snapshot semantics;
    # the durable commit protocol waits for the flush before the marker)
    engine = "sync"

    def _validate(self):
        if str(self.engine).lower() not in ("sync", "async", "nebula",
                                            "torch", "orbax"):
            raise ValueError(
                "checkpoint.engine must be one of sync|async|nebula "
                f"(got {self.engine!r})")


class MeshSection(DeepSpeedConfigModel):
    pp = 1
    dp = 1
    fsdp = -1
    sp = 1
    tp = 1
    ep = 1


class OptimizerConfig:
    def __init__(self, param_dict):
        self.type = param_dict.get(C.TYPE)
        self.params = dict(param_dict.get(C.OPTIMIZER_PARAMS, {}))
        self.legacy_fusion = param_dict.get(C.LEGACY_FUSION, False)


class SchedulerConfig:
    def __init__(self, param_dict):
        self.type = param_dict.get(C.TYPE)
        self.params = dict(param_dict.get(C.SCHEDULER_PARAMS, {}))


class DeepSpeedConfig:

    def __init__(self, config: Union[str, Dict[str, Any]], mesh=None,
                 world_size: int = None):
        if isinstance(config, str):
            if not os.path.exists(config):
                raise DeepSpeedConfigError(
                    f"Config file {config} not found")
            with open(config) as f:
                self._param_dict = json.load(f)
        elif isinstance(config, dict):
            self._param_dict = dict(config)
        else:
            raise DeepSpeedConfigError(
                f"Expected a dict or json path, got {type(config)}")

        # autotuning-v2: when the config names a persisted overlay
        # (autotuning.overlay_path), deep-merge the tuned fragment over
        # the user config before any parsing — initialize() consumes
        # tuned winners with zero caller changes.  Provenance (trial id +
        # snapshot hash) is kept for audit.
        from deepspeed_tpu.autotuning.overlay import maybe_apply_overlay
        self._param_dict, self.overlay_provenance = maybe_apply_overlay(
            self._param_dict)

        pd = self._param_dict
        self._warn_unknown_keys(pd)
        self._note_inert_sparse_attention(pd)
        self.mesh_config = self._parse_mesh(pd.get(C.MESH, {}))

        if world_size is None:
            import jax
            world_size = jax.device_count()
        self.world_size = world_size

        # effective data-parallel degree for the batch triangle (EP overlays
        # DP, so the ep axis carries batch shards too)
        topo = self.mesh_config.resolve(world_size)
        self.data_parallel_size = topo.dp * topo.fsdp * topo.ep

        self.train_batch_size = pd.get(C.TRAIN_BATCH_SIZE)
        self.train_micro_batch_size_per_gpu = pd.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        self.gradient_accumulation_steps = pd.get(C.GRADIENT_ACCUMULATION_STEPS)
        self._maybe_apply_elasticity(pd)
        self._configure_train_batch_size()

        self.steps_per_print = get_scalar_param(pd, C.STEPS_PER_PRINT,
                                                C.STEPS_PER_PRINT_DEFAULT)
        self.dump_state = get_scalar_param(pd, C.DUMP_STATE, C.DUMP_STATE_DEFAULT)
        self.wall_clock_breakdown = get_scalar_param(
            pd, C.WALL_CLOCK_BREAKDOWN, C.WALL_CLOCK_BREAKDOWN_DEFAULT)
        self.gradient_clipping = get_scalar_param(pd, C.GRADIENT_CLIPPING,
                                                  C.GRADIENT_CLIPPING_DEFAULT)
        self.prescale_gradients = get_scalar_param(pd, C.PRESCALE_GRADIENTS,
                                                   C.PRESCALE_GRADIENTS_DEFAULT)
        self.gradient_predivide_factor = get_scalar_param(
            pd, C.GRADIENT_PREDIVIDE_FACTOR, C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT)
        self.sparse_gradients_enabled = get_scalar_param(
            pd, C.SPARSE_GRADIENTS, C.SPARSE_GRADIENTS_DEFAULT)
        self.seed = get_scalar_param(pd, C.SEED, C.SEED_DEFAULT)

        self.zero_config = DeepSpeedZeroConfig(pd.get(C.ZERO_OPTIMIZATION, {}))
        self.fp16_config = FP16Config(pd.get(C.FP16, {}))
        bf16_dict = pd.get(C.BFLOAT16, pd.get(C.BFLOAT16_OLD, {}))
        self.bf16_config = BF16Config(bf16_dict)
        if self.fp16_config.enabled and self.bf16_config.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")

        # data_types.grad_accum_dtype (reference runtime/config.py:943):
        # the dtype the GAS carry / gradient tree rides in.  bfloat16
        # halves grad HBM — the knob that lets a 1B-param model train on
        # one 16 GB chip (Adam math still accumulates fp32 per step).
        dt = pd.get("data_types") or {}
        self.grad_accum_dtype = self._parse_grad_accum_dtype(
            dt.get("grad_accum_dtype"))

        opt_dict = pd.get(C.OPTIMIZER)
        self.optimizer_config = OptimizerConfig(opt_dict) if opt_dict else None
        sched_dict = pd.get(C.SCHEDULER)
        self.scheduler_config = SchedulerConfig(sched_dict) if sched_dict else None

        self.comms_config = CommsConfig(pd.get(C.COMMS_LOGGER, {}))
        self.comm_config = CommConfig(pd.get(C.COMM, {}))
        self.comm_quantization = self.comm_config.quantization
        self.memory_config = MemoryConfig(pd.get("memory", {}))
        self.telemetry_config = TelemetryConfig(pd.get(C.TELEMETRY, {}))
        self.async_pipeline_config = AsyncPipelineConfig(
            pd.get(C.ASYNC_PIPELINE, {}))
        self.monitor_config = {
            "tensorboard": TensorBoardConfig(pd.get(C.MONITOR_TENSORBOARD, {})),
            "wandb": WandbConfig(pd.get(C.MONITOR_WANDB, {})),
            "csv_monitor": CSVConfig(pd.get(C.MONITOR_CSV, {})),
            # the JSONL fourth writer shares the telemetry sink/config
            "telemetry": self.telemetry_config,
        }
        self.flops_profiler_config = FlopsProfilerConfig(pd.get(C.FLOPS_PROFILER, {}))
        self.activation_checkpointing_config = ActivationCheckpointingConfig(
            pd.get(C.ACTIVATION_CHECKPOINTING, {}))
        self.checkpoint_config = CheckpointConfig(pd.get(C.CHECKPOINT, {}))
        self.resilience_config = ResilienceConfig(pd.get(C.RESILIENCE, {}))

        self.elasticity_enabled = bool(pd.get(C.ELASTICITY, {}).get("enabled", False))
        self.data_efficiency_config = pd.get(C.DATA_EFFICIENCY, {})
        self.curriculum_learning_config = pd.get(C.CURRICULUM_LEARNING_LEGACY, {})
        self.progressive_layer_drop_config = pd.get(
            "progressive_layer_drop", {})
        self.eigenvalue_config = pd.get("eigenvalue", {})
        self.compression_config = pd.get(C.COMPRESSION_TRAINING, {})
        self.pipeline_config = pd.get(C.PIPELINE, {})

        self._do_sanity_check()


    # every top-level key this config understands; a typo like
    # "zero_optimisation" silently no-ops otherwise (the reference ignores
    # unknown keys too — warning is strictly more helpful)
    _KNOWN_TOP_LEVEL_KEYS = frozenset({
        C.TRAIN_BATCH_SIZE, C.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
        C.GRADIENT_ACCUMULATION_STEPS, C.OPTIMIZER, C.SCHEDULER, C.FP16,
        C.BFLOAT16, C.BFLOAT16_OLD, C.AMP, C.GRADIENT_CLIPPING,
        C.PRESCALE_GRADIENTS, C.GRADIENT_PREDIVIDE_FACTOR,
        C.STEPS_PER_PRINT, C.WALL_CLOCK_BREAKDOWN, C.DUMP_STATE,
        C.SPARSE_GRADIENTS, C.ZERO_OPTIMIZATION, C.COMMS_LOGGER, C.COMM,
        C.MESH,
        C.ACTIVATION_CHECKPOINTING, C.FLOPS_PROFILER,
        C.MONITOR_TENSORBOARD, C.MONITOR_WANDB, C.MONITOR_CSV, C.TELEMETRY,
        C.ASYNC_PIPELINE, C.RESILIENCE,
        C.DATA_EFFICIENCY, C.CURRICULUM_LEARNING_LEGACY, C.CHECKPOINT,
        C.ELASTICITY, C.COMPRESSION_TRAINING,
        C.PIPELINE, C.SEED, C.ZERO_ALLOW_UNTESTED_OPTIMIZER,
        "eigenvalue", "progressive_layer_drop", "autotuning",
        # serving-side knobs (page size, scheduler, fleet) ride the same
        # config file so one tuned overlay can cover both domains; the
        # training engine ignores the block, create_serving_engine()
        # consumes it
        "serving",
        # tiered-memory engine (runtime/tiered_store.py)
        "memory",
        # reference top-level keys accepted for config portability but
        # intentionally inert here (amp -> XLA owns mixed precision, the
        # dtype/memory knobs have no TPU analogue); listed so ported
        # configs don't warn
        "gradient_accumulation_dtype", "communication_data_type",
        "memory_breakdown",
        # data_types IS wired (grad_accum_dtype); nebula /
        # disable_allgather / zero_force_ds_cpu_optimizer are ZeRO-impl
        # knobs with no TPU analogue — accepted so ported configs don't
        # warn (reference runtime/config.py:943,:954)
        "data_types", "nebula", "disable_allgather",
        "zero_force_ds_cpu_optimizer",
        # sparse_attention gets its own notice (_note_inert_sparse_attention)
        "sparse_attention",
        # emitted by Autotuner.tune(): model-side knob winners (remat
        # policy, attention tile sizes) for the CALLER to apply when
        # rebuilding the model; informational for the engine itself
        "autotuning_model_overrides",
    })

    @staticmethod
    def _parse_grad_accum_dtype(name):
        if name is None:
            return None
        table = {"fp32": "float32", "float32": "float32",
                 "bf16": "bfloat16", "bfloat16": "bfloat16",
                 "fp16": "float16", "float16": "float16"}
        key = str(name).lower()
        if key not in table:
            raise DeepSpeedConfigError(
                "data_types.grad_accum_dtype must be one of "
                f"{sorted(set(table))}, got {name!r}")
        return table[key]

    def _note_inert_sparse_attention(self, pd):
        # 'sparse_attention' names functionality this repo DOES ship
        # (ops/sparse_attention, reference runtime/config.py:918) but the
        # engine config doesn't wire it — models opt in via the ops API.
        # One explicit line, not a silent swallow and not a scary
        # unknown-key warning.
        if "sparse_attention" in pd:
            logger.info(
                "config key 'sparse_attention' is accepted for "
                "portability but not engine-wired; enable sparsity via "
                "the model config / deepspeed_tpu.ops.sparse_attention "
                "(SparseSelfAttention / sparsity configs)")

    def _warn_unknown_keys(self, pd):
        unknown = sorted(k for k in pd if k not in
                         self._KNOWN_TOP_LEVEL_KEYS)
        if unknown:
            import difflib
            for k in unknown:
                close = difflib.get_close_matches(
                    k, self._KNOWN_TOP_LEVEL_KEYS, n=1)
                hint = f" (did you mean '{close[0]}'?)" if close else ""
                logger.warning(
                    f"config key '{k}' is not recognized and will be "
                    f"ignored{hint}")

    @staticmethod
    def _parse_mesh(mesh_dict) -> TopologyConfig:
        sec = MeshSection(mesh_dict)
        return TopologyConfig(pp=sec.pp, dp=sec.dp, fsdp=sec.fsdp,
                              sp=sec.sp, tp=sec.tp, ep=sec.ep)

    def _maybe_apply_elasticity(self, pd):
        """Elastic mode resolves the batch triangle FOR THE CURRENT WORLD
        SIZE during config parsing (parity: reference runtime/config.py
        766-806 — compute_elastic_config runs inside DeepSpeedConfig, so a
        restarted worker at a new world size gets the right batch without
        touching its config file)."""
        esec = pd.get(C.ELASTICITY, {})
        if not esec.get("enabled", False):
            return
        from deepspeed_tpu.elasticity import compute_elastic_config
        # pass the FULL param dict: compute_elastic_config also validates
        # that fixed batch keys don't conflict with elastic mode.
        # world_size is the TOTAL chip count (the solver divides by its
        # own model_parallel_size — which should match the mesh's tp so
        # the derived micro batch lines up with our dp degree)
        batch, valid, micro = compute_elastic_config(
            pd, world_size=max(1, self.world_size))
        self.train_batch_size = batch
        self.train_micro_batch_size_per_gpu = micro
        self.gradient_accumulation_steps = None   # triangle derives it
        logger.info(f"elasticity: batch={batch} micro={micro} for "
                    f"world={self.world_size}")

    # ------------------------------------------------------------------
    # Batch-size triangle: train = micro × gas × dp_world
    # (parity: reference runtime/config.py _batch_assertion / _set_batch_related_parameters)
    # ------------------------------------------------------------------
    def _configure_train_batch_size(self):
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        dp = max(1, self.data_parallel_size)

        if train is not None and micro is not None and gas is not None:
            pass
        elif train is not None and micro is not None:
            gas = train // (micro * dp)
        elif train is not None and gas is not None:
            micro = train // (gas * dp)
        elif micro is not None and gas is not None:
            train = micro * gas * dp
        elif train is not None:
            gas = 1
            micro = train // dp
        elif micro is not None:
            gas = 1
            train = micro * dp
        else:
            raise DeepSpeedConfigError(
                "At least one of train_batch_size / "
                "train_micro_batch_size_per_gpu must be set")

        self.train_batch_size = train
        self.train_micro_batch_size_per_gpu = micro
        self.gradient_accumulation_steps = gas
        self._batch_assertion()

    def _batch_assertion(self):
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        dp = max(1, self.data_parallel_size)
        if train <= 0:
            raise DeepSpeedConfigError(
                f"train_batch_size: {train} must be positive")
        if micro <= 0:
            raise DeepSpeedConfigError(
                f"micro_batch_size: {micro} must be positive")
        if gas <= 0:
            raise DeepSpeedConfigError(
                f"gradient_accumulation_steps: {gas} must be positive")
        if train != micro * gas * dp:
            raise DeepSpeedConfigError(
                f"Check batch-size settings: train_batch_size={train} must "
                f"equal micro_batch={micro} * gradient_accumulation={gas} "
                f"* dp_world={dp}")

    def _do_sanity_check(self):
        if self.zero_config.stage > 0 and self.fp16_config.enabled:
            if self.fp16_config.fp16_master_weights_and_grads and self.zero_config.stage != 2:
                raise DeepSpeedConfigError(
                    "fp16_master_weights_and_grads only supported with ZeRO-2")
        if self.optimizer_config and self.optimizer_config.type:
            from deepspeed_tpu.runtime.optimizers import OPTIMIZER_REGISTRY
            if self.optimizer_config.type.lower() not in OPTIMIZER_REGISTRY and \
                    not self._param_dict.get(C.ZERO_ALLOW_UNTESTED_OPTIMIZER, False):
                logger.warning(
                    f"Optimizer '{self.optimizer_config.type}' is not built in; "
                    "will fall back to user-supplied optax transform")

    # Convenience parity accessors used across the engine
    @property
    def zero_enabled(self):
        return self.zero_config.stage > 0

    @property
    def zero_optimization_stage(self):
        return self.zero_config.stage

    @property
    def fp16_enabled(self):
        return self.fp16_config.enabled

    @property
    def bfloat16_enabled(self):
        return self.bf16_config.enabled

    @property
    def loss_scale(self):
        return self.fp16_config.loss_scale

    @property
    def initial_dynamic_scale(self):
        return 2 ** self.fp16_config.initial_scale_power

    @property
    def dynamic_loss_scale(self):
        return self.fp16_config.loss_scale == 0

    def print(self, name="DeepSpeedConfig"):
        logger.info(f"{name}:")
        logger.info(json.dumps(self._param_dict, indent=2, sort_keys=True, default=str))
