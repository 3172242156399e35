#!/usr/bin/env python
"""Frozen schema for the unified telemetry JSONL event stream.

Every line ``deepspeed_tpu/monitor/telemetry.py`` emits must validate
against the per-kind schema below.  The schema is FROZEN: adding an event
kind or a field means editing this file in the same change, and the tier-1
test (``tests/unit/test_telemetry_schema.py``) diffs ``EVENT_KINDS``
against the telemetry module so the two cannot drift silently.

Usage:
    python scripts/check_telemetry_schema.py <events.jsonl> [more.jsonl ...]
    python scripts/check_telemetry_schema.py --prom <metrics.txt> [...]
    python scripts/check_telemetry_schema.py --shards <shard_dir> [...]
    python scripts/check_telemetry_schema.py --cluster <payload.json> [...]
    python scripts/check_telemetry_schema.py --ledger <ledger.jsonl>
    python scripts/check_telemetry_schema.py --incidents <bundle_or_dir> [...]
    python scripts/check_telemetry_schema.py --tune <overlay_or_dir> [...]

The ``--incidents`` mode validates incident bundles written by the
incident plane (``monitor/incidents.py``): each bundle directory must
contain a schema-valid ``incident.json`` (trigger kind from the frozen
:data:`INCIDENT_TRIGGERS` vocabulary, registry snapshot, correlation
section) plus ``ring.jsonl`` whose every line validates against the
event schema.  A path may be one bundle or a parent ``incidents/``
directory of bundles.

The ``--ledger`` mode validates a metric ledger (the autotuner's control
plane appends one row per scored metric of a trial to its
``ledger_path``): every row must carry
``ts``/``run``/``bench``/``metric``/``value`` with an optional
``unit``.

The ``--prom`` mode validates a Prometheus text exposition page (the
``monitor/export.py`` /metrics surface) instead: metric-name grammar,
known TYPE declarations, numeric sample values.

The ``--shards`` mode validates a distributed-telemetry shard directory
(``events.rank{N}.jsonl`` per process, rotated generations included):
every event on every shard must validate AND carry a ``rank`` stamp
matching its filename.  The ``--cluster`` mode validates a saved
``/cluster`` endpoint payload (``monitor/aggregate.py`` snapshot shape).

Exit code 0 when every event on every file validates; 1 otherwise (each
offending line is reported with its file:lineno).
"""

import glob
import json
import os
import re
import sys

# required: field -> allowed types.  optional: same, may be absent.
# Unknown kinds AND unknown fields are rejected — the stream is a contract.
_NUM = (int, float)

SCHEMA = {
    "span": {
        "required": {"ts": _NUM, "kind": str, "name": str, "dur_ms": _NUM},
        "optional": {"step": int, "attrs": dict},
    },
    "gauge": {
        "required": {"ts": _NUM, "kind": str, "name": str, "value": _NUM,
                     "peak": _NUM},
        "optional": {"step": int},
    },
    "counter": {
        "required": {"ts": _NUM, "kind": str, "name": str, "value": _NUM},
        "optional": {"step": int},
    },
    # collective-tracing events (comm/comm.py _traced spans + analytic
    # censuses): payload bytes are dtype-TRUE; timed records add the
    # host-observed duration, participant count, and achieved bus
    # bandwidth against the analytic per-link peak
    # (comm/topology_model.py).  ``name`` is validated against COMM_OPS.
    # Quantized collectives (comm/quantize.py) add ``wire_dtype`` (the
    # on-wire payload dtype, e.g. "int8" — ``bytes`` is then the reduced
    # wire payload) and ``bytes_saved`` (dtype-true baseline minus wire
    # bytes); unquantized records omit both.
    "comm": {
        "required": {"ts": _NUM, "kind": str, "name": str, "bytes": int,
                     "axis": str},
        "optional": {"dtype": str, "dur_ms": _NUM, "world": int,
                     "busbw_gbps": _NUM, "peak_gbps": _NUM,
                     "wire_dtype": str, "bytes_saved": int},
    },
    "heartbeat": {
        "required": {"ts": _NUM, "kind": str, "name": str, "step": int},
        "optional": {"step_ms": _NUM},
    },
    # a step that never ends ("engine/step": the hang verdict) or one that
    # ran long (the step's span name: the summary of a slow-step record,
    # with the record's ``where``, the stepping thread's CPU seconds and
    # the innermost program span of the last sample)
    "stall": {
        "required": {"ts": _NUM, "kind": str, "name": str, "step": int,
                     "gap_s": _NUM, "median_step_s": _NUM,
                     "threshold_s": _NUM},
        "optional": {"where": str, "cpu_s": _NUM, "span": str},
    },
    "meta": {
        "required": {"ts": _NUM, "kind": str, "name": str},
        "optional": {"attrs": dict, "step": int},
    },
    # fault-tolerance events (runtime/resilience.py): I/O retries
    # ("fault/retry", "fault/dataloader_retry"), checkpoint fallback
    # ("fault/ckpt_fallback"), preemption ("fault/preempt_requested",
    # "fault/preempted"), divergence ("fault/divergence",
    # "fault/auto_restore")
    "fault": {
        "required": {"ts": _NUM, "kind": str, "name": str},
        "optional": {"attrs": dict, "step": int},
    },
    # serving-robustness events (inference/robustness.py): admission
    # ("serve/admit"), typed rejection ("serve/reject"), load shedding
    # ("serve/shed"), deadline cancels ("serve/deadline"), per-slot fault
    # eviction ("serve/evict"), graceful drain ("serve/drain"), normal
    # completion ("serve/finish"), and recovered transient faults
    # ("serve/fault").  Typed reasons ride in attrs["reason"].  The
    # ``name`` field is validated against SERVE_EVENTS below.
    "serve": {
        "required": {"ts": _NUM, "kind": str, "name": str},
        "optional": {"attrs": dict, "step": int},
    },
    # profiling-plane compile tracing (monitor/profiling.py
    # CompileWatcher): one "compile/miss" record per jit-cache miss with
    # the wrapped site, the observed wall time (compile + first
    # execution), the site's cumulative miss count, and the cause diff vs
    # the previous call signature; one "compile/storm" record per storm
    # onset (site "*", count = misses inside the sliding window).  The
    # ``name`` field is validated against COMPILE_EVENTS, ``cause``
    # against COMPILE_CAUSES.
    "compile": {
        "required": {"ts": _NUM, "kind": str, "name": str, "site": str,
                     "count": int},
        "optional": {"dur_ms": _NUM, "cause": str, "window_s": _NUM,
                     "attrs": dict, "step": int},
    },
    # fleet-routing events (inference/fleet.py FleetRouter): replica
    # spawns/respawns, routed dispatches ("fleet/route"), affinity-miss
    # spills, injected dispatch faults, redispatches after a replica
    # failure, abrupt kills, fencing, graceful drains, fleet-level sheds
    # (redispatch budget, fleet drain), and autoscale decisions.  The
    # ``name`` field is validated against FLEET_EVENTS below.
    "fleet": {
        "required": {"ts": _NUM, "kind": str, "name": str},
        "optional": {"attrs": dict, "step": int},
    },
    # incident-plane events (monitor/incidents.py IncidentManager): one
    # "incident/open" per trigger (id, trigger kind from
    # INCIDENT_TRIGGERS, verdict source + detail) and one
    # "incident/written" once its bundle landed on disk (ring-dump event
    # count + bundle path).  The ``name`` field is validated against
    # INCIDENT_EVENTS, ``trigger`` against INCIDENT_TRIGGERS.
    "incident": {
        "required": {"ts": _NUM, "kind": str, "name": str, "id": str,
                     "trigger": str},
        "optional": {"source": str, "detail": str, "step": int,
                     "events": int, "path": str},
    },
    # autotuning control-plane events (autotuning/controlplane.py
    # ControlPlane): one "tune/trial_start" per launched trial (attrs:
    # trial / knobs), one "tune/trial_result" per scored trial (attrs:
    # trial / objective / metrics / snapshot_hash), one
    # "tune/trial_pruned" per point rejected by the feasibility model
    # before running (attrs: trial / knobs / reason), and one
    # "tune/overlay_written" when the winning overlay lands on disk
    # (attrs: trial / path / snapshot_hash).  The ``name`` field is
    # validated against TUNE_EVENTS below.
    "tune": {
        "required": {"ts": _NUM, "kind": str, "name": str},
        "optional": {"attrs": dict, "step": int},
    },
}

# FROZEN: the words a slow-step record's ``where`` (and the ``stall`` event
# that carries its summary) is one of; byte-identical to
# ``deepspeed_tpu.monitor.telemetry.SLOW_STEP_WHERE`` (a tier-1 test diffs
# the two; the rules are in docs/telemetry.md "The slow-step record").
SLOW_STEP_WHERE = ("compile", "host_python", "descheduled", "blocked_io",
                   "runtime_wait", "other_thread", "caller", "unknown")

# FROZEN vocabulary of span names the program passes to ``Telemetry.span``
# — must stay byte-identical to ``deepspeed_tpu.monitor.telemetry.
# SPAN_NAMES`` (a tier-1 test diffs the two).  Every one is recorded in
# the always-on span ring and opened as a profiler annotation; with
# telemetry enabled each close also emits a ``span`` event under the same
# name.  The stream check does not reject other span names (tools and
# tests open their own); the tuple is what readers may key on.
# ``serve/loop`` is one ``ServingEngine.step()``; beneath it
# ``serve/admit``, ``serve/prefill`` (> build, ``serve/step``, fetch,
# sample) and ``serve/decode`` (> build, ``serve/step``, fetch, sample);
# ``engine/train_batch`` holds ``engine/input``, ``engine/dispatch``, with
# the prefetch iterator ``engine/input_wait`` and, with a monitor on,
# ``engine/monitor`` (the host floats it takes: a wait for the device).  ``setup/*`` are the
# process's start (the package's import, each engine's construction and
# its parts) and ``compile`` one program JAX compiled or read from the
# persistent cache, as the compile account closes it (docs/telemetry.md).
SPAN_NAMES = (
    "checkpoint/load", "checkpoint/save",
    "engine/forward", "engine/backward", "engine/step",
    "engine/train_batch", "engine/input", "engine/dispatch",
    "engine/input_wait", "engine/monitor", "param_stream/train_step",
    "serve/loop", "serve/admit", "serve/step",
    "serve/prefill", "serve/prefill/build", "serve/prefill/fetch",
    "serve/prefill/sample",
    "serve/decode", "serve/decode/build", "serve/decode/fetch",
    "serve/decode/sample",
    "setup/import", "setup/engine", "setup/engine/state",
    "setup/engine/weights", "setup/engine/pools", "compile",
)

# FROZEN: what a serving dispatch of a latent-attention / dropless-expert
# model counts on the device, in this order — keys of each such dispatch in
# ``engine.last_step["dispatches"]`` and attrs of its ``serve/step`` span
# (byte-identical to ``deepspeed_tpu.models.transformer.SERVE_COUNTERS``);
# and the ``jax.named_scope``s ``telemetry.op_scopes`` tells apart in that
# model's serving programs (``deepspeed_tpu.monitor.telemetry.
# SERVE_SCOPES``).  A tier-1 test diffs both.
SERVE_COUNTERS = ("selected", "context_keys", "expert_pairs",
                  "expert_load_max", "expert_rows")
SERVE_SCOPES = ("select", "latent_attn", "router", "experts",
                "shared_expert", "attn_window", "attn_full", "attn_gate",
                "latent_ctx", "ssm_proj", "ssm_conv", "ssm_scan", "lin_attn",
                "block_select", "ckey_write", "sparse_attn")
# FROZEN: what a model with sliding-window layers adds to the same
# dispatches and spans, reckoned on the host from the lengths
# (byte-identical to ``deepspeed_tpu.inference.serving.WINDOW_COUNTS``;
# it brings no ``selected``)
WINDOW_COUNTS = ("context_keys", "attended_keys", "pages_full",
                 "pages_ring")
# FROZEN: what a prefill dispatch and its span say of a prompt served in
# chunks (byte-identical to
# ``deepspeed_tpu.inference.serving.CHUNK_COUNTS``): ``ctx_entries``, of a
# latent-attention model WITHOUT a selection (which publishes no
# ``selected``), the pool entries one layer's attention walks for the
# chunk from what was cached before it, reckoned on the host; ``chunk``,
# of the chunked policy whatever the model, the chunk's index in its
# prompt
CHUNK_COUNTS = ("ctx_entries", "chunk")
# FROZEN: the fields of a dispatch's record in ``last_step`` that say what
# it compiled to, each ``"pallas"`` or ``"jnp"`` (byte-identical to
# ``deepspeed_tpu.inference.serving.DISPATCH_IMPLS``): ``kv_write`` (the
# write of the page pools; every dispatch), ``experts`` (a dropless expert
# layer's grouped product; every dispatch of the target model that has
# one), ``latent`` (a latent-attention model's prefill over its pool: the
# ``latent_attention_prefill`` kernel of a model WITHOUT a selection, or
# the XLA walk; its prefill dispatches alone), ``state`` (a state-space
# layer's one-row recurrence on the state pool: the ``ssm_decode_update``
# kernel, or the jnp slice, step and masked write; the decode dispatches
# of a model with such layers)
DISPATCH_IMPLS = ("kv_write", "experts", "latent", "state")
# FROZEN: what a model with state-space layers adds to each prefill and
# decode dispatch and its ``serve/step`` span, from the host
# (byte-identical to ``deepspeed_tpu.inference.serving.STATE_COUNTS``):
# ``state_slots``, the rows whose recurrent state the dispatch advanced,
# and ``state_bytes``, the bytes of state and of the convolution's last
# inputs it had to read and write for them, all such layers
STATE_COUNTS = ("state_slots", "state_bytes")

# FROZEN vocabulary of serve-kind event names — must stay byte-identical
# to ``deepspeed_tpu.inference.robustness.SERVE_EVENTS`` (the tier-1 test
# diffs the two).  The prefix_* names belong to the prefix-cache subsystem
# (inference/prefix_cache.py): cached-page attach hits, copy-on-write
# copies, newly indexed pages, and reclaim-tier evictions.
# "serve/backend" records the attention backend an engine was built with
# (attrs: attention_backend / impl / interpret) so the stream's serve/step
# spans are attributable to the kernel path that produced them.
SERVE_EVENTS = (
    "serve/admit", "serve/reject", "serve/shed", "serve/deadline",
    "serve/evict", "serve/drain", "serve/finish", "serve/fault",
    "serve/prefix_hit", "serve/prefix_cow", "serve/prefix_insert",
    "serve/prefix_evict",
    # "serve/compile_storm" fires once per recompile-storm onset seen by
    # the serving engine's CompileWatcher (monitor/profiling.py): shapes
    # are churning faster than the jit cache amortises (attrs: misses).
    "serve/compile_storm",
    "serve/backend",
    # scheduler plane (inference/scheduler.py): the once-per-engine
    # policy meta record ("serve/sched": policy / prefill_chunk_tokens /
    # speculative / num_draft_tokens / the monolithic policy's
    # prefill_piece_rows), one chunked-prefill dispatch
    # ("serve/prefill_chunk": req_id / slot / start / tokens / remaining /
    # slo_class), one draft-model proposal ("serve/spec_draft": slots /
    # window) and its target verification ("serve/spec_verify": slots /
    # window / accepted / rejected)
    "serve/sched", "serve/prefill_chunk",
    "serve/spec_draft", "serve/spec_verify",
    # the once-per-engine record of a model with state-space layers
    # ("serve/state": layers / slot_bytes / dtype / conv_dtype, the
    # recurrent state it keeps a slot beside the pages, and redo, what a
    # dropped decode row costs: "prefill_from_zero"; a model with
    # linear-attention layers says kind: "linear" in conv_dtype's place)
    "serve/state",
    # per-request lifecycle trace (RequestTracer): one event per state
    # transition, each carrying req_id plus the derived latencies so a
    # request's full history is reconstructible from the JSONL stream
    # alone.  The "queued" state is implicit between admitted and
    # prefill_start (queue_wait_ms attr); the "decode" phase is implicit
    # between first_token and the terminal (tpot_ms attr: the mean gap
    # between successive tokens as they reached the host).  Every admitted
    # request reaches EXACTLY ONE of the four terminals — the
    # trace-completeness invariant leak_report() audits.
    "serve/request/admitted", "serve/request/prefill_start",
    "serve/request/first_token",
    "serve/request/finish", "serve/request/shed",
    "serve/request/deadline", "serve/request/evict",
    # critical-path attribution (monitor/attribution.py): one record
    # adjacent to each terminal carrying the ordered stage breakdown
    # (queue/prefill/migrate/gap/decode _ms attrs, summing to e2e_ms by
    # construction), the terminal it pairs with, chunk count, whether
    # the request crossed a prefill->decode migration, and the "path"
    # flow string ds_trace_export renders as arrows
    "serve/request/attr",
)

# FROZEN vocabulary of fleet-kind event names — must stay byte-identical
# to ``deepspeed_tpu.inference.fleet.FLEET_EVENTS`` (the tier-1 test
# diffs the two).  Typed reasons / replica ids / epochs ride in attrs.
FLEET_EVENTS = (
    "fleet/spawn", "fleet/respawn", "fleet/route", "fleet/spill",
    "fleet/dispatch_fault", "fleet/redispatch", "fleet/kill",
    "fleet/fence", "fleet/drain", "fleet/shed",
    "fleet/scale_up", "fleet/scale_down",
    "fleet/migrate_start", "fleet/migrate_commit", "fleet/migrate_fault",
    "fleet/migrate_abort", "fleet/local_prefill",
    "fleet/worker_lost",
    "fleet/retry", "fleet/breaker_open", "fleet/breaker_close",
    "fleet/dup_call_dropped",
)

# FROZEN vocabulary of the fleet gauge family — must stay byte-identical
# to ``deepspeed_tpu.inference.fleet.FLEET_GAUGES`` (the tier-1 test
# diffs the two).  Every gauge event under the ``fleet/`` prefix is
# validated against this tuple; most of the family is registry-only
# (scraped by the exporter) and only the breaker gauges are also
# emitted as gauge EVENTS at transition time.
FLEET_GAUGES = (
    "fleet/replicas", "fleet/healthy", "fleet/pending",
    "fleet/queue_depth", "fleet/redispatches", "fleet/workers_lost",
    "fleet/heartbeat_age_s", "fleet/migrating", "fleet/migrated_pages",
    "fleet/dedup_skipped_pages", "fleet/prefill_queue_depth",
    "fleet/decode_queue_depth", "fleet/breaker_open_replicas",
    "fleet/breaker_opens", "fleet/breaker_closes", "fleet/retries",
    "fleet/dup_calls_dropped",
)

# FROZEN vocabulary of tune-kind event names — must stay byte-identical
# to ``deepspeed_tpu.autotuning.controlplane.TUNE_EVENTS`` (the tier-1
# test diffs the two).  Trial ids / knob dicts / objective scores ride
# in attrs.
TUNE_EVENTS = (
    "tune/trial_start", "tune/trial_result", "tune/trial_pruned",
    "tune/overlay_written",
)

# Distributed (sharded) mode stamps every record with its origin rank so
# merged streams keep attribution; single-rank streams omit it.
for _spec in SCHEMA.values():
    _spec["optional"]["rank"] = int

# FROZEN vocabulary of comm-kind event names — must stay byte-identical
# to ``deepspeed_tpu.comm.comm.COMM_OPS`` (the tier-1 test diffs the
# two).  Covers every traced dist.* verb plus the analytic censuses for
# XLA-inserted reductions (engine grad reduce, param-stream replication).
COMM_OPS = (
    "all_reduce", "all_gather", "reduce_scatter", "all_to_all",
    "broadcast", "scatter", "ppermute", "barrier",
)

# FROZEN vocabulary of the quantized-collective savings gauges — must
# stay byte-identical to ``deepspeed_tpu.comm.quantize.QUANT_GAUGES``
# (the tier-1 test diffs the two).  One gauge per quantizable wire path;
# any gauge event under the ``comm/`` prefix is validated against this
# tuple (the busbw gauges are registry-only and never emitted as gauge
# events).
QUANT_GAUGES = (
    "comm/all_reduce/quant_bytes_saved",
    "comm/reduce_scatter/quant_bytes_saved",
    "comm/kv_migrate/quant_bytes_saved",
)

# FROZEN vocabulary of the comm/compute-overlap gauges — must stay
# byte-identical to ``deepspeed_tpu.runtime.zero.stage_plan.
# OVERLAP_GAUGES`` (the tier-1 test diffs the two).  Emitted per step by
# the engine when ``zero_optimization.overlap.enabled``; every gauge
# event under the ``comm/overlap/`` prefix is validated against this
# tuple (other ``comm/`` gauges stay on the quantization vocabulary).
OVERLAP_GAUGES = (
    "comm/overlap/exposed_ms",
    "comm/overlap/overlapped_ms",
    "comm/overlap/gather_buckets",
    "comm/overlap/rs_buckets",
    "comm/overlap/prefetch_depth",
)

# FROZEN vocabulary of the tiered-memory-engine gauges — must stay
# byte-identical to ``deepspeed_tpu.runtime.tiered_store.TIER_GAUGES``
# (the tier-1 test diffs the two).  Occupancy per tier, prefetch
# hit/miss counters, eviction/writeback counts, achieved bandwidth per
# transfer path, and int8-tier savings; every gauge event under the
# ``tier/`` prefix is validated against this tuple.
TIER_GAUGES = (
    "tier/hbm_bytes",
    "tier/host_bytes",
    "tier/nvme_bytes",
    "tier/prefetch_hits",
    "tier/prefetch_misses",
    "tier/evictions",
    "tier/writebacks",
    "tier/h2d_gbps",
    "tier/d2h_gbps",
    "tier/nvme_read_gbps",
    "tier/nvme_write_gbps",
    "tier/quant_bytes_saved",
)

# FROZEN vocabulary of the cluster aggregation gauges — must stay
# byte-identical to ``deepspeed_tpu.monitor.aggregate.CLUSTER_GAUGES``
# (the tier-1 test diffs the two).
CLUSTER_GAUGES = (
    "cluster/ranks",
    "cluster/missing_ranks",
    "cluster/step_skew_ms",
    "cluster/step_skew_rel",
    "cluster/collective_spread_ms",
    "cluster/straggler_rank",
)

# FROZEN vocabularies of the profiling plane — each must stay
# byte-identical to its twin in ``deepspeed_tpu.monitor.profiling``
# (the tier-1 test diffs every pair).  compile-kind event names; the
# cause labels a compile/miss may carry; the logical top-level spans
# HBM/roofline attribution keys on; and the per-span metric leaves of
# the ``mem/<span>/<metric>`` and ``roofline/<span>/<metric>`` gauge
# families (validated below for every gauge event under those prefixes).
COMPILE_EVENTS = ("compile/miss", "compile/storm")
COMPILE_CAUSES = ("cold", "new_shape", "new_dtype", "new_callable",
                  "new_static")
PROFILE_SPANS = ("fwd", "bwd", "step", "train_batch", "serve_step",
                 "prefill")
MEM_METRICS = ("live_bytes", "peak_bytes", "frag_bytes")
ROOFLINE_METRICS = ("compute_frac", "bandwidth_frac")

# FROZEN vocabularies of the incident plane — each must stay
# byte-identical to its twin in ``deepspeed_tpu.monitor.incidents``
# (the tier-1 test diffs both pairs).  Incident-kind event names, and
# the closed set of trigger kinds (one per verdict source: watchdog
# stall, recompile-storm onset, cluster straggler, non-empty
# leak_report(), fleet replica kill / fence, SLO burn-rate alert).
INCIDENT_EVENTS = ("incident/open", "incident/written")
INCIDENT_TRIGGERS = ("stall", "storm", "straggler", "leak",
                     "replica_kill", "replica_fence", "slo_burn",
                     "worker_lost", "breaker_open")

# FROZEN vocabularies of the time-attribution plane — each must stay
# byte-identical to its twin in ``deepspeed_tpu.monitor.attribution``
# (the tier-1 test diffs both pairs).  STEP_ATTR_GAUGES is the per-step
# decomposition gauge family (every gauge event under the ``step/attr/``
# prefix is validated against it); ATTR_STAGES is the ordered stage
# vocabulary of the ``serve/request/attr`` critical-path record — its
# attrs must carry one ``<stage>_ms`` per entry plus ``e2e_ms`` the
# stages sum to.
STEP_ATTR_GAUGES = (
    "step/attr/compute_ms",
    "step/attr/exposed_comm_ms",
    "step/attr/input_wait_ms",
    "step/attr/host_sync_ms",
    "step/attr/compile_ms",
    "step/attr/exposed_comm_frac",
)
ATTR_STAGES = ("queue", "prefill", "migrate", "gap", "decode")

# FROZEN vocabulary of the trainer's flash-attention plan — must stay
# byte-identical to ``deepspeed_tpu.models.transformer.ATTN_PLAN`` and
# ``ATTN_SAVED`` under the ``train/attn/`` prefix (the tier-1 test diffs
# the two).  Set once, when the first batch shows the step's shapes: what
# the three flash kernels of one optimizer step visit, mask, compute and
# need, and the bytes a layer keeps of one micro-batch's forward call for
# its backward pass (docs/telemetry.md).
TRAIN_ATTN_GAUGES = (
    "train/attn/tiles_visited",
    "train/attn/tiles_masked",
    "train/attn/pairs_visited",
    "train/attn/pairs_needed",
    "train/attn/saved_residual_bytes",
)

EVENT_KINDS = tuple(SCHEMA)


def validate_event(event):
    """Validate one decoded event dict.  Returns a list of problem strings
    (empty = valid)."""
    problems = []
    if not isinstance(event, dict):
        return [f"event is {type(event).__name__}, not an object"]
    kind = event.get("kind")
    if kind not in SCHEMA:
        return [f"unknown kind {kind!r}"]
    spec = SCHEMA[kind]
    for field, types in spec["required"].items():
        if field not in event:
            problems.append(f"{kind}: missing required field {field!r}")
        elif not isinstance(event[field], types) or \
                isinstance(event[field], bool):
            problems.append(
                f"{kind}: field {field!r} has type "
                f"{type(event[field]).__name__}")
    allowed = set(spec["required"]) | set(spec["optional"])
    for field, value in event.items():
        if field not in allowed:
            problems.append(f"{kind}: unknown field {field!r}")
        elif field in spec["optional"] and (
                not isinstance(value, spec["optional"][field])
                or isinstance(value, bool)):
            problems.append(
                f"{kind}: optional field {field!r} has type "
                f"{type(value).__name__}")
    if kind == "stall" and "where" in event and \
            event["where"] not in SLOW_STEP_WHERE:
        problems.append(f"stall: unknown where {event['where']!r}")
    if kind == "serve" and isinstance(event.get("name"), str) and \
            event["name"] not in SERVE_EVENTS:
        problems.append(f"serve: unknown event name {event['name']!r}")
    if kind == "serve" and event.get("name") == "serve/request/attr":
        attrs = event.get("attrs")
        if not isinstance(attrs, dict):
            problems.append("serve: serve/request/attr carries no attrs")
        else:
            for key in tuple(f"{s}_ms" for s in ATTR_STAGES) + ("e2e_ms",):
                v = attrs.get(key)
                if not isinstance(v, _NUM) or isinstance(v, bool):
                    problems.append(
                        f"serve: serve/request/attr attr {key!r} is "
                        f"{type(v).__name__}, not a number")
    if kind == "fleet" and isinstance(event.get("name"), str) and \
            event["name"] not in FLEET_EVENTS:
        problems.append(f"fleet: unknown event name {event['name']!r}")
    if kind == "tune" and isinstance(event.get("name"), str) and \
            event["name"] not in TUNE_EVENTS:
        problems.append(f"tune: unknown event name {event['name']!r}")
    if kind == "comm" and isinstance(event.get("name"), str) and \
            event["name"] not in COMM_OPS:
        problems.append(f"comm: unknown collective {event['name']!r}")
    if kind == "gauge" and isinstance(event.get("name"), str) and \
            event["name"].startswith("cluster/") and \
            event["name"] not in CLUSTER_GAUGES:
        problems.append(f"gauge: unknown cluster gauge {event['name']!r}")
    if kind == "gauge" and isinstance(event.get("name"), str) and \
            event["name"].startswith("comm/overlap/") and \
            event["name"] not in OVERLAP_GAUGES:
        problems.append(f"gauge: unknown overlap gauge {event['name']!r}")
    if kind == "gauge" and isinstance(event.get("name"), str) and \
            event["name"].startswith("comm/") and \
            not event["name"].startswith("comm/overlap/") and \
            event["name"] not in QUANT_GAUGES:
        problems.append(f"gauge: unknown comm gauge {event['name']!r}")
    if kind == "gauge" and isinstance(event.get("name"), str) and \
            event["name"].startswith("tier/") and \
            event["name"] not in TIER_GAUGES:
        problems.append(f"gauge: unknown tier gauge {event['name']!r}")
    if kind == "gauge" and isinstance(event.get("name"), str) and \
            event["name"].startswith("step/attr/") and \
            event["name"] not in STEP_ATTR_GAUGES:
        problems.append(
            f"gauge: unknown step/attr gauge {event['name']!r}")
    if kind == "gauge" and isinstance(event.get("name"), str) and \
            event["name"].startswith("train/attn/") and \
            event["name"] not in TRAIN_ATTN_GAUGES:
        problems.append(
            f"gauge: unknown train/attn gauge {event['name']!r}")
    if kind == "gauge" and isinstance(event.get("name"), str) and \
            event["name"].startswith("fleet/") and \
            event["name"] not in FLEET_GAUGES:
        problems.append(f"gauge: unknown fleet gauge {event['name']!r}")
    if kind == "compile" and isinstance(event.get("name"), str):
        if event["name"] not in COMPILE_EVENTS:
            problems.append(
                f"compile: unknown event name {event['name']!r}")
        cause = event.get("cause")
        if cause is not None and cause not in COMPILE_CAUSES:
            problems.append(f"compile: unknown cause {cause!r}")
    if kind == "incident":
        if isinstance(event.get("name"), str) and \
                event["name"] not in INCIDENT_EVENTS:
            problems.append(
                f"incident: unknown event name {event['name']!r}")
        trigger = event.get("trigger")
        if isinstance(trigger, str) and trigger not in INCIDENT_TRIGGERS:
            problems.append(f"incident: unknown trigger {trigger!r}")
    if kind == "gauge" and isinstance(event.get("name"), str):
        for prefix, metrics in (("mem/", MEM_METRICS),
                                ("roofline/", ROOFLINE_METRICS)):
            if not event["name"].startswith(prefix):
                continue
            parts = event["name"].split("/")
            if len(parts) != 3 or parts[1] not in PROFILE_SPANS or \
                    parts[2] not in metrics:
                problems.append(
                    f"gauge: unknown {prefix}* gauge {event['name']!r}")
    return problems


def validate_stream(lines):
    """Validate an iterable of JSONL lines.  Yields (lineno, problem)
    pairs; empty/whitespace lines are skipped."""
    for i, line in enumerate(lines, start=1):
        line = line.strip()
        if not line:
            continue
        try:
            event = json.loads(line)
        except ValueError as e:
            yield i, f"not valid JSON: {e}"
            continue
        for p in validate_event(event):
            yield i, p


def validate_file(path):
    with open(path) as f:
        return list(validate_stream(f))


# ----------------------------------------------------------------------
# distributed-telemetry shard directories (monitor/aggregate.py)
# ----------------------------------------------------------------------
_SHARD_RE = re.compile(r"events\.rank(\d+)\.jsonl(\.\d+)?$")


def validate_shard_dir(shard_dir):
    """Validate every per-rank shard under ``shard_dir``.  Beyond the
    per-event schema, each record's ``rank`` stamp must match the rank in
    its shard's filename — a mis-stamped shard would silently corrupt the
    cross-rank alignment.  Returns ``(problems, shards_seen)``."""
    problems = []
    paths = sorted(glob.glob(os.path.join(shard_dir, "events.rank*.jsonl")) +
                   glob.glob(os.path.join(shard_dir, "events.rank*.jsonl.*")))
    shards = 0
    for path in paths:
        m = _SHARD_RE.search(path)
        if not m:
            continue
        shards += 1
        want_rank = int(m.group(1))
        with open(path) as f:
            lines = f.readlines()
        for i, line in enumerate(lines, start=1):
            stripped = line.strip()
            if not stripped:
                continue
            try:
                event = json.loads(stripped)
            except ValueError:
                # torn tail of a live writer: tolerated on the final
                # line (aggregation skips and counts it), fatal
                # anywhere else
                if i != len(lines):
                    problems.append(
                        f"{path}:{i}: unparseable non-final line")
                continue
            for p in validate_event(event):
                problems.append(f"{path}:{i}: {p}")
            got = event.get("rank") if isinstance(event, dict) else None
            if got != want_rank:
                problems.append(
                    f"{path}:{i}: rank stamp {got!r} != shard "
                    f"rank {want_rank}")
    if not shards:
        problems.append(f"{shard_dir}: no events.rank*.jsonl shards found")
    return problems, shards


# ----------------------------------------------------------------------
# /cluster endpoint payload (monitor/aggregate.py aggregate_cluster)
# ----------------------------------------------------------------------
def _check(problems, cond, msg):
    if not cond:
        problems.append(msg)


def validate_cluster_payload(obj):
    """Validate a decoded ``/cluster`` snapshot (the aggregate_cluster
    dict).  Returns a list of problem strings (empty = valid)."""
    problems = []
    if not isinstance(obj, dict):
        return [f"payload is {type(obj).__name__}, not an object"]
    for field, types in (("ts", _NUM), ("shard_dir", str), ("ranks", list),
                         ("missing_ranks", list), ("torn_lines", int),
                         ("steps", dict), ("step_skew", dict),
                         ("collectives", dict), ("straggler", dict)):
        if field not in obj:
            problems.append(f"missing required field {field!r}")
        elif not isinstance(obj[field], types):
            problems.append(f"field {field!r} has type "
                            f"{type(obj[field]).__name__}")
    if problems:
        return problems
    _check(problems, all(isinstance(r, int) for r in obj["ranks"]),
           "ranks: non-int rank")
    _check(problems, all(isinstance(r, int) for r in obj["missing_ranks"]),
           "missing_ranks: non-int rank")
    steps = obj["steps"]
    for f in ("count", "aligned"):
        _check(problems, isinstance(steps.get(f), int),
               f"steps.{f}: not an int")
    _check(problems,
           steps.get("median_step_ms") is None or
           isinstance(steps["median_step_ms"], _NUM),
           "steps.median_step_ms: not numeric or null")
    skew = obj["step_skew"]
    _check(problems, isinstance(skew.get("aligned"), int),
           "step_skew.aligned: not an int")
    for f in ("max_spread_ms", "p50_spread_ms", "max_rel"):
        _check(problems,
               skew.get(f) is None or isinstance(skew[f], _NUM),
               f"step_skew.{f}: not numeric or null")
    for op, row in obj["collectives"].items():
        if op not in COMM_OPS:
            problems.append(f"collectives: unknown collective {op!r}")
            continue
        if not isinstance(row, dict):
            problems.append(f"collectives.{op}: not an object")
            continue
        for f in ("calls", "bytes", "timed_calls", "timed_bytes"):
            _check(problems, isinstance(row.get(f), int),
                   f"collectives.{op}.{f}: not an int")
        _check(problems, isinstance(row.get("dur_ms"), _NUM),
               f"collectives.{op}.dur_ms: not numeric")
        for f in ("achieved_gbps", "busbw_gbps", "peak_gbps"):
            _check(problems,
                   row.get(f) is None or isinstance(row[f], _NUM),
                   f"collectives.{op}.{f}: not numeric or null")
        spread = row.get("arrival_spread_ms")
        _check(problems,
               spread is None or (
                   isinstance(spread, dict) and
                   isinstance(spread.get("p50"), _NUM) and
                   isinstance(spread.get("max"), _NUM)),
               f"collectives.{op}.arrival_spread_ms: malformed")
    strag = obj["straggler"]
    _check(problems,
           strag.get("rank") is None or isinstance(strag["rank"], int),
           "straggler.rank: not an int or null")
    _check(problems,
           strag.get("metric") in (None, "step_time", "collective_entry"),
           f"straggler.metric: unknown metric {strag.get('metric')!r}")
    _check(problems, isinstance(strag.get("threshold"), _NUM),
           "straggler.threshold: not numeric")
    _check(problems, isinstance(strag.get("window"), int),
           "straggler.window: not an int")
    per_rank = strag.get("per_rank")
    if not isinstance(per_rank, dict):
        problems.append("straggler.per_rank: not an object")
    else:
        for r, row in per_rank.items():
            ok = (isinstance(row, dict) and
                  isinstance(row.get("steps"), int) and
                  (row.get("median_step_ms") is None or
                   isinstance(row["median_step_ms"], _NUM)) and
                  isinstance(row.get("mean_entry_delay_ms"), _NUM))
            _check(problems, ok,
                   f"straggler.per_rank[{r!r}]: malformed row")
    return problems


def validate_cluster_file(path):
    with open(path) as f:
        try:
            obj = json.load(f)
        except ValueError as e:
            return [f"not valid JSON: {e}"]
    return validate_cluster_payload(obj)


# ----------------------------------------------------------------------
# metric ledger (autotuning/controlplane.py appends)
# ----------------------------------------------------------------------
# One row per (run, bench, metric): ``run`` groups every metric one
# trial recorded.
LEDGER_REQUIRED = {"ts": _NUM, "run": str, "bench": str, "metric": str,
                   "value": _NUM}
LEDGER_OPTIONAL = {"unit": str}


def validate_ledger_row(row):
    """Validate one decoded ledger row.  Returns a list of problem
    strings (empty = valid)."""
    problems = []
    if not isinstance(row, dict):
        return [f"row is {type(row).__name__}, not an object"]
    for field, types in LEDGER_REQUIRED.items():
        if field not in row:
            problems.append(f"ledger: missing required field {field!r}")
        elif not isinstance(row[field], types) or \
                isinstance(row[field], bool):
            problems.append(f"ledger: field {field!r} has type "
                            f"{type(row[field]).__name__}")
    allowed = set(LEDGER_REQUIRED) | set(LEDGER_OPTIONAL)
    for field, value in row.items():
        if field not in allowed:
            problems.append(f"ledger: unknown field {field!r}")
        elif field in LEDGER_OPTIONAL and (
                not isinstance(value, LEDGER_OPTIONAL[field])
                or isinstance(value, bool)):
            problems.append(f"ledger: optional field {field!r} has type "
                            f"{type(value).__name__}")
    return problems


def validate_ledger_file(path):
    problems = []
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError as e:
                problems.append(f"{path}:{i}: not valid JSON: {e}")
                continue
            for p in validate_ledger_row(row):
                problems.append(f"{path}:{i}: {p}")
    return problems


# ----------------------------------------------------------------------
# autotuning overlays + tune journals (autotuning/controlplane.py)
# ----------------------------------------------------------------------
# A persisted overlay is ``{"overlay": <ds-config fragment>,
# "provenance": {trial, snapshot_hash, objective, ts, knobs}}`` — the
# fragment is deep-merged over the user config at initialize() /
# create_serving_engine() time, and the provenance stamp ties it back to
# the trial + telemetry snapshot that won.
OVERLAY_PROVENANCE = {"trial": str, "snapshot_hash": str,
                      "objective": _NUM, "ts": _NUM, "knobs": dict}


def validate_overlay_payload(obj):
    """Validate one decoded overlay file.  Returns a list of problem
    strings (empty = valid)."""
    problems = []
    if not isinstance(obj, dict):
        return [f"overlay is {type(obj).__name__}, not an object"]
    if not isinstance(obj.get("overlay"), dict):
        problems.append("overlay: missing or non-object 'overlay' fragment")
    prov = obj.get("provenance")
    if not isinstance(prov, dict):
        problems.append("overlay: missing or non-object 'provenance'")
        return problems
    for field, types in OVERLAY_PROVENANCE.items():
        if field not in prov:
            problems.append(
                f"overlay: provenance missing required field {field!r}")
        elif not isinstance(prov[field], types) or \
                isinstance(prov[field], bool):
            problems.append(
                f"overlay: provenance field {field!r} has type "
                f"{type(prov[field]).__name__}")
    return problems


def validate_overlay_file(path):
    with open(path) as f:
        try:
            obj = json.load(f)
        except ValueError as e:
            return [f"{path}: not valid JSON: {e}"]
    return [f"{path}: {p}" for p in validate_overlay_payload(obj)]


def validate_tune_path(path):
    """Validate ``path`` as one overlay JSON file, or as a tune results
    directory (the control plane's ``results_dir``): the overlay (if
    present), every ``events*.jsonl`` tune stream, and every trial
    journal ``*.json``.  Returns ``(problems, artifacts_seen)``."""
    if os.path.isfile(path):
        return validate_overlay_file(path), 1
    problems = []
    seen = 0
    if not os.path.isdir(path):
        return [f"{path}: not a file or directory"], 0
    for stream in sorted(glob.glob(os.path.join(path, "**",
                                                "events*.jsonl"),
                                   recursive=True)):
        seen += 1
        for i, p in validate_file(stream):
            problems.append(f"{stream}:{i}: {p}")
    for jpath in sorted(glob.glob(os.path.join(path, "*.json"))):
        seen += 1
        if os.path.basename(jpath) == "overlay.json":
            problems.extend(validate_overlay_file(jpath))
            continue
        with open(jpath) as f:
            try:
                obj = json.load(f)
            except ValueError as e:
                problems.append(f"{jpath}: not valid JSON: {e}")
                continue
        if not isinstance(obj, dict) or \
                not isinstance(obj.get("ds_config"), dict):
            problems.append(
                f"{jpath}: trial journal missing ds_config object")
    if not seen:
        problems.append(f"{path}: no tune artifacts found")
    return problems, seen


# ----------------------------------------------------------------------
# incident bundles (monitor/incidents.py IncidentManager._write_bundle)
# ----------------------------------------------------------------------
# Each bundle is a directory ``<bundle_dir>/<inc-NNNN-kind>/`` holding
# ``incident.json`` (the typed bundle) + ``ring.jsonl`` (the flight
# recorder's dump, one schema-valid event per line).
INCIDENT_BUNDLE_FILES = ("incident.json", "ring.jsonl")


def validate_incident_bundle(dirpath):
    """Validate one incident bundle directory.  Returns a list of
    problem strings (empty = valid)."""
    problems = []
    inc_path = os.path.join(dirpath, "incident.json")
    ring_path = os.path.join(dirpath, "ring.jsonl")
    if not os.path.isfile(inc_path):
        return [f"{dirpath}: missing incident.json"]
    with open(inc_path) as f:
        try:
            obj = json.load(f)
        except ValueError as e:
            return [f"{inc_path}: not valid JSON: {e}"]
    if not isinstance(obj, dict):
        return [f"{inc_path}: bundle is {type(obj).__name__}, not an object"]
    _check(problems, isinstance(obj.get("id"), str) and obj.get("id"),
           f"{inc_path}: missing or non-string id")
    _check(problems,
           isinstance(obj.get("ts"), _NUM) and
           not isinstance(obj.get("ts"), bool),
           f"{inc_path}: missing or non-numeric ts")
    trig = obj.get("trigger")
    if not isinstance(trig, dict):
        problems.append(f"{inc_path}: trigger is not an object")
    else:
        _check(problems, trig.get("kind") in INCIDENT_TRIGGERS,
               f"{inc_path}: unknown trigger kind {trig.get('kind')!r}")
        _check(problems, isinstance(trig.get("source"), str),
               f"{inc_path}: trigger.source is not a string")
    reg = obj.get("registry")
    if not isinstance(reg, dict):
        problems.append(f"{inc_path}: registry is not an object")
    else:
        for f_ in ("counters", "gauges", "histograms"):
            _check(problems, isinstance(reg.get(f_), dict),
                   f"{inc_path}: registry.{f_} is not an object")
    corr = obj.get("correlation")
    if not isinstance(corr, dict):
        problems.append(f"{inc_path}: correlation is not an object")
    else:
        _check(problems,
               isinstance(corr.get("window_s"), _NUM) and
               not isinstance(corr.get("window_s"), bool),
               f"{inc_path}: correlation.window_s is not numeric")
        _check(problems, isinstance(corr.get("windows"), list),
               f"{inc_path}: correlation.windows is not a list")
        _check(problems, isinstance(corr.get("links"), list),
               f"{inc_path}: correlation.links is not a list")
    ring = obj.get("ring")
    if not isinstance(ring, dict):
        problems.append(f"{inc_path}: ring is not an object")
    else:
        _check(problems,
               isinstance(ring.get("events"), int) and
               not isinstance(ring.get("events"), bool),
               f"{inc_path}: ring.events is not an int")
        _check(problems, isinstance(ring.get("path"), str),
               f"{inc_path}: ring.path is not a string")
    if not os.path.isfile(ring_path):
        problems.append(f"{dirpath}: missing ring.jsonl")
    else:
        for i, p in validate_file(ring_path):
            problems.append(f"{ring_path}:{i}: {p}")
    return problems


def validate_incidents_path(path):
    """Validate ``path`` as one bundle directory, or as a parent
    ``incidents/`` directory of bundles.  Returns ``(problems,
    bundles_seen)``."""
    if os.path.isfile(os.path.join(path, "incident.json")):
        return validate_incident_bundle(path), 1
    problems = []
    bundles = 0
    for entry in sorted(os.listdir(path) if os.path.isdir(path) else []):
        sub = os.path.join(path, entry)
        if os.path.isfile(os.path.join(sub, "incident.json")):
            bundles += 1
            problems.extend(validate_incident_bundle(sub))
    if not bundles:
        problems.append(f"{path}: no incident bundles found")
    return problems, bundles


# ----------------------------------------------------------------------
# exporter metric-name validation (monitor/export.py)
# ----------------------------------------------------------------------
# Prometheus text exposition format 0.0.4, the exporter's /metrics
# surface.  Every exported family name must match the metric-name
# grammar, carry a known TYPE, and every sample must belong to a typed
# family (summaries also own their _sum/_count companions).
PROM_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
PROM_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})?\s+(\S+)$")
PROM_TYPES = ("counter", "gauge", "summary", "histogram", "untyped")


def validate_prom_exposition(text):
    """Validate a Prometheus text exposition page (the exporter's
    ``/metrics`` body).  Returns a list of problem strings (empty =
    valid)."""
    problems = []
    typed = set()
    for i, line in enumerate(text.splitlines(), start=1):
        line = line.rstrip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4:
                problems.append(f"line {i}: malformed TYPE line")
                continue
            _, _, name, ptype = parts
            if not PROM_NAME_RE.match(name):
                problems.append(f"line {i}: illegal metric name {name!r}")
            if ptype not in PROM_TYPES:
                problems.append(f"line {i}: unknown type {ptype!r}")
            typed.add(name)
            continue
        if line.startswith("#"):
            continue    # HELP / comments
        m = PROM_SAMPLE_RE.match(line)
        if not m:
            problems.append(f"line {i}: malformed sample line {line!r}")
            continue
        name, _, value = m.group(1), m.group(2), m.group(3)
        try:
            float(value)
        except ValueError:
            if value not in ("+Inf", "-Inf", "NaN"):
                problems.append(
                    f"line {i}: non-numeric sample value {value!r}")
        family = name
        for suffix in ("_sum", "_count", "_bucket"):
            if name.endswith(suffix) and name[:-len(suffix)] in typed:
                family = name[:-len(suffix)]
                break
        if family not in typed:
            problems.append(
                f"line {i}: sample {name!r} has no TYPE declaration")
    return problems


def validate_prom_file(path):
    with open(path) as f:
        return validate_prom_exposition(f.read())


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    if not argv:
        print(__doc__)
        return 2
    if argv[0] == "--prom":
        bad = 0
        for path in argv[1:]:
            for p in validate_prom_file(path):
                print(f"{path}: {p}")
                bad += 1
        if bad:
            print(f"FAIL: {bad} problem(s)")
            return 1
        print("OK: exposition validated")
        return 0
    if argv[0] == "--shards":
        bad = shards = 0
        for shard_dir in argv[1:]:
            problems, n = validate_shard_dir(shard_dir)
            shards += n
            for p in problems:
                print(p)
                bad += 1
        if bad:
            print(f"FAIL: {bad} problem(s) across {shards} shard(s)")
            return 1
        print(f"OK: {shards} shard(s) validated")
        return 0
    if argv[0] == "--ledger":
        bad = 0
        for path in argv[1:]:
            for p in validate_ledger_file(path):
                print(p)
                bad += 1
        if bad:
            print(f"FAIL: {bad} problem(s)")
            return 1
        print("OK: ledger validated")
        return 0
    if argv[0] == "--cluster":
        bad = 0
        for path in argv[1:]:
            for p in validate_cluster_file(path):
                print(f"{path}: {p}")
                bad += 1
        if bad:
            print(f"FAIL: {bad} problem(s)")
            return 1
        print("OK: cluster payload validated")
        return 0
    if argv[0] == "--tune":
        bad = artifacts = 0
        for path in argv[1:]:
            problems, n = validate_tune_path(path)
            artifacts += n
            for p in problems:
                print(p)
                bad += 1
        if bad:
            print(f"FAIL: {bad} problem(s) across {artifacts} artifact(s)")
            return 1
        print(f"OK: {artifacts} tune artifact(s) validated")
        return 0
    if argv[0] == "--incidents":
        bad = bundles = 0
        for path in argv[1:]:
            problems, n = validate_incidents_path(path)
            bundles += n
            for p in problems:
                print(p)
                bad += 1
        if bad:
            print(f"FAIL: {bad} problem(s) across {bundles} bundle(s)")
            return 1
        print(f"OK: {bundles} bundle(s) validated")
        return 0
    bad = 0
    total = 0
    for path in argv:
        with open(path) as f:
            for i, line in enumerate(f, start=1):
                if not line.strip():
                    continue
                total += 1
                try:
                    event = json.loads(line)
                    problems = validate_event(event)
                except ValueError as e:
                    problems = [f"not valid JSON: {e}"]
                for p in problems:
                    print(f"{path}:{i}: {p}")
                    bad += 1
    if bad:
        print(f"FAIL: {bad} problem(s) across {total} event(s)")
        return 1
    print(f"OK: {total} event(s) validated")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
