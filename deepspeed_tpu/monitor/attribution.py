"""Causal time-attribution plane: exposed-comm step decomposition and
per-request serving critical paths, sharing one interval-algebra core.

The observability stack so far records *what happened* (PR 1 spans, PR 8
comm tracing, the request lifecycle tracer); this module answers *where
the time went*:

* **Training** — :class:`AttributionPlane` taps ``Telemetry.emit`` (the
  same pattern the incident flight recorder uses) and reconstructs every
  engine step from the events already flowing: ``engine/forward`` /
  ``engine/backward`` / ``engine/step`` spans become compute intervals,
  timed ``comm`` records become collective intervals,
  ``engine/input_wait`` spans become pipeline-starvation intervals, and
  ``compile`` records become XLA-compile intervals.  The watchdog
  heartbeat (``engine/step``) closes each step window and the plane
  emits the frozen ``step/attr/*`` gauge family: a non-overlapping
  decomposition (precedence compile > compute > exposed comm > input
  wait, residual = host sync) whose headline is
  ``step/attr/exposed_comm_frac`` — the fraction of the step spent in
  collectives NOT hidden behind compute, i.e. the number ZeRO-style
  overlap work must drive to zero (docs/mfu_ceiling.md maps it onto the
  0.4855 -> ~0.55-0.62 MFU headroom).

* **Serving** — :class:`RequestAttributor` builds one ordered
  critical-path attribution per request (queue, prefill-active, migrate,
  scheduler gap, decode) from a compact :class:`TraceContext` that
  serializes into ``PrefillHandoff`` as plain primitives — wire-ready by
  construction, so a prefill -> decode migration carries its history
  across the replica boundary and the terminal-adjacent
  ``serve/request/attr`` event reports the FULL path, not the decode
  leg.  Stage sums equal the end-to-end latency by construction (the
  gap stage absorbs the residual), which is the invariant the tier-1
  FakeClock test freezes.

Both halves are host-side accounting over events/timestamps that already
exist: no device syncs, no extra compiles.  Collective durations inside
``jit`` are trace-time (the census convention), so live training
decompositions are simulation-grade off-hardware;
``tests/unit/test_attribution.py`` pins the algebra to a known workload.

Frozen vocabularies below are mirrored byte-identical in
``scripts/check_telemetry_schema.py`` (tier-1 lockstep tests diff them).
"""

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

# FROZEN gauge vocabulary of the per-step decomposition — mirrored in
# scripts/check_telemetry_schema.py (the tier-1 test diffs the two).
# All five *_ms components are disjoint by construction and sum to the
# step wall time; exposed_comm_frac = exposed_comm_ms / step_ms.
STEP_ATTR_GAUGES = (
    "step/attr/compute_ms",
    "step/attr/exposed_comm_ms",
    "step/attr/input_wait_ms",
    "step/attr/host_sync_ms",
    "step/attr/compile_ms",
    "step/attr/exposed_comm_frac",
)

# FROZEN ordered stage vocabulary of the per-request critical path (the
# ``serve/request/attr`` event carries one ``<stage>_ms`` attr per entry;
# their sum equals ``e2e_ms`` by construction).  Mirrored in
# scripts/check_telemetry_schema.py.
ATTR_STAGES = ("queue", "prefill", "migrate", "gap", "decode")

# span names folded into the training decomposition.  engine/train_batch
# encloses the whole step and is deliberately excluded; engine/step is
# the optimizer-apply span (disjoint from fwd/bwd), not the heartbeat.
COMPUTE_SPANS = ("engine/forward", "engine/backward", "engine/step")
INPUT_WAIT_SPANS = ("engine/input_wait",)


# ----------------------------------------------------------------------
# interval algebra (seconds; [t0, t1] pairs with t1 >= t0)
# ----------------------------------------------------------------------
def merge_intervals(intervals) -> List[Tuple[float, float]]:
    """Sorted union of possibly-overlapping intervals."""
    ivs = sorted((float(a), float(b)) for a, b in intervals if b > a)
    out: List[Tuple[float, float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total_length(intervals) -> float:
    """Length of the union (seconds)."""
    return sum(b - a for a, b in merge_intervals(intervals))


def overlap_length(a, b) -> float:
    """Length of the intersection of two interval unions (seconds)."""
    ma, mb = merge_intervals(a), merge_intervals(b)
    i = j = 0
    total = 0.0
    while i < len(ma) and j < len(mb):
        lo = max(ma[i][0], mb[j][0])
        hi = min(ma[i][1], mb[j][1])
        if hi > lo:
            total += hi - lo
        if ma[i][1] <= mb[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip_intervals(intervals, t0, t1) -> List[Tuple[float, float]]:
    """Intersect every interval with the window [t0, t1]."""
    out = []
    for a, b in intervals:
        lo, hi = max(float(a), t0), min(float(b), t1)
        if hi > lo:
            out.append((lo, hi))
    return out


def decompose_step(t0, t1, compute=(), comm=(), input_wait=(),
                   compiles=()) -> Dict[str, float]:
    """Pure decomposition of one step window into the frozen components.

    Precedence makes the components disjoint: compile time first (it
    nests inside the forward span on a cache miss — counting it twice
    would drive host_sync negative), then compute, then collectives not
    already under compile/compute (the EXPOSED fraction — overlapped
    collectives are free), then input wait; the residual is host sync.
    The five ``*_ms`` values therefore sum to ``step_ms`` exactly, up to
    clock noise the residual clamps away."""
    t0, t1 = float(t0), float(t1)
    step_ms = max(0.0, t1 - t0) * 1000.0
    comp = clip_intervals(compiles, t0, t1)
    compute_c = clip_intervals(compute, t0, t1)
    comm_c = clip_intervals(comm, t0, t1)
    input_c = clip_intervals(input_wait, t0, t1)
    compile_ms = total_length(comp) * 1000.0
    compute_ms = (total_length(compute_c)
                  - overlap_length(compute_c, comp)) * 1000.0
    busy = merge_intervals(list(comp) + list(compute_c))
    exposed_ms = (total_length(comm_c)
                  - overlap_length(comm_c, busy)) * 1000.0
    busy = merge_intervals(busy + comm_c)
    input_ms = (total_length(input_c)
                - overlap_length(input_c, busy)) * 1000.0
    host_ms = max(0.0, step_ms - compile_ms - compute_ms - exposed_ms
                  - input_ms)
    return {
        "step_ms": round(step_ms, 3),
        "compute_ms": round(compute_ms, 3),
        # total collective time regardless of overlap — comm_ms minus
        # exposed_comm_ms is the OVERLAPPED (free) communication, the
        # quantity the zero_optimization.overlap gauges report
        "comm_ms": round(total_length(comm_c) * 1000.0, 3),
        "exposed_comm_ms": round(exposed_ms, 3),
        "input_wait_ms": round(input_ms, 3),
        "host_sync_ms": round(host_ms, 3),
        "compile_ms": round(compile_ms, 3),
        "exposed_comm_frac": round(exposed_ms / step_ms, 6)
        if step_ms > 0 else 0.0,
    }


# ----------------------------------------------------------------------
# training half: the telemetry-owned step attributor
# ----------------------------------------------------------------------
class AttributionPlane:
    """Per-step time attribution tapped into ``Telemetry.emit``
    (``telemetry.attribution`` config block; ``telemetry.attribution`` is
    None when the block is off — callers gate on that single check).

    ``record`` ingests only span / comm / compile / heartbeat events (and
    the serving ``serve/request/attr`` records, kept for the exporter
    snapshot) — its own gauge emissions recurse into ``emit`` once and
    fall straight through the kind filter, so the tap is re-entrancy
    safe.  Span and comm records stamp ``ts`` at their END (the sink
    convention), so each becomes the interval
    ``[ts - dur_ms/1000, ts]``.  The watchdog heartbeat closes a step;
    engines running without a watchdog call :meth:`beat` directly."""

    def __init__(self, telemetry, history=64, serve_history=256):
        self.telemetry = telemetry
        self.history = deque(maxlen=max(1, int(history)))
        self.serve_history = deque(maxlen=max(1, int(serve_history)))
        self._lock = threading.Lock()
        self._compute: List[Tuple[float, float]] = []
        self._comm: List[Tuple[float, float]] = []
        self._input: List[Tuple[float, float]] = []
        self._compiles: List[Tuple[float, float]] = []
        self._last_beat = None
        self.steps_attributed = 0

    @staticmethod
    def _interval(event) -> Optional[Tuple[float, float]]:
        try:
            ts = float(event["ts"])
            dur_ms = float(event["dur_ms"])
        except (KeyError, TypeError, ValueError):
            return None
        if dur_ms < 0:
            return None
        return (ts - dur_ms / 1000.0, ts)

    def record(self, event: dict):
        """Fold one emitted event into the pending step (called from
        inside ``Telemetry.emit`` — must stay cheap and never raise)."""
        kind = event.get("kind")
        if kind == "span":
            name = event.get("name")
            iv = self._interval(event)
            if iv is None:
                return
            if name in COMPUTE_SPANS:
                with self._lock:
                    self._compute.append(iv)
            elif name in INPUT_WAIT_SPANS:
                with self._lock:
                    self._input.append(iv)
        elif kind == "comm":
            iv = self._interval(event)
            if iv is not None:
                with self._lock:
                    self._comm.append(iv)
        elif kind == "compile":
            iv = self._interval(event)
            if iv is not None:
                with self._lock:
                    self._compiles.append(iv)
        elif kind == "heartbeat" and event.get("name") == "engine/step":
            step_ms = event.get("step_ms")
            self._close(event.get("step"), step_ms,
                        float(event.get("ts", 0.0)))
        elif kind == "serve" and event.get("name") == "serve/request/attr":
            attrs = event.get("attrs")
            if isinstance(attrs, dict):
                with self._lock:
                    self.serve_history.append(dict(attrs))

    def beat(self, step, now=None):
        """Close the step ending now — the no-watchdog path (the engine
        calls this from its per-step telemetry tail; with a watchdog the
        heartbeat event drives :meth:`record` instead).  The first beat
        only arms the window, mirroring the watchdog contract."""
        now = float(now) if now is not None else time.time()
        with self._lock:
            last, self._last_beat = self._last_beat, now
        step_ms = (now - last) * 1000.0 if last is not None else None
        self._close(step, step_ms, now)

    def _close(self, step, step_ms, t_end):
        if step_ms is None or step_ms <= 0:
            # first beat of the run: nothing measurable yet — drop any
            # warmup intervals so they can't bleed into step 1
            with self._lock:
                self._reset_pending(t_end)
            return
        t0 = t_end - step_ms / 1000.0
        with self._lock:
            rec = decompose_step(t0, t_end, self._compute, self._comm,
                                 self._input, self._compiles)
            self._reset_pending(t_end)
            rec["step"] = int(step) if step is not None else -1
            rec["t0"] = round(t0, 6)
            rec["t1"] = round(t_end, 6)
            self.history.append(rec)
            self.steps_attributed += 1
        # emit OUTSIDE the lock: gauge() -> emit() -> record() recurses
        # into this plane (and the incident ring) once per gauge
        tel = self.telemetry
        if tel is not None and tel.enabled:
            s = rec["step"] if rec["step"] >= 0 else None
            for key in ("compute_ms", "exposed_comm_ms", "input_wait_ms",
                        "host_sync_ms", "compile_ms", "exposed_comm_frac"):
                tel.gauge(f"step/attr/{key}", rec[key], step=s)

    def _reset_pending(self, t_end):
        """Drop intervals consumed by the closed window; keep anything
        extending past it (it belongs to the next step).  Caller holds
        the lock."""
        for attr in ("_compute", "_comm", "_input", "_compiles"):
            kept = [(a, b) for a, b in getattr(self, attr) if b > t_end]
            setattr(self, attr, kept)

    def snapshot(self) -> Dict[str, Any]:
        """JSON-safe plane state — the ``GET /attribution`` payload:
        recent per-step decompositions plus the most recent serving
        critical paths seen going past on the event stream."""
        with self._lock:
            steps = [dict(r) for r in self.history]
            serve = [dict(r) for r in self.serve_history]
        return {
            "steps_attributed": self.steps_attributed,
            "steps": steps,
            "last": steps[-1] if steps else None,
            "requests": serve,
        }


# ----------------------------------------------------------------------
# serving half: wire-propagable per-request critical paths
# ----------------------------------------------------------------------
@dataclass
class TraceContext:
    """Compact, wire-ready per-request timing context.  Engine-clock
    seconds; ``-1.0`` marks a state never reached (the RequestTrace
    convention).  ``to_wire``/``from_wire`` round-trip through plain
    primitives so the struct serializes into ``PrefillHandoff`` — and
    therefore across any future process boundary — unchanged."""
    req_id: Any
    t_admit: float
    t_prefill_start: float = -1.0
    t_first_token: float = -1.0
    t_handoff: float = -1.0
    t_import: float = -1.0
    prefill_active_ms: float = 0.0   # accumulated prefill dispatch time
    chunks: int = 0                  # prefill dispatches folded in
    migrated: bool = False

    def to_wire(self) -> Dict[str, Any]:
        return {
            "req_id": self.req_id,
            "t_admit": float(self.t_admit),
            "t_prefill_start": float(self.t_prefill_start),
            "t_first_token": float(self.t_first_token),
            "t_handoff": float(self.t_handoff),
            "prefill_active_ms": float(self.prefill_active_ms),
            "chunks": int(self.chunks),
        }

    @classmethod
    def from_wire(cls, wire: Dict[str, Any]) -> "TraceContext":
        return cls(
            req_id=wire.get("req_id"),
            t_admit=float(wire.get("t_admit", -1.0)),
            t_prefill_start=float(wire.get("t_prefill_start", -1.0)),
            t_first_token=float(wire.get("t_first_token", -1.0)),
            t_handoff=float(wire.get("t_handoff", -1.0)),
            prefill_active_ms=float(wire.get("prefill_active_ms", 0.0)),
            chunks=int(wire.get("chunks", 0)),
            migrated=True,
        )


def request_stages(ctx: TraceContext, t_end: float) -> Dict[str, float]:
    """Ordered stage attribution for one closed request (milliseconds).

    ``queue`` is admit -> prefill start; ``prefill`` is accumulated
    dispatch-active time; ``migrate`` is handoff-capture -> decode-side
    import; ``decode`` is first-token -> terminal minus the migration
    window; ``gap`` is the residual (scheduler wait between prefill
    chunks, handoff linger) — computed as ``e2e - sum(others)`` so the
    stage sum equals ``e2e_ms`` by construction, the invariant the
    tier-1 FakeClock test freezes."""
    e2e = max(0.0, t_end - ctx.t_admit)
    t_ps, t_ft = ctx.t_prefill_start, ctx.t_first_token
    queue = max(0.0, (t_ps if t_ps >= 0 else t_end) - ctx.t_admit)
    migrate = 0.0
    if ctx.t_handoff >= 0 and ctx.t_import >= 0:
        migrate = max(0.0, ctx.t_import - ctx.t_handoff)
    prefill = 0.0
    if t_ps >= 0:
        span = max(0.0, (t_ft if t_ft >= 0 else t_end) - t_ps)
        prefill = min(ctx.prefill_active_ms / 1000.0, span) \
            if ctx.chunks > 0 else span
    decode = max(0.0, (t_end - t_ft) - migrate) if t_ft >= 0 else 0.0
    gap = e2e - (queue + prefill + migrate + decode)
    if gap < 0:
        # clock noise / clamping pushed the parts past the whole — fold
        # the excess out of decode so the sum stays exact
        decode = max(0.0, decode + gap)
        gap = 0.0
    ms = 1000.0
    return {"queue_ms": queue * ms, "prefill_ms": prefill * ms,
            "migrate_ms": migrate * ms, "gap_ms": gap * ms,
            "decode_ms": decode * ms, "e2e_ms": e2e * ms}


class RequestAttributor:
    """Always-on critical-path bookkeeping for one serving engine —
    dict updates against the engine's injectable clock, cheap enough to
    leave on with telemetry disabled (the RequestTracer discipline).
    The engine pairs each terminal with one frozen ``serve/request/attr``
    event built from :meth:`finalize`."""

    def __init__(self, clock=None):
        self._clock = clock if clock is not None else time.monotonic
        self._open: Dict[Any, TraceContext] = {}
        self.finalized = 0
        self.migrated = 0

    def admit(self, req_id, now=None):
        now = self._clock() if now is None else now
        self._open[req_id] = TraceContext(req_id=req_id, t_admit=now)

    def prefill_start(self, req_id):
        ctx = self._open.get(req_id)
        if ctx is not None and ctx.t_prefill_start < 0:
            ctx.t_prefill_start = self._clock()

    def chunk(self, req_id, active_ms):
        """Fold one prefill dispatch's active wall time in (chunked
        scheduler chunks and the monolithic prefill both land here)."""
        ctx = self._open.get(req_id)
        if ctx is not None:
            ctx.prefill_active_ms += max(0.0, float(active_ms))
            ctx.chunks += 1

    def first_token(self, req_id):
        ctx = self._open.get(req_id)
        if ctx is not None and ctx.t_first_token < 0:
            ctx.t_first_token = self._clock()

    def capture_handoff(self, req_id) -> Optional[Dict[str, Any]]:
        """Stamp the handoff-capture time and return the wire dict for
        embedding into ``PrefillHandoff``.  The context stays open — the
        source leg still closes through :meth:`finalize` when the engine
        ends its trace."""
        ctx = self._open.get(req_id)
        if ctx is None:
            return None
        ctx.t_handoff = self._clock()
        return ctx.to_wire()

    def import_ctx(self, req_id, wire):
        """Adopt a migrated request on the decode side: rebuild the
        context from the handoff's wire dict (falling back to a fresh
        admit when an old handoff carries none) and stamp the import
        time — the migrate stage is handoff -> here."""
        if not isinstance(wire, dict):
            self.admit(req_id)
            return
        ctx = TraceContext.from_wire(wire)
        ctx.req_id = req_id
        ctx.t_import = self._clock()
        self._open[req_id] = ctx

    def discard(self, req_id):
        """Forget a context without a terminal (import rollback)."""
        self._open.pop(req_id, None)

    def finalize(self, req_id, terminal, now=None) -> \
            Optional[Dict[str, Any]]:
        """Close the context and return the flattened
        ``serve/request/attr`` attrs (None for untracked ids — the
        engine then simply emits no attr event)."""
        ctx = self._open.pop(req_id, None)
        if ctx is None:
            return None
        now = self._clock() if now is None else now
        stages = request_stages(ctx, now)
        self.finalized += 1
        if ctx.migrated:
            self.migrated += 1
        path = ">".join(
            s for s in ATTR_STAGES
            if stages[f"{s}_ms"] > 0 or s in ("queue", "decode"))
        attrs = {"req_id": req_id, "terminal": str(terminal),
                 "migrated": 1 if ctx.migrated else 0,
                 "chunks": int(ctx.chunks), "path": path}
        attrs.update({k: round(v, 3) for k, v in stages.items()})
        return attrs
