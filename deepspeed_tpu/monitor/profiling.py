"""Performance observability plane: compile tracing, HBM attribution, and
the live roofline.

PRs 7-8 instrumented the request and cluster axes; this module covers the
remaining blind spot — *why a step is slow on one chip*:

* :class:`CompileWatcher` wraps the jitted entry points (engine train
  step / fwd-bwd / apply / eval, pipe-engine grad step, serving step /
  chunk / page-copy), fingerprints every call signature (avals, static
  args, donation), and emits a frozen ``compile`` event on each cache
  miss with the observed wall time, the cumulative miss count, and a
  cause diff against the previous signature at that site (new shape vs
  new dtype vs new callable vs new static arg).  A sliding-window
  recompile-storm verdict feeds serving ``health()`` (the
  :class:`StepStallWatchdog` exempts compile time from its hang verdict by
  the always-on compile account, not by this plane).
* :class:`HbmTracker` folds periodic live-buffer snapshots
  (``jax.Device.memory_stats()``; backends without allocator stats skip
  quietly) into per-span peak attribution — frozen ``mem/<span>/*``
  gauges for live/peak/fragmentation bytes per top-level span — plus a
  monotonic-growth leak detector that ``leak_report()`` folds in.
* :func:`ProfilingPlane.roofline` turns the docs/mfu_ceiling.md
  decomposition into always-on telemetry: per-span achieved-vs-peak
  compute and bandwidth fractions (``roofline/<span>/*`` gauges) from
  the flops profiler's analytic counts and the chip tables in
  ``comm/topology_model.py``.

All three ride the same frozen-schema telemetry spine: the ``compile``
event kind and the ``mem/*`` / ``roofline/*`` gauge vocabularies below
are mirrored byte-identical in ``scripts/check_telemetry_schema.py``
(tier-1 lockstep tests diff them).  Everything is host-side accounting —
no device syncs, no extra compiles; a disabled plane costs the hot path
one ``None`` check.
"""

import threading
import time
from collections import deque
from contextlib import contextmanager

from deepspeed_tpu.utils.logging import logger

# FROZEN event-name vocabulary for the ``compile`` kind (mirrored in
# scripts/check_telemetry_schema.py; the tier-1 test diffs the two).
COMPILE_EVENTS = ("compile/miss", "compile/storm")

# FROZEN cause labels a compile/miss carries: what changed vs the
# previous signature at the same jit site.
COMPILE_CAUSES = ("cold", "new_shape", "new_dtype", "new_callable",
                  "new_static")

# FROZEN top-level spans HBM and roofline attribution keys on.  These are
# logical names, not raw telemetry span names: engine/forward -> fwd,
# engine/backward -> bwd, engine/step -> step, engine/train_batch ->
# train_batch, serve/step decode -> serve_step, serve/step prefill ->
# prefill.
PROFILE_SPANS = ("fwd", "bwd", "step", "train_batch", "serve_step",
                 "prefill")

# FROZEN per-span memory metrics: gauge names are mem/<span>/<metric>.
MEM_METRICS = ("live_bytes", "peak_bytes", "frag_bytes")

# FROZEN per-span roofline metrics: gauge names are
# roofline/<span>/<metric> — achieved/peak fractions in [0, ~1].
ROOFLINE_METRICS = ("compute_frac", "bandwidth_frac")


def _default_memory_stats():
    """Live allocator stats of device 0 (``bytes_in_use``,
    ``peak_bytes_in_use``, ...).  None on backends without allocator
    stats (CPU) — callers skip quietly."""
    try:
        import jax
        return jax.local_devices()[0].memory_stats()
    except Exception:
        return None


def _leaf_sig(x):
    """(shape, dtype) signature of one call argument leaf.  Arrays carry
    their aval; scalars degrade to their python type so an int-vs-float
    static flip still reads as a signature change."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    return ((), type(x).__name__)


def fingerprint_call(args, kwargs=None):
    """Signature fingerprint of one call into a jitted function: the
    pytree structure plus every leaf's (shape, dtype).  Two calls with
    equal fingerprints hit the same ``jax.jit`` cache entry (donation
    and static args are fixed per wrapped site, so they live in the
    site identity, not the fingerprint)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    return (str(treedef), tuple(_leaf_sig(x) for x in leaves))


def diff_cause(prev, cur):
    """Frozen cause label for a new fingerprint vs the site's previous
    one (see :data:`COMPILE_CAUSES`)."""
    if prev is None:
        return "cold"
    if prev[0] != cur[0] or len(prev[1]) != len(cur[1]):
        return "new_callable"
    prev_shapes = tuple(s for s, _ in prev[1])
    cur_shapes = tuple(s for s, _ in cur[1])
    prev_dtypes = tuple(d for _, d in prev[1])
    cur_dtypes = tuple(d for _, d in cur[1])
    if prev_shapes != cur_shapes and prev_dtypes == cur_dtypes:
        return "new_shape"
    if prev_shapes == cur_shapes and prev_dtypes != cur_dtypes:
        return "new_dtype"
    if prev_shapes != cur_shapes:
        return "new_shape"
    return "new_static"


class CompileWatcher:
    """Host-side XLA recompilation tracer.

    :meth:`wrap` returns a call-through wrapper around a jitted callable.
    Each call is fingerprinted; an unseen fingerprint at a site means
    ``jax.jit`` is about to compile, so the wrapper times the call and
    emits one frozen ``compile/miss`` event carrying the observed wall
    time (compile + first execution — the caller-visible cost), the
    site's cumulative miss count, and the cause diff vs the previous
    signature.  Hot calls (seen fingerprint) pay one dict lookup.

    A deque of recent miss times drives the storm verdict:
    ``storm_threshold`` or more *non-cold* misses inside
    ``storm_window_s`` means shapes are churning faster than the cache
    amortises — the verdict is emitted once per storm onset
    (``compile/storm``), mirrored onto gauge ``compile/storm_active``,
    and surfaced through serving ``health()``.  Cold misses (first
    compile at a site) are exempt: a process start compiles every entry
    point once and that is amortisation working, not churn.
    :meth:`compile_secs_since` says how much of an interval went to the
    misses seen here (the stall watchdog reads the compile account).
    """

    def __init__(self, telemetry, storm_threshold=3, storm_window_s=60.0,
                 clock=None):
        self.telemetry = telemetry
        self.storm_threshold = max(1, int(storm_threshold))
        self.storm_window_s = float(storm_window_s)
        self._clock = clock if clock is not None else time.monotonic
        self._lock = threading.Lock()
        self._sites = {}      # site -> {fingerprint: first-seen ts}
        self._last_fp = {}    # site -> previous fingerprint
        self._counts = {}     # site -> cumulative miss count
        self._misses = deque(maxlen=256)   # (ts, dur_s, cause) of misses
        self._storm_active = False
        self.total_misses = 0

    def wrap(self, fn, site, step_fn=None):
        """Wrap jitted ``fn``; ``step_fn`` (optional, zero-arg) supplies
        the current step for event stamping."""
        def wrapper(*args, **kwargs):
            fp = fingerprint_call(args, kwargs)
            seen = self._sites.setdefault(site, {})
            if fp in seen:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            dur_s = time.perf_counter() - t0
            self.note_miss(site, fp, dur_s,
                           step=step_fn() if step_fn is not None else None)
            return out
        wrapper.__wrapped__ = fn
        return wrapper

    def note_miss(self, site, fp, dur_s, step=None):
        """Record one cache miss at ``site`` (the wrapper calls this;
        tests and benches may inject misses directly)."""
        now = self._clock()
        with self._lock:
            seen = self._sites.setdefault(site, {})
            cause = diff_cause(self._last_fp.get(site), fp)
            seen[fp] = now
            self._last_fp[site] = fp
            self._counts[site] = self._counts.get(site, 0) + 1
            count = self._counts[site]
            self._misses.append((now, float(dur_s), cause))
            self.total_misses += 1
        tel = self.telemetry
        if tel is not None and tel.enabled:
            tel.registry.counter("compile/misses").inc()
            tel.registry.counter(f"compile/{site}/misses").inc()
            tel.registry.gauge("compile/last_ms").set(dur_s * 1000.0)
            tel.emit("compile", "compile/miss", site=str(site),
                     dur_ms=round(dur_s * 1000.0, 3), count=count,
                     cause=cause, step=step)
        self._check_storm(now, step=step)

    def _recent(self, now):
        """Misses inside the storm window, cold ones excluded — first
        compiles at a site are expected, only re-compiles are churn."""
        cutoff = now - self.storm_window_s
        return [m for m in self._misses
                if m[0] >= cutoff and m[2] != "cold"]

    def _check_storm(self, now, step=None):
        recent = self._recent(now)
        active = len(recent) >= self.storm_threshold
        newly = active and not self._storm_active
        self._storm_active = active
        tel = self.telemetry
        if tel is not None and tel.enabled:
            tel.registry.gauge("compile/storm_active").set(1.0 if active
                                                           else 0.0)
            if newly:
                logger.warning(
                    f"recompile storm: {len(recent)} non-cold jit cache "
                    f"misses in {self.storm_window_s:.0f}s (threshold "
                    f"{self.storm_threshold}) — shapes are churning faster "
                    f"than the compile cache amortises")
                tel.emit("compile", "compile/storm", site="*",
                         count=len(recent),
                         window_s=round(self.storm_window_s, 3), step=step)
                incidents = getattr(tel, "incidents", None)
                if incidents is not None:
                    # incident plane: the storm onset (rising edge) opens
                    # one bundle snapshotting the flight recorder
                    incidents.trigger(
                        "storm", source="compile/storm", step=step,
                        detail=f"{len(recent)} non-cold misses in "
                               f"{self.storm_window_s:.0f}s")
        return newly

    @property
    def storm_active(self):
        """Current verdict (re-evaluated against the live clock so an old
        storm decays once the window slides past it)."""
        with self._lock:
            recent = self._recent(self._clock())
        self._storm_active = len(recent) >= self.storm_threshold
        return self._storm_active

    def compile_secs_since(self, t):
        """Total observed compile seconds since monotonic time ``t``: a
        step that recompiled may legitimately take longer by exactly this
        much."""
        with self._lock:
            return sum(d for ts, d, _ in self._misses if ts >= t)

    def snapshot(self):
        """JSON-safe summary for health()/report surfaces."""
        with self._lock:
            recent = self._recent(self._clock())
            return {
                "total_misses": self.total_misses,
                "sites": dict(self._counts),
                "recent_misses": len(recent),
                "storm_threshold": self.storm_threshold,
                "storm_window_s": self.storm_window_s,
                "storm_active": len(recent) >= self.storm_threshold,
            }


class HbmTracker:
    """Per-span HBM attribution + monotonic-growth leak detection.

    :meth:`track` samples allocator stats at span entry and exit and
    emits the frozen ``mem/<span>/*`` gauges: ``live_bytes`` (in use at
    exit), ``peak_bytes`` (allocator peak observed across the span —
    the process peak when the span raised it, else the exit live size),
    and ``frag_bytes`` (reserved-but-idle bytes; peak-live proxy when
    the allocator doesn't report a pool size).  Backends without
    ``memory_stats()`` (CPU) make every method a quiet no-op; tests and
    benches inject ``stats_fn``.

    :meth:`sample` records one live-size observation per
    ``snapshot_interval`` steps; ``leak_report()`` flags
    ``leak_window`` consecutive strictly-increasing samples with total
    growth over ``min_growth_bytes`` — the shape a slow KV-page or
    buffer leak produces, invisible to any single snapshot."""

    def __init__(self, telemetry, stats_fn=None, snapshot_interval=8,
                 leak_window=8, min_growth_bytes=1 << 20):
        self.telemetry = telemetry
        self.stats_fn = stats_fn if stats_fn is not None \
            else _default_memory_stats
        self.snapshot_interval = max(1, int(snapshot_interval))
        self.leak_window = max(2, int(leak_window))
        self.min_growth_bytes = int(min_growth_bytes)
        self._samples = deque(maxlen=max(64, self.leak_window))
        self._last_sample_step = None

    def _stats(self):
        try:
            return self.stats_fn() or None
        except Exception:
            return None

    @contextmanager
    def track(self, span):
        """Attribute this region's memory behavior to logical ``span``
        (one of :data:`PROFILE_SPANS`)."""
        before = self._stats()
        try:
            yield
        finally:
            after = self._stats()
            if after and span in PROFILE_SPANS:
                self._emit(span, before or {}, after)

    def _emit(self, span, before, after):
        tel = self.telemetry
        if tel is None or not tel.enabled:
            return
        live = float(after.get("bytes_in_use", 0))
        peak_after = after.get("peak_bytes_in_use")
        peak_before = before.get("peak_bytes_in_use")
        if peak_after is not None and (peak_before is None or
                                       peak_after > peak_before):
            peak = float(peak_after)     # this span raised the process peak
        else:
            peak = live
        pool = after.get("pool_bytes", after.get("bytes_reserved"))
        if pool is not None:
            frag = max(0.0, float(pool) - live)
        else:
            frag = max(0.0, float(peak_after or live) - live)
        tel.gauge(f"mem/{span}/live_bytes", live)
        tel.gauge(f"mem/{span}/peak_bytes", peak)
        tel.gauge(f"mem/{span}/frag_bytes", frag)

    def sample(self, step):
        """One periodic live-size observation (every
        ``snapshot_interval`` steps) feeding the leak detector."""
        if self._last_sample_step is not None and \
                step - self._last_sample_step < self.snapshot_interval:
            return
        stats = self._stats()
        if not stats or "bytes_in_use" not in stats:
            return
        self._last_sample_step = step
        self._samples.append((int(step), float(stats["bytes_in_use"])))

    def leak_report(self):
        """{} when clean; else one ``hbm_monotonic_growth`` entry with
        the window, total growth, and endpoints."""
        samples = list(self._samples)[-self.leak_window:]
        if len(samples) < self.leak_window:
            return {}
        values = [v for _, v in samples]
        if all(b > a for a, b in zip(values, values[1:])) and \
                values[-1] - values[0] >= self.min_growth_bytes:
            return {"hbm_monotonic_growth": {
                "samples": len(samples),
                "growth_bytes": int(values[-1] - values[0]),
                "from_step": samples[0][0], "to_step": samples[-1][0],
                "from_bytes": int(values[0]), "to_bytes": int(values[-1]),
            }}
        return {}


class ProfilingPlane:
    """The bundled fourth observability plane, owned by
    :class:`Telemetry` (``telemetry.profiling`` config block).  One
    instance per process; engines and the serving path reach it through
    ``get_telemetry().profiling`` (None when the block is off — callers
    gate on that single check)."""

    def __init__(self, telemetry, snapshot_interval=8, storm_threshold=3,
                 storm_window_s=60.0, leak_window=8,
                 min_growth_bytes=1 << 20, peak_hbm_gbps=0.0,
                 stats_fn=None, clock=None):
        self.telemetry = telemetry
        self.compiles = CompileWatcher(telemetry,
                                       storm_threshold=storm_threshold,
                                       storm_window_s=storm_window_s,
                                       clock=clock)
        self.hbm = HbmTracker(telemetry, stats_fn=stats_fn,
                              snapshot_interval=snapshot_interval,
                              leak_window=leak_window,
                              min_growth_bytes=min_growth_bytes)
        self.peak_hbm_gbps = float(peak_hbm_gbps or 0.0)

    # -- compile tracing -------------------------------------------------
    def wrap(self, fn, site, step_fn=None):
        return self.compiles.wrap(fn, site, step_fn=step_fn)

    @property
    def storm_active(self):
        return self.compiles.storm_active

    def compile_snapshot(self):
        return self.compiles.snapshot()

    # -- HBM attribution -------------------------------------------------
    def track(self, span):
        return self.hbm.track(span)

    def on_step(self, step):
        self.hbm.sample(step)

    def leak_report(self):
        return self.hbm.leak_report()

    # -- live roofline ---------------------------------------------------
    def hbm_peak_bytes_per_sec(self):
        """Bandwidth roofline denominator: the config override when set,
        else the chip table (None off-TPU with no override — the
        bandwidth fraction simply doesn't emit)."""
        if self.peak_hbm_gbps > 0:
            return self.peak_hbm_gbps * 1e9
        from deepspeed_tpu.comm.topology_model import hbm_peak_gbps
        gbps = hbm_peak_gbps()
        return gbps * 1e9 if gbps else None

    def roofline(self, span, dur_s, flops=None, bytes_moved=None,
                 peak_flops=None, step=None):
        """Emit the per-span achieved-vs-peak fractions.  ``flops`` and
        ``bytes_moved`` are analytic per-execution counts (flops
        profiler); a fraction emits only when both its numerator and its
        peak are known — absent peaks (CPU runs with no override) drop
        the gauge rather than emitting garbage."""
        tel = self.telemetry
        if tel is None or not tel.enabled or span not in PROFILE_SPANS \
                or not dur_s or dur_s <= 0:
            return
        if flops and peak_flops:
            tel.gauge(f"roofline/{span}/compute_frac",
                      (float(flops) / dur_s) / float(peak_flops), step=step)
        peak_bw = self.hbm_peak_bytes_per_sec()
        if bytes_moved and peak_bw:
            tel.gauge(f"roofline/{span}/bandwidth_frac",
                      (float(bytes_moved) / dur_s) / peak_bw, step=step)
