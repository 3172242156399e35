"""Unified telemetry spine: structured run-event stream, metrics registry,
xprof spans, and the record of every step that runs long.

The reference threads observability through every layer (``monitor/``,
``utils/timer.py``, ``comms_logger``, flops profiler) but each fragment has
its own sink.  Here every subsystem writes into ONE process-local
:class:`Telemetry` object:

* :class:`MetricsRegistry` — counters, gauges (with peak tracking), and
  time-window histograms, safe to touch from worker threads (param-stream
  H2D drain, the watchdog).
* :meth:`Telemetry.span` — a context manager that times its body on
  ``time.perf_counter_ns``, ALWAYS records it into the object's
  :class:`SpanRing` (id, parent, name, t0, t1, step or req_id, attrs) and
  opens a ``jax.profiler.TraceAnnotation`` so the same region shows up
  under the device lines of an xprof capture.  With ``enabled`` it also
  records the duration into a histogram and emits a ``span`` event.
* :func:`register_compiled` / :func:`op_scopes` — every jitted entry point
  the engines route through ``_wrap_compiled`` is registered under its
  site name; ``op_scopes(site)`` maps the compiled program's instruction
  names (the names a device trace shows: ``fusion.728``) to the step
  phase (``fwd``, ``bwd``, ``remat``, ``loss_head``, ``optimizer``,
  ``grad_reduce``, ``other``) their ``jax.named_scope`` path says.
* the compile account — one record for every program JAX traces, lowers
  and compiles or reads from the persistent cache (``jax.monitoring``),
  with the site and the span it happened in, and the ``setup/*`` spans of
  import and engine construction, kept apart from the ring:
  :meth:`Telemetry.compile_log`, :meth:`Telemetry.startup_report`.
* :class:`JsonlEventSink` — rank-0-gated JSONL stream with size-based
  rotation.  ``MonitorMaster`` gains it as a fourth writer, so scalar
  monitor events, comm census, HBM gauges, heartbeats and stalls all land
  in the same replayable stream.
* :class:`StepStallWatchdog` — one a :class:`Telemetry`, on like the ring:
  the judge of every step's length at its close (a ``serve/loop``; a
  trainer's time from one ``engine/train_batch`` to the next), a thread
  that sleeps until a step is late and then samples every thread's stack,
  every native thread's state and the machine's counters, and the record
  of each step that ran long, with one word for where the time went
  (:meth:`Telemetry.step_span`, :meth:`Telemetry.slow_steps`).  Fed a
  heartbeat by the trainer it also gives the hang verdict it was made
  for: a gap over a multiple of the rolling-median step emits a ``stall``
  event, which turns the silent-hang failure class (a hung backend leaves
  zero in-band evidence) into an observable one.

Every event is one JSON object per line with at minimum ``ts`` (unix
seconds), ``kind`` and ``name``.  The frozen per-kind schema lives in
``scripts/check_telemetry_schema.py`` and is enforced by a tier-1 test.
"""

import contextlib
import functools
import itertools
import json
import math
import os
import re
import sys
import threading
import time
import weakref
from collections import deque
from typing import Any, NamedTuple, Optional

import jax

from deepspeed_tpu.utils.logging import logger

# The closed set of event kinds.  Adding a kind means updating the frozen
# schema in scripts/check_telemetry_schema.py (a tier-1 test diffs the two).
EVENT_KINDS = ("span", "gauge", "counter", "comm", "heartbeat", "stall",
               "meta", "fault", "serve", "compile", "fleet", "incident",
               "tune")


# The span vocabulary: every name the program passes to ``Telemetry.span``.
# Frozen like the event vocabularies — ``scripts/check_telemetry_schema.py``
# carries the same tuple and a tier-1 test diffs the two; a reader
# (chipbench/reducers, ds_telemetry_report) may key on any of them.
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


# ----------------------------------------------------------------------
# the span ring
# ----------------------------------------------------------------------
class Span(NamedTuple):
    """One finished span as :meth:`Telemetry.spans` returns it.  ``t0_ns``
    and ``t1_ns`` are ``time.perf_counter_ns`` readings; ``parent`` is the
    id of the span that was open on the same thread (None at the top);
    ``key`` is the step (training) or the req_id (serving), if given."""
    id: int
    parent: Optional[int]
    name: str
    t0_ns: int
    t1_ns: int
    key: Any
    attrs: Optional[dict]


# What the judge at a step's close and the sampler behind it go by
# (docs/telemetry.md "The slow-step record").  A step is slow when it took
# over SLOW_STEP_MEDIANS medians of its kind AND over that median by
# SLOW_STEP_EXCESS_NS, once its kind has SLOW_STEP_MIN_KIND steps.  From
# the records: the stalls met were 3.7-8 medians in the train cells (1.8 s
# on 0.49, 1.9-3.4 s on 0.41) and over 20 in serving; the largest honest
# ratio inside one kind is 2.2 (a chunk over 14,336 cached entries against
# one over none, 126.5 / 58.8 ms).
SLOW_STEP_MEDIANS = 3
SLOW_STEP_EXCESS_NS = 250_000_000
SLOW_STEP_MIN_KIND = 8
SLOW_STEP_WINDOW = 64           # steps of a kind its median runs over
SLOW_STEP_KINDS = 256           # kinds remembered, the oldest dropped
SLOW_STEPS_KEPT = 64            # records, in a store beside the ring
SAMPLE_EVERY_NS = 50_000_000    # between two samples of a late step ...
SAMPLES_MAX = 200               # ... and how many of them it gets
SAMPLE_FRAMES = 12              # innermost Python frames of a stack
SAMPLE_THREADS = 8              # native threads a record names
# the words a record's ``where`` is one of
SLOW_STEP_WHERE = ("compile", "host_python", "descheduled", "blocked_io",
                   "runtime_wait", "other_thread", "caller", "unknown")


class SpanRing:
    """The last ``capacity`` finished spans of one :class:`Telemetry`
    (the process-wide ``get_telemetry()`` for the engines), oldest first.
    Recording is one ``deque.append`` of a tuple (atomic under the GIL, so
    worker threads need no lock); a span enters when it CLOSES, so a
    parent follows its children."""

    def __init__(self, capacity=65536):
        self.capacity = int(capacity)
        self._spans = deque(maxlen=self.capacity)

    def __len__(self):
        return len(self._spans)

    def record(self, span):
        """``span``: the seven fields of :class:`Span`, as a tuple."""
        self._spans.append(span)

    def spans(self, since_ns=None, until_ns=None):
        """Spans that lie inside ``[since_ns, until_ns]`` (either bound
        may be None), ordered by start."""
        out = [Span(*s) for s in tuple(self._spans)
               if (since_ns is None or s[3] >= since_ns)
               and (until_ns is None or s[4] <= until_ns)]
        out.sort(key=lambda s: s.t0_ns)
        return out


# ids are unique in the process and the open span is per thread, whichever
# Telemetry object a span belongs to: a parent link may cross objects
_span_ids = itertools.count(1)
_open = threading.local()     # .top: the innermost open span (_OpenSpan)


class _OpenSpan:
    """The context manager :meth:`Telemetry.span` returns.  Hand-written
    (no generator) because it is on every serving and training step: about
    2 us a span on the CPU, profiler annotation included."""

    __slots__ = ("tel", "name", "step", "attrs", "req_id", "id", "parent",
                 "t0", "_ann")       # parent: the _OpenSpan around it

    def __init__(self, tel, name, step, attrs, req_id):
        self.tel, self.name, self.step = tel, name, step
        self.attrs, self.req_id = attrs, req_id

    def __enter__(self):
        self.parent = getattr(_open, "top", None)
        _open.top = self
        self.id = next(_span_ids)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        parent = _open.top = self.parent
        step = self.step
        tel = self.tel
        tel.ring.record(
            (self.id, parent and parent.id, self.name, self.t0, t1,
             step if step is not None else self.req_id, self.attrs))
        if tel.enabled:
            dur_ms = (t1 - self.t0) / 1e6
            attrs = self.attrs
            if self.req_id is not None:
                attrs = dict(attrs or {}, req_id=self.req_id)
            tel.registry.histogram(f"span/{self.name}").observe(dur_ms)
            tel.emit("span", self.name, dur_ms=round(dur_ms, 3), step=step,
                     attrs=attrs or None)
        return False


class _StepSpan(_OpenSpan):
    """The outermost span of a step (:meth:`Telemetry.step_span`): a span
    like any other in the ring, whose open and close the telemetry's
    :class:`StepStallWatchdog` judges.  ``report`` is a serving loop's
    report (the step is this span); with ``period`` the step is the time
    from this open to the next one of the same ``owner`` on the thread."""

    __slots__ = ("report", "period", "owner", "cpu0")

    def __init__(self, tel, name, step, report, period, owner):
        super().__init__(tel, name, step, None, None)
        self.report, self.period, self.owner = report, period, id(owner)

    def __enter__(self):
        super().__enter__()
        self.tel.watchdog.step_opened(self)
        return self

    def __exit__(self, *exc):
        if self.period:         # the next open closes the step
            return super().__exit__(*exc)
        t1 = time.perf_counter_ns()
        out = super().__exit__(*exc)
        self.tel.watchdog.loop_closed(self, t1)
        return out


# ----------------------------------------------------------------------
# compiled programs by site, and what each of their instructions is for
# ----------------------------------------------------------------------
# phases ``op_scopes`` sorts a compiled step's instructions into; the
# first five are what the trainer's ``jax.named_scope``s (and JAX's own
# ``transpose(jvp(..))`` / ``rematted_computation`` markers) tell apart
OP_PHASES = ("fwd", "bwd", "remat", "loss_head", "optimizer", "grad_reduce",
             "other")

# what a latent-attention / dropless-expert model, one whose layers are
# sliding-window or full attention by a pattern, and one with state-space
# layers (``ssm_*``: in attention's place) name inside ``attn`` and
# ``mlp`` (models/transformer.py): ``phase_of`` gives the innermost of
# these where an instruction has one, in any program
SERVE_SCOPES = ("select", "latent_attn", "router", "experts",
                "shared_expert", "attn_window", "attn_full", "attn_gate",
                "latent_ctx", "ssm_proj", "ssm_conv", "ssm_scan", "lin_attn",
                "block_select", "ckey_write", "sparse_attn")

_MODEL_SCOPES = frozenset(("embed", "norm", "attn", "mlp"))
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# a computation opens with ``[ENTRY] %name (params) -> type {``; an
# instruction line has `` = ``
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")


def phase_of(op_name):
    """The step phase of one instruction from its ``op_name`` (the
    name-stack path JAX records: ``jit(step)/fwd/transpose(jvp(..))/
    checkpoint/rematted_computation/mlp/dot_general``)."""
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in SERVE_SCOPES:
            return part
    if "optimizer" in parts:
        return "optimizer"
    if "grad_reduce" in parts:
        return "grad_reduce"
    if "loss_head" in parts:
        return "loss_head"
    backward = "bwd" in parts
    for i, part in enumerate(parts):
        if part.startswith("transpose("):
            backward = True
        elif part == "rematted_computation":
            # what follows the marker is the forward, run again; a later
            # transpose(..) component is the backward proper
            if not any(p.startswith("transpose(") for p in parts[i + 1:]):
                return "remat"
    if backward:
        return "bwd"
    if "fwd" in parts or any(p.startswith("jvp(") for p in parts) or \
            _MODEL_SCOPES.intersection(parts):
        return "fwd"
    return "other"


def parse_op_scopes(hlo_text):
    """``{instruction name: phase}`` for every instruction of every
    computation in a compiled module's text.  An instruction with no
    ``op_name`` of its own (the compiler made it: a relayout copy, an
    asynchronous prefetch) takes the commonest phase of the computation it
    calls, if it calls one, else the phase of the next instruction of its
    computation that has one, else of the one before: the text of a
    compiled module is in schedule order, so that is the work it runs
    beside."""
    own, calls, members = {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if not found:
            opened = _COMPUTATION.match(line)
            if opened:
                computation = opened.group(1)
            continue
        name = found.group(1)
        op_name = _OP_NAME.search(line)
        if op_name:
            own[name] = phase_of(op_name.group(1))
        else:
            own[name] = None
            callee = _CALLS.search(line)
            if callee:
                calls[name] = callee.group(1)
        members.setdefault(computation, []).append(name)
    out = {}
    for name, phase in own.items():
        if phase is None:
            inner = [own[m] for m in members.get(calls.get(name), ())
                     if own.get(m) not in (None, "other")]
            phase = max(set(inner), key=inner.count) if inner else None
        out[name] = phase
    for names in members.values():
        known = [out[n] if out[n] != "other" else None for n in names]
        following = None
        for i in range(len(names) - 1, -1, -1):     # the next one's phase
            following = known[i] or following
            if out[names[i]] is None:
                out[names[i]] = following
        before = None
        for i, name in enumerate(names):            # else the last one's
            before = known[i] or before
            if out[name] is None:
                out[name] = before or "other"
    return out


def _abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


class CompiledSite:
    """A jitted entry point under its site name (``engine/train_step:2``,
    ``serve/step_fn``).  Calls pass straight through; the first one of
    each shape (the shapes of its positional arguments that are arrays: a
    serving program is compiled once a prefill bucket) also keeps what it
    was called with, so that :meth:`op_scopes` can compile the same
    program again later (under ``mesh``, the context the owner calls it
    in), outside any timed window, and read its text: with the persistent
    compile cache on that second ``compile()`` is a cache read."""

    def __init__(self, fn, site, mesh=None):
        self._fn, self.site, self._mesh = fn, site, mesh
        self._calls = {}        # shapes of the array arguments -> abstract
        self._scopes = {}       # the same key -> parsed table
        self._compiled = {}     # the same key -> programs the account saw

    def __call__(self, *args, **kwargs):
        shapes = tuple(getattr(a, "shape", None) for a in args)
        if shapes not in self._calls:
            self._calls[shapes] = jax.tree_util.tree_map(_abstract,
                                                         (args, kwargs))
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):      # .lower, .__wrapped__, ...
        return getattr(self._fn, name)

    def _find(self, arg_shapes):
        """The key of the first call seen (``arg_shapes`` None), or of the
        call whose positional argument ``i`` had shape ``arg_shapes[i]``
        for every ``i`` given; None where there is none."""
        for shapes in self._calls:
            if arg_shapes is None or all(
                    i < len(shapes) and shapes[i] == tuple(shape)
                    for i, shape in arg_shapes.items()):
                return shapes
        return None

    def compiled_text(self, arg_shapes=None):
        """Text of the program compiled for a call's shapes (the first
        call's without ``arg_shapes``), or None before any such call."""
        key = self._find(arg_shapes)
        return None if key is None else self._text(key)

    def _text(self, shapes):
        args, kwargs = self._calls[shapes]
        with (self._mesh if self._mesh is not None
              else contextlib.nullcontext()):
            return self._fn.lower(*args, **kwargs).compile().as_text()

    def op_scopes(self, arg_shapes=None):
        key = self._find(arg_shapes)
        if key is None:
            return {}
        if key not in self._scopes:
            self._scopes[key] = parse_op_scopes(self._text(key))
        return self._scopes[key]


# site -> CompiledSite, newest wins; weak, so a dropped engine is freed
# (a trainer's step closes over its engine and its state)
_sites = weakref.WeakValueDictionary()
# ... and the sites whose table has to OUTLIVE their owner (``keep``): a
# reader asks for the serving programs' scopes after the run that made the
# engine has handed it back, and the engine, a cycle of references, goes
# whenever the collector next runs: the shares of device time by scope
# came and went by the run.  Their closures hold the model, no weights
_kept = {}
_registered = itertools.count()


def register_compiled(fn, site, mesh=None, keep=False):
    """Wrap jitted ``fn`` as the program of ``site``; ``mesh`` is the
    context its owner calls it in, if any.  ``keep``: the site stays
    readable after its owner is dropped, until a newer one of its name
    (for an ``fn`` whose closure holds no engine and no arrays)."""
    wrapped = _sites[site] = CompiledSite(fn, site, mesh)
    if keep:
        _kept[site] = wrapped
    wrapped.order = next(_registered)
    return wrapped


def op_scopes(site, arg_shapes=None):
    """``{instruction name: phase}`` (phase one of :data:`OP_PHASES`, or
    the innermost of :data:`SERVE_SCOPES`) of the program compiled at
    ``site`` — an exact site name, or a prefix up to a ``:``
    (``"engine/train_step"`` finds ``engine/train_step:2``; of several
    live ones, say two trainers' ``:1`` and ``:2``, the one registered
    last, never a mixture); empty for a site that has not run.  Programs share instruction names
    (``fusion.3``), so tables are per site, and per shape where a site
    compiles several: ``arg_shapes`` (``{1: (1, 8192)}``: positional
    argument 1 had that shape) picks the program of that call, the first
    call's without it.  Parsed from the compiled text once, lazily: call
    it outside any timed window."""
    found = [entry for name, entry in [*_kept.items(), *_sites.items()]
             if name == site or name.startswith(site + ":")]
    if not found:
        return {}
    return max(found, key=lambda entry: entry.order).op_scopes(arg_shapes)


# ----------------------------------------------------------------------
# the compile account, and the set-up spans
# ----------------------------------------------------------------------
_TRACED = "/jax/core/compile/jaxpr_trace_duration"
_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

# the frames a compilation is attributed to a site by: found on the stack
# when JAX reports a backend compile, so a call that compiles nothing pays
# nothing for it (both have ``self`` and ``shapes`` among their locals)
_SITE_FRAMES = (CompiledSite.__call__.__code__, CompiledSite._text.__code__)


def _ids_upward(span):
    """Ids of an open span and of those around it, innermost first."""
    ids = []
    while span is not None:
        ids.append(span.id)
        span = span.parent
    return tuple(ids)


class _Pending(threading.local):
    """What one thread has traced and lowered since its last record."""

    def __init__(self):
        self.reset()

    def reset(self):
        # (t0_ns, seconds) of the traces no later trace has swallowed yet;
        # bounded for a thread that only ever traces
        self.traces = deque(maxlen=4096)
        self.lower_s = self.retrieval_s = 0.0
        self.cache = "off"


class CompileAccount:
    """Every program JAX compiled or read from the persistent cache in
    this process, and the ``setup/*`` spans, in a store of their own: a
    serving run wraps the span ring long before anyone asks how the
    process started.  Process-wide, as ``_sites`` is, and fed by the one
    pair of ``jax.monitoring`` listeners registered below.

    A record closes at JAX's backend-compile event (a compilation, or the
    read when the cache had the program) and takes with it what its
    thread traced and lowered since the thread's last record: a trace
    that led to no program (``jax.eval_shape``) is booked to the next
    one.  ``t0_ns = t1_ns - (trace_s + lower_s + backend_s)``."""

    KEPT = 4096     # records, and set-up spans: the newest of each

    def __init__(self):
        self.records = deque(maxlen=self.KEPT)
        self.spans = deque(maxlen=self.KEPT)    # (Span, ids of ancestors)
        self._pending = _Pending()

    # -- the listeners -------------------------------------------------
    def on_duration(self, event, secs, fun_name=None, **_kw):
        pending = self._pending
        if event == _TRACED:
            # an inner jit (every jnp function is one) is traced inside
            # the outer one's trace and reported first, thousands of them
            # in a model's: the outer interval swallows them all
            t0 = time.perf_counter_ns() - int(secs * 1e9)
            traces = pending.traces
            while traces and traces[-1][0] >= t0:
                traces.pop()
            traces.append((t0, secs))
        elif event == _LOWERED:
            pending.lower_s += secs
        elif event == _CACHE_READ:
            pending.retrieval_s = secs
        elif event == _BACKEND:
            self._close(pending, secs, fun_name)

    def on_event(self, event, **_kw):
        if event == _CACHE_ASKED:
            # JAX asks its cache whether or not it was given a directory;
            # with one, a miss until the hit is reported
            self._pending.cache = \
                "miss" if jax.config.jax_compilation_cache_dir else "off"
        elif event == _CACHE_HIT:
            self._pending.cache = "hit"

    def _close(self, pending, backend_s, name):
        t1 = time.perf_counter_ns()
        trace_s = math.fsum(t[1] for t in pending.traces)
        record = {"t0_ns": t1 - int(
                      (trace_s + pending.lower_s + backend_s) * 1e9),
                  "t1_ns": t1, "name": name, "trace_s": trace_s,
                  "lower_s": pending.lower_s, "backend_s": backend_s,
                  "cache": pending.cache, "site": None, "shapes": None,
                  "repeat": False}
        if pending.cache == "hit":
            record["retrieval_s"] = pending.retrieval_s
        pending.reset()
        frame = sys._getframe()
        while frame is not None and frame.f_code not in _SITE_FRAMES:
            frame = frame.f_back
        if frame is not None:
            site, shapes = frame.f_locals["self"], frame.f_locals["shapes"]
            seen = site._compiled.get(shapes, 0)
            site._compiled[shapes] = seen + 1
            record.update(site=site.site, shapes=shapes, repeat=seen > 0)
        span = getattr(_open, "top", None)
        ids = _ids_upward(span)
        record.update(span=span.name if span else None,
                      span_attrs=span.attrs if span else None,
                      span_ids=ids)
        self.records.append(record)
        # ... and a ``compile`` span of the ring, timed by JAX
        tel, t0 = _telemetry, record["t0_ns"]
        attrs = {"site": record["site"], "cache": record["cache"]}
        tel.ring.record((next(_span_ids), ids[0] if ids else None, "compile",
                         t0, t1, None, attrs))
        if tel.enabled:
            tel.emit("span", "compile", dur_ms=round((t1 - t0) / 1e6, 3),
                     attrs=attrs)

    # -- the readers ---------------------------------------------------
    def log(self, since_ns=None, until_ns=None):
        return [dict(r) for r in tuple(self.records)
                if (since_ns is None or r["t1_ns"] >= since_ns)
                and (until_ns is None or r["t1_ns"] <= until_ns)]

    def report(self, until_ns=None):
        records = self.log(until_ns=until_ns)
        spans = [(s, up) for s, up in tuple(self.spans)
                 if until_ns is None or s.t1_ns <= until_ns]
        names = {s.id: s.name for s, _ in spans}
        seconds = {"import": 0.0, "engine": 0.0}
        for span, ancestors in spans:
            if any(names.get(i) == span.name for i in ancestors):
                continue        # nested in its like: the outer one counts
            # its thread's records, one after another: none takes more of
            # the span than passed since the one before it closed (a
            # record may carry a trace from before the span)
            inside, since = 0.0, span.t0_ns
            for r in records:
                if span.id in r["span_ids"]:
                    inside += min(_seconds(r), (r["t1_ns"] - since) / 1e9)
                    since = r["t1_ns"]
            key = span.name.partition("/")[2]
            seconds[key] = seconds.get(key, 0.0) + \
                (span.t1_ns - span.t0_ns) / 1e9 - inside
        by_site = {}
        for r in records:
            by_site.setdefault(r["site"], []).append(r)
        out = _totals(records)
        out["seconds"] = {**seconds, **out["seconds"]}
        out.update(
            by_site={site: _totals(rs) for site, rs in by_site.items()},
            slowest=sorted(records, key=_seconds, reverse=True)[:10],
            spans=[s for s, _ in spans])
        return out


def _seconds(record):
    return record["trace_s"] + record["lower_s"] + record["backend_s"]


def _totals(records):
    """Counts and seconds of some of the account's records."""
    hits = [r for r in records if r["cache"] == "hit"]
    return {"programs": len(records), "cache_hits": len(hits),
            "cache_misses": sum(r["cache"] == "miss" for r in records),
            "repeat_compiles": sum(r["repeat"] for r in records),
            "seconds": {
                "trace": math.fsum(r["trace_s"] for r in records),
                "lower": math.fsum(r["lower_s"] for r in records),
                "compile": math.fsum(r["backend_s"] for r in records
                                     if r["cache"] != "hit"),
                "cache_read": math.fsum(r["backend_s"] for r in hits)}}


_account = CompileAccount()
jax.monitoring.register_event_duration_secs_listener(_account.on_duration)
jax.monitoring.register_event_listener(_account.on_event)


class _SetupSpan(_OpenSpan):
    """A span of the process's start (``setup/import``, ``setup/engine``
    and its parts): a handful a process, never on a step's path, kept in
    the account as well as in the ring."""

    __slots__ = ("since",)

    def __init__(self, name, attrs, since_ns):
        super().__init__(_telemetry, name, None, attrs or None, None)
        self.since = since_ns

    def __enter__(self):
        super().__enter__()
        if self.since is not None:
            self.t0 = self.since
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        ancestors = _ids_upward(self.parent)
        _account.spans.append(
            (Span(self.id, ancestors[0] if ancestors else None, self.name,
                  self.t0, t1, None, self.attrs), ancestors))
        return super().__exit__(*exc)


def setup_span(name, since_ns=None, **attrs):
    """A set-up span of the process-wide telemetry, as a context manager.
    ``since_ns`` back-dates its start: the package's ``__init__`` reads
    the clock before it can import this module."""
    return _SetupSpan(name, attrs, since_ns)


def in_setup_span(name, **attrs):
    """Decorator: the call runs inside ``setup_span(name, **attrs)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inside(*args, **kwargs):
            with setup_span(name, **attrs):
                return fn(*args, **kwargs)
        return inside
    return wrap


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------
class Counter:
    __slots__ = ("name", "value")

    def __init__(self, name):
        self.name = name
        self.value = 0

    def inc(self, n=1):
        self.value += n


class Gauge:
    __slots__ = ("name", "value", "peak")

    def __init__(self, name):
        self.name = name
        self.value = 0.0
        self.peak = float("-inf")

    def set(self, value):
        value = float(value)
        # peak is written BEFORE value: a concurrent snapshot may then see
        # a stale value with a fresh peak, but never value > peak — the
        # invariant scrapers rely on survives lock-free sets
        if value > self.peak:
            self.peak = value
        self.value = value


class Histogram:
    """Time-window histogram: keeps ``(t, value)`` samples no older than
    ``window_secs`` (bounded by ``max_samples``); percentile queries prune
    lazily."""

    __slots__ = ("name", "window_secs", "_samples", "_lock")

    def __init__(self, name, window_secs=600.0, max_samples=4096):
        self.name = name
        self.window_secs = float(window_secs)
        self._samples = deque(maxlen=max_samples)
        # per-histogram lock: callers observe() OUTSIDE the registry lock
        # while exporter scrape threads iterate the same deque via
        # summary() — without this, values()'s comprehension races the
        # append/popleft and raises "deque mutated during iteration"
        self._lock = threading.Lock()

    def observe(self, value, now=None):
        now = now if now is not None else time.monotonic()
        with self._lock:
            self._prune(now)
            self._samples.append((now, float(value)))

    def _prune(self, now=None):
        # caller holds self._lock
        now = now if now is not None else time.monotonic()
        cutoff = now - self.window_secs
        while self._samples and self._samples[0][0] < cutoff:
            self._samples.popleft()

    def values(self, now=None):
        with self._lock:
            self._prune(now)
            return [v for _, v in self._samples]

    def percentile(self, q, now=None):
        """q-th percentile over the live window (stale samples are pruned
        here too, not just on observe).  None on an empty window."""
        vals = sorted(self.values(now))
        if not vals:
            return None
        idx = min(len(vals) - 1, max(0, int(round(q / 100.0 * (len(vals) - 1)))))
        return vals[idx]

    def summary(self, now=None):
        """Windowed stats.  An empty window returns the full typed shape
        (count 0, every stat None) so consumers — the exporter, health(),
        the report script — never KeyError on a quiet histogram."""
        vals = sorted(self.values(now))
        if not vals:
            return {"count": 0, "min": None, "max": None, "mean": None,
                    "p50": None, "p90": None, "p99": None}
        n = len(vals)

        def pct(q):
            return vals[min(n - 1, max(0, int(round(q / 100.0 * (n - 1)))))]
        return {"count": n, "min": vals[0], "max": vals[-1],
                "mean": sum(vals) / n, "p50": pct(50), "p90": pct(90),
                "p99": pct(99)}


class MetricsRegistry:
    """Process-local named counters / gauges / time-window histograms."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counters = {}
        self.gauges = {}
        self.histograms = {}

    def counter(self, name) -> Counter:
        with self._lock:
            if name not in self.counters:
                self.counters[name] = Counter(name)
            return self.counters[name]

    def gauge(self, name) -> Gauge:
        with self._lock:
            if name not in self.gauges:
                self.gauges[name] = Gauge(name)
            return self.gauges[name]

    def histogram(self, name, window_secs=600.0) -> Histogram:
        with self._lock:
            if name not in self.histograms:
                self.histograms[name] = Histogram(name,
                                                  window_secs=window_secs)
            return self.histograms[name]

    def snapshot(self):
        with self._lock:
            return {
                "counters": {n: c.value for n, c in self.counters.items()},
                "gauges": {n: {"value": g.value, "peak": g.peak}
                           for n, g in self.gauges.items()},
                "histograms": {n: h.summary()
                               for n, h in self.histograms.items()},
            }

    def reset(self):
        with self._lock:
            self.counters = {}
            self.gauges = {}
            self.histograms = {}


# ----------------------------------------------------------------------
# JSONL sink with size-based rotation
# ----------------------------------------------------------------------
class JsonlEventSink:
    """Append-only ``events.jsonl`` with size-based rotation: when the live
    file exceeds ``max_bytes`` it is renamed to ``events.jsonl.1`` (older
    generations shift up, the oldest beyond ``max_files`` is dropped)."""

    def __init__(self, output_dir, filename="events.jsonl",
                 max_bytes=64 * 1024 * 1024, max_files=4):
        self.output_dir = output_dir
        self.path = os.path.join(output_dir, filename)
        self.max_bytes = int(max_bytes)
        self.max_files = max(1, int(max_files))
        os.makedirs(output_dir, exist_ok=True)
        self._lock = threading.Lock()
        self._file = open(self.path, "a")

    def emit(self, event: dict):
        line = json.dumps(event, default=str)
        with self._lock:
            if self._file is None:
                return
            self._file.write(line + "\n")
            self._file.flush()
            if self._file.tell() >= self.max_bytes:
                self._rotate()

    def _rotate(self):
        self._file.close()
        for i in range(self.max_files - 1, 0, -1):
            src = f"{self.path}.{i}"
            if os.path.exists(src):
                os.replace(src, f"{self.path}.{i + 1}")
        oldest = f"{self.path}.{self.max_files}"
        if os.path.exists(oldest):
            os.remove(oldest)
        os.replace(self.path, f"{self.path}.1")
        self._file = open(self.path, "a")

    def close(self):
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


def _coerce_distributed(dcfg):
    """``telemetry.distributed`` block as a plain dict — accepts the
    TelemetryDistributedConfig object, a raw dict (hand-built configs),
    or None (block absent: distributed mode off)."""
    if dcfg is None:
        return {"enabled": False, "shard_dir": "", "skew_threshold": 2.0,
                "straggler_window": 32}
    if isinstance(dcfg, dict):
        return {"enabled": bool(dcfg.get("enabled", False)),
                "shard_dir": str(dcfg.get("shard_dir", "") or ""),
                "skew_threshold": float(dcfg.get("skew_threshold", 2.0)),
                "straggler_window": int(dcfg.get("straggler_window", 32))}
    return {"enabled": bool(dcfg.enabled),
            "shard_dir": str(dcfg.shard_dir or ""),
            "skew_threshold": float(dcfg.skew_threshold),
            "straggler_window": int(dcfg.straggler_window)}


def _coerce_profiling(pcfg):
    """``telemetry.profiling`` block as a plain dict — accepts the
    TelemetryProfilingConfig object, a raw dict (hand-built configs), or
    None (block absent: profiling plane off)."""
    defaults = {"enabled": False, "snapshot_interval": 8,
                "storm_threshold": 3, "storm_window_s": 60.0,
                "leak_window": 8, "peak_hbm_gbps": 0.0}
    if pcfg is None:
        return defaults
    get = (pcfg.get if isinstance(pcfg, dict)
           else lambda k, d: getattr(pcfg, k, d))
    return {"enabled": bool(get("enabled", False)),
            "snapshot_interval": int(get("snapshot_interval", 8)),
            "storm_threshold": int(get("storm_threshold", 3)),
            "storm_window_s": float(get("storm_window_s", 60.0)),
            "leak_window": int(get("leak_window", 8)),
            "peak_hbm_gbps": float(get("peak_hbm_gbps", 0.0))}


def _coerce_incidents(icfg):
    """``telemetry.incidents`` block as a plain dict — accepts the
    TelemetryIncidentsConfig object, a raw dict (hand-built configs), or
    None (block absent: incident plane off)."""
    defaults = {"enabled": False, "ring_capacity": 2048,
                "ring_max_age_s": 600.0, "burn_windows": [],
                "burn_min_requests": 8, "cooldown_s": 60.0,
                "bundle_dir": "", "max_bundles": 16}
    if icfg is None:
        return defaults
    get = (icfg.get if isinstance(icfg, dict)
           else lambda k, d: getattr(icfg, k, d))
    return {"enabled": bool(get("enabled", False)),
            "ring_capacity": int(get("ring_capacity", 2048)),
            "ring_max_age_s": float(get("ring_max_age_s", 600.0)),
            "burn_windows": list(get("burn_windows", []) or []),
            "burn_min_requests": int(get("burn_min_requests", 8)),
            "cooldown_s": float(get("cooldown_s", 60.0)),
            "bundle_dir": str(get("bundle_dir", "") or ""),
            "max_bundles": int(get("max_bundles", 16))}


def _coerce_attribution(acfg):
    """``telemetry.attribution`` block as a plain dict — accepts the
    TelemetryAttributionConfig object, a raw dict (hand-built configs),
    or None (block absent: attribution plane off)."""
    defaults = {"enabled": False, "history": 64, "serve_history": 256}
    if acfg is None:
        return defaults
    get = (acfg.get if isinstance(acfg, dict)
           else lambda k, d: getattr(acfg, k, d))
    return {"enabled": bool(get("enabled", False)),
            "history": int(get("history", 64)),
            "serve_history": int(get("serve_history", 256))}


# ----------------------------------------------------------------------
# the telemetry object
# ----------------------------------------------------------------------
class Telemetry:
    """Process-local telemetry: registry + (rank-0) JSONL sink + spans.

    Disabled by default: events, histograms, gauges and the planes are
    gated on ``telemetry.enabled`` (one attribute read).  Spans alone are
    always recorded (``ring``) and annotated for the profiler.
    """

    def __init__(self):
        self.enabled = False
        self.registry = MetricsRegistry()
        # every span, enabled or not (the hot path's one always-on record)
        self.ring = SpanRing()
        # the judge of every step's length and the sampler behind it: on
        # like the ring, its thread started by the engines
        self.watchdog = StepStallWatchdog(self, hangs=False)
        self.sink = None
        self.config = None
        self.exporter = None
        self.rank = 0
        self.cluster = None
        self.profiling = None
        self.incidents = None
        self.attribution = None
        self._stamp_rank = False

    def configure(self, config=None, rank=None):
        """(Re)configure from a ``TelemetryConfig``-shaped object.

        Default mode keeps the PR 1 contract: the sink is rank-0-gated
        (``events.jsonl``); non-zero ranks keep the registry and spans
        (xprof annotations are per-host) but write no events.  With the
        ``telemetry.distributed`` block enabled, EVERY process writes its
        own shard ``events.rank{N}.jsonl`` (rank stamped into each
        record) and rank 0 additionally owns a :class:`ClusterAggregator`
        over the shard directory — the data plane behind the exporter's
        ``/cluster`` endpoint, the watchdog's cross-rank check, and
        ``health()``'s cluster section.  When the config carries an
        enabled ``export`` block, a rank-0 background HTTP exporter
        (monitor/export.py) is started on the same gate."""
        if self.sink is not None:
            self.sink.close()
            self.sink = None
        if self.exporter is not None:
            self.exporter.close()
            self.exporter = None
        self.cluster = None
        self.profiling = None
        self.incidents = None
        self.attribution = None
        self._stamp_rank = False
        self.config = config
        self.enabled = bool(config is not None and config.enabled)
        if not self.enabled:
            return self
        pcfg = _coerce_profiling(getattr(config, "profiling", None))
        if pcfg.pop("enabled"):
            # fourth observability plane (monitor/profiling.py): compile
            # tracing, per-span HBM attribution, live roofline — built on
            # EVERY rank (registry + events; the sink gates writes)
            from deepspeed_tpu.monitor.profiling import ProfilingPlane
            self.profiling = ProfilingPlane(self, **pcfg)
        acfg = _coerce_attribution(getattr(config, "attribution", None))
        if acfg.pop("enabled"):
            # time-attribution plane (monitor/attribution.py): per-step
            # exposed-comm decomposition tapped into emit() like the
            # incident ring, closed by the watchdog heartbeat (or the
            # engine's direct beat when the watchdog is off)
            from deepspeed_tpu.monitor.attribution import AttributionPlane
            self.attribution = AttributionPlane(self, **acfg)
        if rank is None:
            try:
                import jax
                rank = jax.process_index()
            except Exception:
                rank = 0
        self.rank = int(rank)
        dcfg = _coerce_distributed(getattr(config, "distributed", None))
        out_dir = os.path.join(config.output_path or "./telemetry",
                               config.job_name)
        icfg = _coerce_incidents(getattr(config, "incidents", None))
        if icfg.pop("enabled"):
            # incident plane (monitor/incidents.py): flight-recorder ring
            # fed by emit() on EVERY rank, bundle writer + SLO burn-rate
            # alerter; bundles default under the telemetry output dir
            from deepspeed_tpu.monitor.incidents import IncidentManager
            bundle_dir = icfg.pop("bundle_dir") or \
                os.path.join(out_dir, "incidents")
            self.incidents = IncidentManager(self, bundle_dir=bundle_dir,
                                             **icfg)
        if dcfg["enabled"]:
            shard_dir = dcfg["shard_dir"] or out_dir
            self.sink = JsonlEventSink(
                shard_dir, filename=f"events.rank{self.rank}.jsonl",
                max_bytes=int(float(config.max_file_mb) * 1024 * 1024),
                max_files=config.max_files)
            self._stamp_rank = True
            if self.rank == 0:
                from deepspeed_tpu.monitor.aggregate import ClusterAggregator
                self.cluster = ClusterAggregator(
                    shard_dir,
                    skew_threshold=dcfg["skew_threshold"],
                    straggler_window=dcfg["straggler_window"],
                    registry=self.registry,
                    incidents=self.incidents)
                self._start_exporter(getattr(config, "export", None))
        elif self.rank == 0:
            self.sink = JsonlEventSink(
                out_dir,
                max_bytes=int(float(config.max_file_mb) * 1024 * 1024),
                max_files=config.max_files)
            self._start_exporter(getattr(config, "export", None))
        return self

    def _start_exporter(self, export_cfg):
        """Start the pull-based metrics exporter when the config asks for
        one.  Accepts a ``TelemetryExportConfig`` or a plain dict (callers
        that hand-build configs); failure to bind is logged, never fatal —
        observability must not take down the run."""
        if export_cfg is None:
            return
        if isinstance(export_cfg, dict):
            enabled = bool(export_cfg.get("enabled", False))
            host = str(export_cfg.get("host", "127.0.0.1"))
            port = int(export_cfg.get("port", 9866))
        else:
            enabled = bool(export_cfg.enabled)
            host = str(export_cfg.host)
            port = int(export_cfg.port)
        if not enabled:
            return
        try:
            from deepspeed_tpu.monitor.export import MetricsExporter
            labels = {"rank": str(self.rank)} if self._stamp_rank else None
            cluster_fn = (self.cluster.snapshot
                          if self.cluster is not None else None)
            incidents_fn = (self.incidents.snapshot
                            if self.incidents is not None else None)
            attribution_fn = (self.attribution.snapshot
                              if self.attribution is not None else None)
            self.exporter = MetricsExporter(self, host=host, port=port,
                                            labels=labels,
                                            cluster_fn=cluster_fn,
                                            incidents_fn=incidents_fn,
                                            attribution_fn=attribution_fn)
            self.exporter.start()
        except Exception as e:
            logger.warning(f"metrics exporter failed to start: {e}")
            self.exporter = None
            return
        addr = self.exporter.address
        self.emit("meta", "telemetry/export",
                  attrs={"host": addr[0], "port": addr[1]})

    def snapshot(self):
        """One JSON-safe snapshot of the whole registry — counters, gauges
        (value + peak), and histogram summaries with p50/p90/p99 — stamped
        with the capture time.  This is the object the exporter serves and
        the registry snapshot API callers poll."""
        snap = self.registry.snapshot()
        snap["ts"] = round(time.time(), 6)
        return snap

    # -- events --------------------------------------------------------
    def emit(self, kind, name, **fields):
        incidents = self.incidents
        attribution = self.attribution
        if not self.enabled or (self.sink is None and incidents is None
                                and attribution is None):
            return
        event = {"ts": round(time.time(), 6), "kind": kind, "name": name}
        if self._stamp_rank:
            # distributed (sharded) mode: every record carries its origin
            # rank so a merged stream keeps per-rank attribution
            event["rank"] = self.rank
        event.update({k: v for k, v in fields.items() if v is not None})
        if incidents is not None:
            # flight recorder sees every event on every rank — the sink
            # below may be rank-0-gated, the black box is not
            incidents.record(event)
        if attribution is not None:
            # attribution plane folds span/comm/compile intervals into
            # the pending step and closes it on the heartbeat; its own
            # gauge emissions recurse here once and fall through the
            # plane's kind filter (re-entrancy safe by construction)
            attribution.record(event)
        if self.sink is not None:
            self.sink.emit(event)

    def span(self, name, step=None, attrs=None, req_id=None):
        """A span around the body, whether or not telemetry is enabled:
        recorded in this object's span ring on ``time.perf_counter_ns``
        (read it with :meth:`spans`) and opened as a profiler annotation,
        so a capture shows it on the host's lines above the device's.
        Enabled, the duration also lands in histogram ``span/<name>`` and
        a ``span`` event is emitted."""
        return _OpenSpan(self, name, step, attrs, req_id)

    def spans(self, since_ns=None, until_ns=None):
        """Finished spans of this object's ring inside the given
        ``perf_counter_ns`` bounds, ordered by start."""
        return self.ring.spans(since_ns, until_ns)

    def step_span(self, name, step=None, report=None, period=False,
                  owner=None):
        """The outermost span of a step: recorded like :meth:`span`, and
        judged by this object's watchdog when the step closes.  A serving
        loop hands its ``report`` (the step is the span, its kind what the
        report's dispatches launched); a trainer says ``period=True`` (the
        step runs from this open to the next one of the same ``owner`` on
        the thread, so it holds the caller's wait for the loss).  Costs
        one ``time.thread_time_ns`` reading, a look-up and two comparisons
        more than a span; docs/telemetry.md, "The slow-step record"."""
        return _StepSpan(self, name, step, report, period, owner)

    def slow_steps(self, since_ns=None, until_ns=None):
        """The records of the steps that ran long (at most
        ``SLOW_STEPS_KEPT``, oldest first) that lie inside the given
        ``perf_counter_ns`` bounds: one dict a step with ``name``,
        ``key``, ``kind``, ``t0_ns``, ``t1_ns``, ``median_ns``,
        ``cpu_ns``, ``spans``, ``compiles``, ``samples``, ``threads``,
        ``machine`` and ``where``.  docs/telemetry.md, "The slow-step
        record"."""
        return [dict(r) for r in tuple(self.watchdog.records)
                if (since_ns is None or r["t0_ns"] >= since_ns)
                and (until_ns is None or r["t1_ns"] <= until_ns)]

    @staticmethod
    def compile_log(since_ns=None, until_ns=None):
        """The compile account's records that closed inside the bounds,
        oldest first: one dict for every program JAX compiled or read from
        the persistent cache in this process (whichever ``Telemetry``
        object is asked), with ``t0_ns`` / ``t1_ns``, ``name``,
        ``trace_s``, ``lower_s``, ``backend_s``, ``cache`` (``hit`` with
        ``retrieval_s``, ``miss``, ``off``), ``site`` and ``shapes`` (a
        call through :func:`register_compiled`, else None), ``repeat``
        (the site had compiled those shapes before), ``span`` /
        ``span_attrs`` / ``span_ids`` (the spans open on its thread,
        innermost first).  docs/telemetry.md, "Set-up and the compile
        account"."""
        return _account.log(since_ns, until_ns)

    @staticmethod
    def startup_report(until_ns=None):
        """How the process started, up to ``until_ns``: ``seconds`` in
        ``import``, ``engine`` (with its parts ``engine/state``,
        ``engine/weights``, ``engine/pools``; each less the account's
        seconds inside it), ``trace``, ``lower``, ``compile`` (misses, and
        programs compiled with no cache), ``cache_read`` (hits); counts
        ``programs``, ``cache_hits``, ``cache_misses``,
        ``repeat_compiles``; the same ``by_site``; the ten ``slowest``
        records; the ``setup/*`` ``spans``.  Kept apart from the ring, so
        it still holds the start after the ring has wrapped."""
        return _account.report(until_ns)

    def gauge(self, name, value, step=None):
        """Set gauge ``name`` (peak-tracked) and emit a ``gauge`` event."""
        if not self.enabled:
            return
        g = self.registry.gauge(name)
        g.set(value)
        self.emit("gauge", name, value=float(value),
                  peak=round(g.peak, 6), step=step)

    def count(self, name, n=1):
        if not self.enabled:
            return
        self.registry.counter(name).inc(n)

    def fault(self, name, step=None, attrs=None):
        """Structured fault-tolerance event (runtime/resilience.py): I/O
        retries, checkpoint fallbacks, preemptions, divergence trips.  Each
        also bumps counter ``<name>/count`` so the registry shows fault
        totals without replaying the stream."""
        if not self.enabled:
            return
        self.registry.counter(f"{name}/count").inc()
        self.emit("fault", name, step=step, attrs=attrs or None)

    def serve(self, name, step=None, attrs=None):
        """Structured serving-robustness event (inference/robustness.py):
        admissions, typed rejections, load shedding, deadline cancels,
        per-slot evictions, drains.  Like :meth:`fault`, each also bumps
        counter ``<name>/count`` so the registry carries serving totals
        without replaying the stream."""
        if not self.enabled:
            return
        self.registry.counter(f"{name}/count").inc()
        self.emit("serve", name, step=step, attrs=attrs or None)

    def fleet(self, name, step=None, attrs=None):
        """Structured fleet-routing event (inference/fleet.py): replica
        spawns/kills/fences, routed dispatches, spills, redispatches,
        drains, respawns, and autoscale decisions.  Like :meth:`serve`,
        each also bumps counter ``<name>/count``."""
        if not self.enabled:
            return
        self.registry.counter(f"{name}/count").inc()
        self.emit("fleet", name, step=step, attrs=attrs or None)

    def tune(self, name, step=None, attrs=None):
        """Structured autotuning event (autotuning/controlplane.py): trial
        starts/results, feasibility prunes, and overlay persistence.  Like
        :meth:`serve`, each also bumps counter ``<name>/count`` so the
        registry carries tuning totals without replaying the stream."""
        if not self.enabled:
            return
        self.registry.counter(f"{name}/count").inc()
        self.emit("tune", name, step=step, attrs=attrs or None)

    def comm(self, op_name, size_bytes, axis):
        """Per-op comm census (trace-time: a shape traces once, executes
        many times — counts are per-trace like ``CommsLogger``).  Bare
        bytes-only form; timed spans go through :meth:`collective`."""
        self.collective(op_name, size_bytes, axis)

    def collective(self, op_name, size_bytes, axis, dtype=None, dur_ms=None,
                   world=None, wire_dtype=None, bytes_saved=None):
        """One traced/timed collective: counters ``comm/{op}/calls|bytes``,
        duration histogram ``comm/{op}_ms``, and a ``comm`` event carrying
        payload dtype, axis/group, world size, and achieved bus bandwidth
        against the analytic per-link peak (comm/topology_model.py).

        Quantized collectives (comm/quantize.py) pass ``size_bytes`` as
        the actual WIRE payload (int8 codes + scales) so the busbw math
        reflects the reduced traffic, plus ``wire_dtype`` (the on-wire
        dtype, e.g. ``"int8"``) and ``bytes_saved`` (dtype-true baseline
        minus wire bytes) — booked into counter
        ``comm/{op}/bytes_saved`` and the frozen gauge
        ``comm/{op}/quant_bytes_saved``.

        Durations are host-observed around the verb — trace time inside
        ``jit`` (the census convention), true wall time for host-level ops
        (``barrier``) and for callers that time executed programs (the
        comm benchmarks, the cpu_comm_census micro-bench)."""
        if not self.enabled:
            return
        self.registry.counter(f"comm/{op_name}/calls").inc()
        self.registry.counter(f"comm/{op_name}/bytes").inc(int(size_bytes))
        busbw = peak = None
        if dur_ms is not None:
            dur_ms = float(dur_ms)
            self.registry.histogram(f"comm/{op_name}_ms").observe(dur_ms)
            from deepspeed_tpu.comm.topology_model import bus_bandwidth
            busbw, peak = bus_bandwidth(op_name, size_bytes, dur_ms, world)
            if busbw is not None:
                self.registry.gauge(f"comm/{op_name}/busbw_gbps").set(busbw)
        if bytes_saved:
            self.registry.counter(
                f"comm/{op_name}/bytes_saved").inc(int(bytes_saved))
            self.registry.gauge(
                f"comm/{op_name}/quant_bytes_saved").set(int(bytes_saved))
        self.emit("comm", op_name, bytes=int(size_bytes), axis=str(axis),
                  dtype=str(dtype) if dtype is not None else None,
                  dur_ms=round(dur_ms, 4) if dur_ms is not None else None,
                  world=int(world) if world is not None else None,
                  busbw_gbps=(round(busbw, 4) if busbw is not None
                              else None),
                  peak_gbps=peak,
                  wire_dtype=(str(wire_dtype) if wire_dtype is not None
                              else None),
                  bytes_saved=(int(bytes_saved) if bytes_saved is not None
                               else None))

    def close(self):
        if self.exporter is not None:
            self.exporter.close()
            self.exporter = None
        if self.sink is not None:
            self.sink.close()
            self.sink = None
        self.cluster = None
        self.profiling = None
        self.incidents = None
        self.attribution = None
        self._stamp_rank = False
        self.enabled = False


# ----------------------------------------------------------------------
# the steps that run long (judge, sampler, record) and the hang verdict
# ----------------------------------------------------------------------
_TICK_S = 1.0 / os.sysconf("SC_CLK_TCK")


def _read(path):
    """A small ``/proc`` file's text (its first 64 KiB), or None where it
    cannot be read.  Three system calls: each one lets the interpreter's
    lock go, and a reader beside a thread that runs Python code waits to
    get it back."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return None
    try:
        return os.read(fd, 65536).decode("ascii", "replace")
    except OSError:
        return None
    finally:
        os.close(fd)


def read_tasks(only=None, first=(), budget_ns=None):
    """This process's native threads (``only``: those tids) from
    ``/proc/self/task/*/stat``: ``{tid: (comm, state, user + system
    ticks, major faults)}``, the tids of ``first`` ahead of the others.  A
    thread that cannot be read is left out; so are, past ``budget_ns``,
    the threads not read yet (while a thread runs Python code every read
    costs the reader a wait for the interpreter's lock: 7 ms a file was
    measured), and key None then says ``"partial"``."""
    if only is None:
        try:
            only = os.listdir("/proc/self/task")
        except OSError:
            return {}
    out, ahead = {}, [str(tid) for tid in first]
    since = time.perf_counter_ns()
    for tid in ahead + [t for t in map(str, only) if t not in ahead]:
        if budget_ns is not None and tid not in ahead and \
                time.perf_counter_ns() - since > budget_ns:
            out[None] = "partial"
            break
        text = _read(f"/proc/self/task/{tid}/stat")
        if text is None:
            continue
        # pid (comm) state ppid ... majflt(12) .. utime(14) stime(15)
        left, right = text.find("("), text.rfind(")")
        rest = text[right + 2:].split()
        try:
            out[int(tid)] = (text[left + 1:right], rest[0],
                             int(rest[11]) + int(rest[12]), int(rest[9]))
        except (IndexError, ValueError):
            continue
    return out


def read_machine(tid=None):
    """What the machine says of itself, as counters to take differences
    of: ``steal_s`` and ``iowait_s`` (``/proc/stat``), ``pgmajfault``,
    ``allocstall`` and ``compact_stall`` (``/proc/vmstat``),
    ``pressure_cpu_us`` / ``_memory_us`` / ``_io_us`` (the ``some total``
    of ``/proc/pressure/*``), the level ``load1``, and thread ``tid``'s
    ``voluntary`` / ``nonvoluntary`` context switches.  A file that cannot
    be read leaves its fields out."""
    out = {}
    try:
        cpu = (_read("/proc/stat") or "").split("\n", 1)[0].split()
        if len(cpu) > 8 and cpu[0] == "cpu":
            out["iowait_s"] = int(cpu[5]) * _TICK_S
            out["steal_s"] = int(cpu[8]) * _TICK_S
        for line in (_read("/proc/vmstat") or "").splitlines():
            name, _, value = line.partition(" ")
            if name in ("pgmajfault", "compact_stall"):
                out[name] = int(value)
            elif name.startswith("allocstall"):
                out["allocstall"] = out.get("allocstall", 0) + int(value)
        for what in ("cpu", "memory", "io"):
            some = (_read(f"/proc/pressure/{what}") or "").split("\n", 1)[0]
            if some.startswith("some") and "total=" in some:
                out[f"pressure_{what}_us"] = int(some.rpartition("total=")[2])
        load = _read("/proc/loadavg")
        if load:
            out["load1"] = float(load.split()[0])
        if tid is not None:
            status = _read(f"/proc/self/task/{tid}/status") or ""
            for line in status.splitlines():
                if line.startswith(("voluntary_ctxt", "nonvoluntary_ctxt")):
                    out[line.partition("_")[0]] = int(line.split()[-1])
    except (IndexError, ValueError):
        pass
    return out


def _stack(frame):
    """The innermost ``SAMPLE_FRAMES`` frames, innermost first, as
    ``file:line function``."""
    out = []
    while frame is not None and len(out) < SAMPLE_FRAMES:
        code = frame.f_code
        out.append(f"{code.co_filename}:{frame.f_lineno} {code.co_name}")
        frame = frame.f_back
    return tuple(out)


def _in_runtime(frames):
    """Whether a stack's innermost Python frame is JAX's: the thread is
    inside (or on its way back from) the runtime."""
    return bool(frames) and ("/jax/" in frames[0] or "/jaxlib/" in frames[0])


def where_of(record):
    """The one word of ``SLOW_STEP_WHERE`` for a record, by the rules of
    docs/telemetry.md "The slow-step record", first match wins."""
    half = (record["t1_ns"] - record["t0_ns"] - record["median_ns"]) / 2
    if record["compiles"]:
        return "compile"
    if record["cpu_ns"] > half:
        return "host_python"
    samples, me = record["samples"], record["thread"]
    machine = record["machine"]
    mine = [s["tasks"][s["python"][me]] for s in samples
            if s["python"].get(me) in s["tasks"]]
    states = [task[1] for task in mine]

    def most(count):
        return bool(samples) and 2 * count >= len(samples)

    late = max(record["late"], key=lambda note: note["late_ns"],
               default=None)
    if late is not None and late["late_ns"] < half:
        late = None
    # nobody of the process ran while the sampler overslept: the whole
    # process was off the CPU
    if late and 4 * late["process_cpu_ns"] < late["late_ns"]:
        return "descheduled"
    if most(states.count("R")) and (machine.get("steal_s", 0) > 0
                                    or machine.get("nonvoluntary", 0) > 0):
        return "descheduled"
    sampled_s = (samples[-1]["t_ns"] - samples[0]["t_ns"]) / 1e9 \
        if samples else 0.0
    if "D" in states or (mine and mine[-1][3] > mine[0][3]) or (
            machine.get("pgmajfault", 0) > 0
            and machine.get("iowait_s", 0.0) > sampled_s / 2 > 0):
        return "blocked_io"
    # the sampler overslept while another Python thread ran: that thread
    # held the interpreter's lock
    if late and any(2 * cpu >= late["late_ns"]
                    for name, cpu in late["threads"].items() if name != me):
        return "other_thread"
    if most(states.count("S")) and most(sum(
            _in_runtime(s["stacks"].get(me)) for s in samples)):
        return "runtime_wait"
    running = {}
    for s in samples:
        for name, tid in s["python"].items():
            if name != me and s["tasks"].get(tid, ("", "S"))[1] == "R":
                running[name] = running.get(name, 0) + 1
    if any(most(count) for count in running.values()):
        return "other_thread"
    if record.get("outside_ns", 0) >= half:
        return "caller"
    return "unknown"


class _Sampled:
    """What the sampler has of one late step."""

    __slots__ = ("id", "samples", "first", "last", "machine")

    def __init__(self, span_id):
        self.id, self.samples = span_id, []
        self.first = self.last = None   # the native threads, whole
        self.machine = None             # the counters at the first sample


class _Asleep(NamedTuple):
    """Where the sampler meant to wake, and what it knew going to sleep."""
    wake_at: int
    process_cpu: int
    python_cpu: dict        # thread name -> ticks
    since: int


class StepStallWatchdog:
    """The judge of every step's length, the sampler that looks at a step
    while it is late, and the verdict on a step that never ends.  One a
    :class:`Telemetry` (``telemetry.watchdog``), on like the ring; the
    engines start its thread.  docs/telemetry.md, "The slow-step record".

    **The judge** runs on the stepping thread at a step's close
    (:class:`_StepSpan`): the step is compared with the running median
    (last ``window``) of the steps of its kind and, past both
    ``SLOW_STEP_*`` thresholds, leaves a record in ``records``
    (:meth:`Telemetry.slow_steps`), one ``logger.warning`` line and, with
    telemetry enabled, a ``stall`` event and incident.

    **The sampler** is the thread: it sleeps until the armed step's
    deadline (its start + ``SLOW_STEP_EXCESS_NS`` + ``SLOW_STEP_MEDIANS``
    x the largest median any kind has) and, if that step is still open
    then, samples every ``SAMPLE_EVERY_NS`` until it closes: every
    thread's Python stack, every native thread's state and CPU ticks and,
    first and last, the machine's counters.  The judge takes the samples
    into the record; a step it does not call slow drops them.

    **The hang verdict** (``hangs``; the trainer with telemetry enabled
    feeds :meth:`beat`) is what the class was before: once the time since
    the last beat, less what the compile account saw compiled in it,
    passes ``max(stall_factor * median beat, min_stall_secs)``, one
    ``stall`` event a stalled step.  ``poll_interval_secs`` is no poll
    any more: it bounds the thread's sleep while ``hangs`` is set.
    """

    def __init__(self, telemetry, stall_factor=10.0,
                 poll_interval_secs=1.0, min_stall_secs=1.0,
                 window=SLOW_STEP_WINDOW, cluster=None,
                 cluster_poll_secs=30.0, hangs=True):
        self.telemetry = telemetry
        self.window = int(window)
        self.configure(hangs=hangs, stall_factor=stall_factor,
                       poll_interval_secs=poll_interval_secs,
                       min_stall_secs=min_stall_secs, cluster=cluster,
                       cluster_poll_secs=cluster_poll_secs)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = None
        # the judge: a kind's last steps (ns) and its median
        self.records = deque(maxlen=SLOW_STEPS_KEPT)
        self._kinds = {}        # kind -> [its last steps (ns), median, n]
        self._wait_ns = SLOW_STEP_EXCESS_NS
        self._local = threading.local()     # .period: the open _StepSpan
        # the step the sampler waits for: (span id, t0_ns, deadline_ns,
        # thread ident), written by the stepping thread
        self.armed = None
        # the sampler: what it has of one late step (_Sampled), where it
        # meant to wake (_Asleep) and how late it has been
        self._sampled = None
        self._asleep = None
        self._cpu_rate = 0.0
        self._late = deque(maxlen=8)
        self.wakes = 0
        self.read_tasks, self.read_machine = read_tasks, read_machine
        self.clock_ns = time.perf_counter_ns
        self.process_cpu_ns = time.process_time_ns

    def configure(self, hangs, stall_factor=10.0, poll_interval_secs=1.0,
                  min_stall_secs=1.0, cluster=None, cluster_poll_secs=30.0):
        """Set what the hang verdict goes by and forget its beats (an
        engine does so when it is made: ``hangs`` False leaves the judge
        and the sampler alone on)."""
        self.hangs = bool(hangs)
        self.stall_factor = float(stall_factor)
        self.poll_interval_secs = float(poll_interval_secs)
        self.min_stall_secs = float(min_stall_secs)
        # distributed mode: a ClusterAggregator over the rank shards —
        # the watchdog doubles as the cross-rank straggler sentinel
        self.cluster = cluster
        self.cluster_poll_secs = float(cluster_poll_secs)
        self._last_cluster_poll = None
        self._cluster_reported = None
        self._durations = deque(maxlen=self.window)
        self._last_beat = None
        self._last_step = -1
        self._stall_reported = False
        return self

    def start(self):
        if self._thread is None:
            self._stop.clear()
            # the thread holds no reference to this object between two
            # wake-ups: a Telemetry that is dropped takes its thread along
            self._thread = threading.Thread(
                target=self._run, args=(weakref.ref(self), self._stop),
                daemon=True, name="ds-stall-watchdog")
            self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    # -- the judge (the stepping thread) -------------------------------
    def step_opened(self, span):
        t0 = span.t0
        span.cpu0 = cpu = time.thread_time_ns()
        if span.period:
            local = self._local
            before = getattr(local, "period", None)
            local.period = span
            if before is not None and before.owner == span.owner:
                self.judge(before.name, before.step, (span.owner, "train"),
                           before.t0, t0, cpu - before.cpu0, before.id,
                           None, True)
        self.armed = (span.id, t0, t0 + self._wait_ns,
                      threading.get_ident())

    def loop_closed(self, span, t1):
        self.armed = None
        t0, report = span.t0, span.report
        # the thread's CPU time is a system call (6 us on the chip's host):
        # read again only for a step that can be slow at all
        cpu = time.thread_time_ns() if t1 - t0 > SLOW_STEP_EXCESS_NS \
            else span.cpu0
        # the kind of a loop: the phase and shape of what it launched
        self.judge(span.name, span.step,
                   (span.owner, *[(d["phase"], d["batch"], d["tokens"])
                                  for d in report["dispatches"]
                                  if d["t0_ns"] >= t0])
                   if report else (span.owner,),
                   t0, t1, cpu - span.cpu0, span.id, report)

    def judge(self, name, key, kind, t0_ns, t1_ns, cpu_ns=0, span_id=None,
              report=None, period=False):
        """One closed step of ``kind`` (hashable; its first item the
        owner): compare it with the median of its kind, remember it, and
        return its record if it was slow (None otherwise).  The median is
        that of the kind's last ``window`` steps as of the last multiple
        of ``SLOW_STEP_MIN_KIND`` steps, so a sound step costs a look-up,
        two comparisons and an append."""
        took = t1_ns - t0_ns
        state = self._kinds.get(kind)
        if state is None:
            if len(self._kinds) >= SLOW_STEP_KINDS:
                del self._kinds[next(iter(self._kinds))]
            state = self._kinds[kind] = [deque(maxlen=self.window), None, 0]
        steps, median, _ = state
        steps.append(took)
        state[2] = n = state[2] + 1
        if not n % SLOW_STEP_MIN_KIND:
            state[1] = sorted(steps)[len(steps) // 2]
            self._wait_ns = SLOW_STEP_EXCESS_NS + SLOW_STEP_MEDIANS * max(
                s[1] for s in self._kinds.values() if s[1] is not None)
        if median is not None and took > SLOW_STEP_MEDIANS * median \
                and took - median > SLOW_STEP_EXCESS_NS:
            return self._record(name, key, kind, t0_ns, t1_ns, median,
                                cpu_ns, span_id, report, period, n)
        return None

    def _record(self, name, key, kind, t0, t1, median, cpu_ns, span_id,
                report, period, n):
        me = threading.current_thread()
        with self._lock:
            sampled = self._sampled
            if sampled is None or sampled.id != span_id or span_id is None:
                sampled = _Sampled(span_id)
            samples = list(sampled.samples)
        spans = self._tree(span_id, t0, t1)
        for sample in samples:
            inside = [s for s in spans
                      if s["t0_ns"] <= sample["t_ns"] <= s["t1_ns"]]
            sample["span"] = inside[-1]["name"] if inside else None
        machine = {}
        if sampled.machine is not None:
            last = self.read_machine(me.native_id)
            machine = {k: last[k] if k == "load1" else last[k] - v
                       for k, v in sampled.machine.items() if k in last}
        python = samples[-1]["python"] if samples else {}
        names = {tid: thread for thread, tid in python.items()}
        # from the step's first sample to a reading of the judge's own
        first = sampled.first or {}
        last = self.read_tasks() if first else {}
        threads = sorted(
            ({"tid": tid, "comm": task[0], "python": names.get(tid),
              "cpu_s": (task[2] - first[tid][2]) * _TICK_S}
             for tid, task in last.items()
             if tid is not None and tid in first),
            key=lambda t: t["cpu_s"], reverse=True)[:SAMPLE_THREADS]
        notes = (*tuple(self._late), self.late_note(t1))
        late = {n["from_ns"]: n for n in notes
                if n is not None and n["to_ns"] > t0 and n["from_ns"] < t1}
        record = {
            "name": name, "key": key,
            "kind": "+".join(k if isinstance(k, str) else
                             "{}:{}x{}".format(*k) for k in kind[1:])
                    or "idle",
            "t0_ns": t0, "t1_ns": t1, "median_ns": median,
            "cpu_ns": cpu_ns, "thread": me.name, "tid": me.native_id,
            "spans": spans,
            "compiles": [
                {k: r[k] for k in ("name", "site", "cache", "t0_ns",
                                   "t1_ns", "span")}
                for r in _account.log(t0, t1)],
            "samples": samples, "threads": threads, "machine": machine,
            "late": sorted(late.values(), key=lambda n: n["from_ns"])}
        if period:
            # the part of a trainer's period under no program span
            own = [s for s in spans if s["id"] == span_id]
            record["outside_ns"] = (t1 - t0) - sum(
                s["t1_ns"] - s["t0_ns"] for s in own)
        if report is not None:
            # what the loop launched
            record["dispatches"] = [dict(d) for d in report["dispatches"]
                                    if d["t0_ns"] >= t0]
        record["where"] = where_of(record)
        self.records.append(record)
        self._tell(record, n)
        return record

    def _tree(self, span_id, t0, t1):
        """The step's span and those beneath it, from the ring, ordered by
        start (so the innermost of two that hold an instant comes last)."""
        ring = getattr(self.telemetry, "ring", None)
        if ring is None or span_id is None:
            return []
        inside, tree = ring.spans(t0, None), {span_id}
        out = []
        for s in inside:        # by start: a parent ahead of its children
            if s.id == span_id or s.parent in tree:
                tree.add(s.id)
                out.append({"id": s.id, "parent": s.parent, "name": s.name,
                            "t0_ns": s.t0_ns, "t1_ns": s.t1_ns})
        return out

    def _tell(self, record, n):
        wall = (record["t1_ns"] - record["t0_ns"]) / 1e9
        median = record["median_ns"] / 1e9
        samples, machine = record["samples"], record["machine"]
        span = samples[-1]["span"] if samples else None
        other = next((t for t in record["threads"]
                      if t["tid"] != record["tid"]), None)
        late = max((n["late_ns"] for n in record["late"]), default=0)
        logger.warning(
            f"slow step: {record['name']} {record['key']} "
            f"[{record['kind']}] took {wall:.3f}s (median of its kind "
            f"{median:.4f}s), {record['cpu_ns'] / 1e9:.3f}s on the CPU: "
            f"{record['where']}; in {span or 'no program span'}; "
            + (f"busiest other thread {other['comm']} "
               f"{other['cpu_s']:.2f}s; " if other else "")
            + "".join(f"{k} +{machine[k]:.3g}; "
                      for k in ("steal_s", "iowait_s", "compact_stall")
                      if k in machine)
            + f"sampler late {late / 1e9:.3f}s; {len(samples)} samples; "
            f"t0_ns {record['t0_ns']} t1_ns {record['t1_ns']}")
        tel = self.telemetry
        if not getattr(tel, "enabled", False):
            return
        step = record["key"] if isinstance(record["key"], int) else n
        threshold = max(SLOW_STEP_MEDIANS * median,
                        median + SLOW_STEP_EXCESS_NS / 1e9)
        tel.emit("stall", record["name"], step=step, gap_s=round(wall, 3),
                 median_step_s=round(median, 6),
                 threshold_s=round(threshold, 3), where=record["where"],
                 cpu_s=round(record["cpu_ns"] / 1e9, 3), span=span)
        incidents = getattr(tel, "incidents", None)
        if incidents is not None:
            incidents.trigger(
                "stall", source=record["name"], step=step,
                detail=f"slow step: {wall:.3f}s against a median of "
                       f"{median:.4f}s ({record['where']})")

    # -- the sampler (the thread) --------------------------------------
    def _python_cpu(self):
        """CPU ticks of the Python threads, by name."""
        threads = {t.native_id: t.name for t in threading.enumerate()}
        return {threads[tid]: task[2]
                for tid, task in self.read_tasks(list(threads)).items()}

    def late_note(self, now_ns):
        """How far past the sampler's intended wake-up ``now_ns`` lies, if
        by a sample's interval or more, with the CPU the process and its
        Python threads used meanwhile (the process's less what it uses in
        a sleep of the planned length, by the last sleep that ended on
        time); None otherwise.  Read by the sampler when it wakes, and by
        the judge when a slow step closes before the sampler has."""
        asleep = self._asleep
        if asleep is None or now_ns - asleep.wake_at < SAMPLE_EVERY_NS:
            return None
        usual = int(self._cpu_rate * (asleep.wake_at - asleep.since))
        return {"from_ns": asleep.wake_at, "to_ns": now_ns,
                "late_ns": now_ns - asleep.wake_at,
                "process_cpu_ns": max(
                    0, self.process_cpu_ns() - asleep.process_cpu - usual),
                "threads": {
                    name: int((ticks - asleep.python_cpu[name]) * _TICK_S
                              * 1e9)
                    for name, ticks in self._python_cpu().items()
                    if name in asleep.python_cpu}}

    def _stacks(self, now_ns, late_ns):
        """A sample with every other thread's Python stack (one call under
        the interpreter's lock), its native threads still to be read."""
        me = threading.get_ident()
        names = {t.ident: (t.name, t.native_id)
                 for t in threading.enumerate()}
        stacks, python = {}, {}
        frames = sys._current_frames()
        for ident, frame in frames.items():
            if ident != me:
                name, tid = names.get(ident, (f"thread-{ident}", None))
                stacks[name], python[name] = _stack(frame), tid
        # a kept frame keeps its locals alive: only the text stays
        del frames, frame
        return {"t_ns": now_ns, "late_ns": late_ns, "span": None,
                "stacks": stacks, "python": python, "tasks": {}}

    def wake(self, now_ns=None):
        """One turn of the sampler (tests call it on their own clock):
        note how late it is, judge a hang, sample the armed step if that
        is past its deadline, and say when to wake next (ns)."""
        now = self.clock_ns() if now_ns is None else now_ns
        self.wakes += 1
        note = self.late_note(now)
        if note is not None:
            self._late.append(note)
        elif self._asleep is not None and now > self._asleep.since:
            # CPU nanoseconds the process uses a nanosecond, while sound
            self._cpu_rate = (self.process_cpu_ns()
                              - self._asleep.process_cpu) \
                / (now - self._asleep.since)
        if self.hangs:
            self.check(now / 1e9)
            self.check_cluster(now / 1e9)
        armed, sampled = self.armed, self._sampled
        if armed is None or now < armed[2]:
            # nothing is late: what was sampled of a step that has closed
            # the judge took if it wanted it
            if sampled is not None and (armed is None
                                        or armed[0] != sampled.id):
                with self._lock:
                    self._sampled = None
            wake_at = armed[2] if armed is not None else now + self._wait_ns
        else:
            if sampled is None or sampled.id != armed[0]:
                sampled = _Sampled(armed[0])
            wake_at = now + SAMPLE_EVERY_NS
            if len(sampled.samples) < SAMPLES_MAX:
                self._take(sampled, armed, now, note)
            elif not self.hangs:
                # sampled out, and no verdict to give: wait for the close
                wake_at = now + self._wait_ns
        if self.hangs:
            wake_at = min(wake_at,
                          now + int(self.poll_interval_secs * 1e9))
        self._asleep = _Asleep(wake_at, self.process_cpu_ns(),
                               self._python_cpu(), now)
        return wake_at

    def _take(self, sampled, armed, now, note):
        """One sample of the armed step into ``sampled``: the stacks at
        once, then the machine (a step's first sample) and the native
        threads, the Python ones first."""
        sample = self._stacks(now, note["late_ns"] if note else 0)
        with self._lock:
            sampled.samples.append(sample)
            self._sampled = sampled
        python = [tid for tid in sample["python"].values()
                  if tid is not None]
        if sampled.machine is None:
            sampled.machine = self.read_machine(next(
                (t.native_id for t in threading.enumerate()
                 if t.ident == armed[3]), None))
        tasks = self.read_tasks(first=python, budget_ns=SAMPLE_EVERY_NS // 2)
        if sampled.first is None:
            sample["tasks"] = sampled.first = tasks
        else:
            # keep of the native threads those a reader needs: the Python
            # ones and whatever ran or was not asleep; and one copy of a
            # stack that did not move
            before = sampled.samples[-2]
            sample["tasks"] = {
                tid: task for tid, task in tasks.items()
                if tid in python or tid is None or task[1] != "S"
                or task[2] != sampled.last.get(tid, task)[2]}
            for name, frames in sample["stacks"].items():
                if before["stacks"].get(name) == frames:
                    sample["stacks"][name] = before["stacks"][name]
        sampled.last = tasks

    @staticmethod
    def _run(ref, stop):
        timeout = 0.0
        while not stop.wait(timeout):
            watchdog = ref()
            if watchdog is None:
                return
            try:
                wake_at = watchdog.wake()
                timeout = max(0.0, (wake_at - watchdog.clock_ns()) / 1e9)
            except Exception as e:  # never kill the host process
                logger.warning(f"stall watchdog failed: {e}")
                timeout = 1.0
            del watchdog

    # -- the hang verdict ----------------------------------------------
    def beat(self, step, now=None):
        """Record a completed step; emits a ``heartbeat`` event carrying the
        measured step wall time.  ``now`` is injectable for deterministic
        tests (FakeClock), defaulting to ``time.perf_counter`` (the clock
        of the ring and of the compile account)."""
        now = now if now is not None else time.perf_counter()
        with self._lock:
            step_s = (now - self._last_beat
                      if self._last_beat is not None else None)
            if step_s is not None:
                self._durations.append(step_s)
            self._last_beat = now
            self._last_step = int(step)
            self._stall_reported = False
        self.telemetry.emit(
            "heartbeat", "engine/step", step=int(step),
            step_ms=(round(step_s * 1000.0, 3)
                     if step_s is not None else None))

    def median_step_secs(self):
        with self._lock:
            if not self._durations:
                return None
            vals = sorted(self._durations)
            return vals[len(vals) // 2]

    def check(self, now=None):
        """One evaluation of the hang verdict (the thread calls this when
        it wakes; tests may call it directly for determinism).  Returns
        True if a stall event was emitted."""
        now = now if now is not None else time.perf_counter()
        with self._lock:
            if self._last_beat is None or len(self._durations) < 2 or \
                    self._stall_reported:
                return False
            last_beat, last_step = self._last_beat, self._last_step
            vals = sorted(self._durations)
            median = vals[len(vals) // 2]
        threshold = max(self.stall_factor * median, self.min_stall_secs)
        # a step that compiled may exceed the threshold by what the
        # compile account saw compiled since the last beat
        gap = now - last_beat - math.fsum(
            min(_seconds(r), r["t1_ns"] / 1e9 - last_beat)
            for r in _account.log(int(last_beat * 1e9), int(now * 1e9)))
        if gap <= threshold:
            return False
        with self._lock:
            self._stall_reported = True
        logger.warning(
            f"step stall: {gap:.1f}s since step {last_step} completed "
            f"(rolling-median step {median:.3f}s, threshold {threshold:.1f}s)")
        self.telemetry.emit(
            "stall", "engine/step", step=last_step, gap_s=round(gap, 3),
            median_step_s=round(median, 6), threshold_s=round(threshold, 3))
        incidents = getattr(self.telemetry, "incidents", None)
        if incidents is not None:
            incidents.trigger(
                "stall", source="engine/step", step=last_step,
                detail=f"gap {gap:.1f}s > threshold {threshold:.1f}s "
                       f"(median step {median:.3f}s)")
        return True

    def check_cluster(self, now=None):
        """Cross-rank straggler sweep (distributed mode only): refresh the
        shard aggregator on its own slower cadence and emit ONE meta event
        per newly flagged straggler rank.  Returns the flagged rank (int)
        or None.  File I/O bounded: the aggregator tails shards and this
        runs every ``cluster_poll_secs``, not every watchdog poll."""
        if self.cluster is None:
            return None
        now = now if now is not None else time.perf_counter()
        if self._last_cluster_poll is not None and \
                now - self._last_cluster_poll < self.cluster_poll_secs:
            return self._cluster_reported
        self._last_cluster_poll = now
        snap = self.cluster.snapshot()
        verdict = snap.get("straggler") or {}
        rank = verdict.get("rank")
        if rank is not None and rank != self._cluster_reported:
            logger.warning(
                f"cluster straggler: rank {rank} "
                f"({verdict.get('metric')}) beyond "
                f"{verdict.get('threshold')}x median")
            self.telemetry.emit(
                "meta", "cluster/straggler",
                attrs={"rank": int(rank),
                       "metric": str(verdict.get("metric")),
                       "threshold": verdict.get("threshold")})
        self._cluster_reported = rank
        return rank


_telemetry = Telemetry()


def get_telemetry() -> Telemetry:
    """The process-global telemetry instance (engine init configures it)."""
    return _telemetry


# ----------------------------------------------------------------------
# non-blocking metric readback
# ----------------------------------------------------------------------
class MetricsDrain:
    """Defers device→host metric readback off the dispatch hot path.

    The engine pushes each step's metric scalars as DEVICE values (no
    ``float()``, no ``device_get``) — they stay enqueued as in-flight array
    references while dispatch runs ahead.  Readback happens either

    * on a ``sync_interval`` boundary: every K-th ``push`` fetches all
      pending steps with ONE batched ``jax.device_get`` (K device hops
      collapse to one, amortized across the interval), or
    * on a drainer thread (``use_thread=True``): ``push`` hands the device
      refs to a daemon that blocks on them off-thread, so the training
      loop never waits at all.  The hand-off queue is bounded and lossy
      (``drain/dropped`` counts discards) — a slow drainer must never
      backpressure the step loop.

    ``emit_fn(step, {name: float})`` receives host values in step order.
    All readback funnels through ``jax.device_get`` so tests can assert
    the hot loop performs none (monkeypatch-count).
    """

    def __init__(self, emit_fn, sync_interval=1, use_thread=False,
                 max_pending=256):
        self.emit_fn = emit_fn
        self.sync_interval = max(1, int(sync_interval))
        self.use_thread = bool(use_thread)
        self._pending = []  # [(step, {name: device_scalar})]
        self._dropped = 0
        self._queue = None
        self._thread = None
        self._stop = None
        if self.use_thread:
            import queue as queue_lib
            self._queue = queue_lib.Queue(maxsize=max(1, int(max_pending)))
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._drain_loop, daemon=True, name="ds-metrics-drain")
            self._thread.start()

    # -- hot path (no device sync) -------------------------------------
    def push(self, step, values):
        """Queue one step's device metric scalars; returns immediately."""
        if self.use_thread:
            import queue as queue_lib
            try:
                self._queue.put_nowait((int(step), values))
            except queue_lib.Full:
                self._dropped += 1  # never block the step loop
            return
        self._pending.append((int(step), values))
        if len(self._pending) >= self.sync_interval:
            self.flush()

    @property
    def pending(self):
        return len(self._pending)

    @property
    def dropped(self):
        return self._dropped

    # -- readback ------------------------------------------------------
    def _fetch_and_emit(self, batch):
        """One batched transfer for every pending step, then per-step emit."""
        if not batch:
            return
        import jax
        flat = [v for _, vals in batch for v in vals.values()]
        host = iter(jax.device_get(flat))
        for step, vals in batch:
            self.emit_fn(step, {k: float(next(host)) for k in vals})

    def flush(self):
        """Fetch + emit everything pending (interval mode; thread mode
        drains via its worker — flush just waits for the queue to empty)."""
        if self.use_thread:
            if self._queue is not None:
                self._queue.join()
            return
        batch, self._pending = self._pending, []
        self._fetch_and_emit(batch)

    def _drain_loop(self):
        import queue as queue_lib
        while not self._stop.is_set():
            try:
                item = self._queue.get(timeout=0.1)
            except queue_lib.Empty:
                continue
            try:
                self._fetch_and_emit([item])
            except Exception as e:
                logger.warning(f"metrics drain failed: {e}")
            finally:
                self._queue.task_done()

    def close(self):
        """Flush remaining metrics and stop the drainer."""
        if self.use_thread:
            if self._queue is not None:
                self._queue.join()
            self._stop.set()
            if self._thread is not None:
                self._thread.join(timeout=5.0)
                self._thread = None
            return
        self.flush()
