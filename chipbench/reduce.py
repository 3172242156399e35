"""From a profiler trace (``*.xplane.pb``) to numbers: device busy and idle
time, time per kind of operation, and what the host was doing in each idle
gap.  No other file reads a trace; the per-layer readers in
``chipbench/reducers/`` ask this module.

What a TPU trace holds (looked at by hand, PERF.md §6): one plane per chip
named ``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per executed
operation, named by its whole HLO text (``%fusion.3 = bf16[..] fusion(..)``);
asynchronous operations (copies, collectives) span start to done on the
line ``Async XLA Ops``; the line ``XLA Modules`` has one event per executed
PROGRAM, named by its jit (``jit_serve_prefill(<fingerprint>)``), that
spans the program's operations.  Host threads are lines of the plane
``/host:CPU``; the benchmark's own ``jax.profiler.TraceAnnotation``s
(``chipbench/...``) are events there, on the same clock to within about a
millisecond.
"""

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

ANNOTATION_PREFIX = "chipbench/"
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
# operations whose event spans the events of their body: counted as busy
# time like any other, but not summed beside their own children
CONTAINERS = ("while", "conditional", "call")
_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)(-start|-done)?$")


def parse_op(text):
    """HLO text of one event -> (name, opcode).  ``%a.1 = f32[8] add(..)``
    gives ``("a.1", "add")``; a bare name gives ``(name, "")``."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%"), ""
    if rest.startswith("("):            # tuple type: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    found = re.match(r"[\w\-]+", rest)
    return name.lstrip("%"), found.group(0) if found else ""


def op_kind(text):
    """One of ``pallas``, ``collective``, ``xla`` for an event's text."""
    name, opcode = parse_op(text)
    if PALLAS_MARK in text:
        return "pallas"
    if _COLLECTIVE.match(opcode) or _COLLECTIVE.match(
            re.sub(r"[.\d]+$", "", name)):
        return "collective"
    return "xla"


@dataclass
class DeviceLine:
    """Events of one line of one device, as arrays (nanoseconds)."""
    start: np.ndarray
    dur: np.ndarray
    label: np.ndarray          # index into Trace.labels


@dataclass
class Trace:
    labels: list = field(default_factory=list)     # "name:opcode"
    kinds: list = field(default_factory=list)      # per label
    ops: list = field(default_factory=list)        # DeviceLine per device
    async_ops: list = field(default_factory=list)  # DeviceLine per device
    annotations: list = field(default_factory=list)  # (name, start, end)
    # the executed programs: a DeviceLine per device whose ``label`` is an
    # index into ``module_names`` (the event's whole name, fingerprint and
    # all); empty where the trace has no ``XLA Modules`` line
    modules: list = field(default_factory=list)
    module_names: list = field(default_factory=list)

    @property
    def window(self):
        """The traced window: from the first to the last of the
        benchmark's own annotations."""
        if not self.annotations:
            starts = [d.start.min() for d in self.ops if len(d.start)]
            ends = [(d.start + d.dur).max() for d in self.ops if len(d.start)]
            return (min(starts), max(ends)) if starts else (0, 0)
        return (min(a[1] for a in self.annotations),
                max(a[2] for a in self.annotations))


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path):
    """Read a trace with nothing but JAX."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    trace = Trace()

    def new_op(text):
        trace.labels.append(":".join(parse_op(text)))
        trace.kinds.append(op_kind(text))

    def line_arrays(line, index, new_label):
        """A line's events; ``index`` maps an event's text to its label,
        ``new_label(text)`` records a text met for the first time."""
        start, dur, label = [], [], []
        for event in line.events:
            text = event.name
            if text not in index:
                index[text] = len(index)
                new_label(text)
            start.append(event.start_ns)
            dur.append(event.duration_ns)
            label.append(index[text])
        return DeviceLine(np.asarray(start, np.float64),
                          np.asarray(dur, np.float64),
                          np.asarray(label, np.int64))

    ops, modules = {}, {}       # text -> label: the two lists of labels
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                trace.ops.append(line_arrays(lines["XLA Ops"], ops, new_op))
                if "Async XLA Ops" in lines:
                    trace.async_ops.append(line_arrays(
                        lines["Async XLA Ops"], ops, new_op))
                if "XLA Modules" in lines:
                    trace.modules.append(line_arrays(
                        lines["XLA Modules"], modules,
                        trace.module_names.append))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith(ANNOTATION_PREFIX):
                        trace.annotations.append(
                            (event.name, event.start_ns,
                             event.start_ns + event.duration_ns))
    trace.annotations.sort(key=lambda a: a[1])
    return trace


def _union(start, end):
    """Merge intervals; returns (starts, ends) of the disjoint union."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start, kind="stable")
    start, end = start[order], np.maximum.accumulate(end[order])
    new = np.concatenate(([True], start[1:] > end[:-1]))
    first = np.flatnonzero(new)
    last = np.concatenate((first[1:] - 1, [len(start) - 1]))
    return start[first], end[last]


def _clipped(line, window, mask=None):
    lo, hi = window
    start, end = line.start, line.start + line.dur
    if mask is not None:
        start, end = start[mask], end[mask]
    keep = (end > lo) & (start < hi)
    return np.clip(start[keep], lo, hi), np.clip(end[keep], lo, hi)


def _length(start, end):
    return float(np.sum(end - start))


def _selected(trace, kind, name):
    """Which of the trace's labels are operations of ``kind`` whose name
    on the device's line begins with ``name`` (either may be None: any)."""
    return np.asarray([(kind is None or k == kind)
                       and (name is None or label.startswith(name))
                       for label, k in zip(trace.labels, trace.kinds)], bool)


def busy_seconds(trace, kind=None, name=None):
    """Seconds in which an operation (of ``kind``, and named ``name...``,
    if given) ran on the device inside the window: the union of the
    intervals, averaged over the devices."""
    per_device = []
    selected = _selected(trace, kind, name)
    for line in trace.ops:
        per_device.append(_length(*_union(*_clipped(
            line, trace.window, selected[line.label]))))
    return float(np.mean(per_device)) / 1e9 if per_device else 0.0


def window_seconds(trace):
    lo, hi = trace.window
    return (hi - lo) / 1e9


def op_seconds(trace, kind=None, name=None):
    """Summed durations of the window's operations by label, averaged over
    the devices: {label: seconds}, of ``kind`` and named ``name...`` if
    given.  Loops and calls are left out: their bodies' operations are
    events of their own."""
    totals = np.zeros(len(trace.labels))
    selected = _selected(trace, kind, name)
    for line in trace.ops:
        start, end = line.start, line.start + line.dur
        lo, hi = trace.window
        length = np.clip(end, lo, hi) - np.clip(start, lo, hi)
        np.add.at(totals, line.label, np.maximum(length, 0))
    totals /= max(1, len(trace.ops)) * 1e9
    out = {}    # two programs may each have a "fusion.3": one label, summed
    for label, t, wanted in zip(trace.labels, totals, selected):
        if t > 0 and wanted and \
                label.rpartition(":")[2] not in CONTAINERS:
            out[label] = out.get(label, 0.0) + float(t)
    return out


def op_count(trace, kind, name=None):
    """Executions of operations of ``kind`` (named ``name...``, if given)
    in the window, averaged over the devices."""
    selected = _selected(trace, kind, name)
    lo, hi = trace.window
    counts = [int(np.sum(selected[line.label] & (line.start >= lo)
                         & (line.start < hi))) for line in trace.ops]
    return float(np.mean(counts)) if counts else 0.0


def collective_seconds(trace):
    """(seconds in collectives, seconds of them with no computation on
    that device), averaged over the devices.  A collective's interval is
    its event on either line (an asynchronous one spans start to done);
    computation is every other event of ``XLA Ops``."""
    kinds = np.asarray(trace.kinds)
    total, exposed = [], []
    for i, line in enumerate(trace.ops):
        coll = [_clipped(line, trace.window, kinds[line.label] == "collective")]
        if i < len(trace.async_ops):
            other = trace.async_ops[i]
            coll.append(_clipped(other, trace.window,
                                 kinds[other.label] == "collective"))
        c_start, c_end = _union(np.concatenate([c[0] for c in coll]),
                                np.concatenate([c[1] for c in coll]))
        k_start, k_end = _union(*_clipped(
            line, trace.window, kinds[line.label] != "collective"))
        both = _length(*_union(np.concatenate((c_start, k_start)),
                               np.concatenate((c_end, k_end))))
        total.append(_length(c_start, c_end))
        exposed.append(both - _length(k_start, k_end))
    if not total:
        return 0.0, 0.0
    return float(np.mean(total)) / 1e9, float(np.mean(exposed)) / 1e9


def idle_gaps(trace):
    """Idle seconds of the first device by what the host was doing: each
    gap between operations goes to the innermost of the benchmark's
    annotations that covers its middle.  {annotation: seconds}."""
    if not trace.ops:
        return {}
    lo, hi = trace.window
    start, end = _union(*_clipped(trace.ops[0], trace.window))
    gap_start = np.concatenate(([lo], end))
    gap_end = np.concatenate((start, [hi]))
    real = gap_end > gap_start
    gap_start, gap_end = gap_start[real], gap_end[real]
    middle = (gap_start + gap_end) / 2
    # longest annotations first, so the innermost one that covers a gap
    # is the last to claim it
    owner = np.full(len(middle), -1)
    spans = sorted(trace.annotations, key=lambda a: a[1] - a[2])
    for i, (_, a, b) in enumerate(spans):
        owner[(middle >= a) & (middle <= b)] = i
    out = {}
    for i in np.unique(owner):
        name = spans[i][0] if i >= 0 else "_no_annotation_"
        out[name] = out.get(name, 0.0) + float(np.sum(
            (gap_end - gap_start)[owner == i])) / 1e9
    return out


def breakdown(trace, top=10):
    """The ``breakdown`` of a traced result line: seconds over the traced
    window, in every cell."""
    ops = sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
