"""What the per-layer readers share that read the PROGRAM's own account of
its work: the span ring of ``deepspeed_tpu.monitor.telemetry`` (every
``Telemetry.span`` of the process, on ``time.perf_counter_ns``, the clock
``run.steps`` is stamped with) and ``op_scopes()`` (instruction name ->
step phase).  A program that has neither (a commit from before they were
added) gives None everywhere, and the metric is left out."""

import fnmatch

import numpy as np

from chipbench import reduce

STEP_ANNOTATION = reduce.ANNOTATION_PREFIX + "step"


def telemetry():
    from deepspeed_tpu.monitor.telemetry import get_telemetry
    return get_telemetry()


def window_spans(run):
    """The program's spans that lie wholly inside the run's whole steps
    (``run.steps[0]["t0"]`` .. ``run.steps[-1]["t1"]``), ordered by start;
    None where the program keeps no ring or recorded nothing there."""
    tel = telemetry()
    if not hasattr(tel, "spans") or not run.steps:
        return None
    return tel.spans(int(run.steps[0]["t0"] * 1e9),
                     int(run.steps[-1]["t1"] * 1e9)) or None


def matches(name, patterns):
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def children_of(spans):
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    return kids


def nested_ns(span, kids, patterns):
    """Nanoseconds of ``span`` spent in its outermost descendants whose
    names match ``patterns``."""
    total = 0
    for child in kids.get(span.id, ()):
        if matches(child.name, patterns):
            total += child.t1_ns - child.t0_ns
        else:
            total += nested_ns(child, kids, patterns)
    return total


def clock_fit(run):
    """How the ring's clock lies on the trace's: ``offset_ns`` to add to a
    ``perf_counter_ns`` reading to get the trace's clock (the median, over
    the traced steps, of the end of the step's ``chipbench/step``
    annotation less the step's ``t1``: the benchmark reads its clock right
    after the annotation closes), the ``steps`` matched, and how far the
    single steps' offsets lie from the median (``worst_ns``,
    ``median_abs_ns``).  The trace may hold annotations of steps the
    window left out, so the steps are slid along the annotations to where
    the offsets agree best.  None without a trace, annotations or traced
    steps."""
    if run.trace is None or not run.traced_steps:
        return None
    ends = np.asarray(sorted(a[2] for a in run.trace.annotations
                             if a[0] == STEP_ANNOTATION), np.float64)
    steps = np.asarray([s["t1"] * 1e9 for s in run.traced_steps])
    if not len(ends):
        return None
    short, long_ = (steps, ends) if len(steps) <= len(ends) else (ends, steps)
    sign = 1.0 if len(steps) <= len(ends) else -1.0
    best = None
    for shift in range(len(long_) - len(short) + 1):
        diff = sign * (long_[shift:shift + len(short)] - short)
        spread = float(np.max(diff) - np.min(diff))
        if best is None or spread < best[0]:
            best = (spread, diff)
    offset = float(np.median(best[1]))
    return {"offset_ns": offset, "steps": len(short),
            "worst_ns": float(np.max(np.abs(best[1] - offset))),
            "median_abs_ns": float(np.median(np.abs(best[1] - offset)))}


def clock_offset_ns(run):
    fit = clock_fit(run)
    return fit["offset_ns"] if fit else None


def idle_gaps_ns(trace):
    """(starts, ends) of the first device's idle gaps inside the traced
    window, as ``reduce.idle_gaps`` cuts them."""
    lo, hi = trace.window
    start, end = reduce._union(*reduce._clipped(trace.ops[0], trace.window))
    gap_start = np.concatenate(([lo], end))
    gap_end = np.concatenate((start, [hi]))
    real = gap_end > gap_start
    return gap_start[real], gap_end[real]
