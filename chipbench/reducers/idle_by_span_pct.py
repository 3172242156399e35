"""Idle time of the first device, by what the PROGRAM was doing: the idle
gaps of the traced window are cut at the program's span boundaries (the
ring's clock laid on the trace's by the ``chipbench/step`` annotations,
``program_spans.clock_offset_ns``), every piece goes to the innermost
span over it, and the pieces whose span's name matches ``names`` are
summed, in % of the traced window.  A gap is often longer than the spans
it crosses (the logits copy, then sampling, then the next launch), so it
is shared out by overlap, not handed whole to one span.  Idle under no
listed name stays with ``idle_pct``'s rest."""

import numpy as np

from chipbench import reduce
from chipbench.reducers import program_spans


def gaps_by_span(run):
    """{span name or "_no_span_": idle seconds} over the traced window,
    or None where there is no trace or no ring."""
    if run.trace is None or not run.trace.ops:
        return None
    tel = program_spans.telemetry()
    offset = program_spans.clock_offset_ns(run)
    if not hasattr(tel, "spans") or offset is None:
        return None
    lo, hi = run.trace.window
    # spans that overlap the traced window at all, on the trace's clock
    spans = [(s.t0_ns + offset, s.t1_ns + offset, s.name)
             for s in tel.spans()
             if s.t1_ns + offset > lo and s.t0_ns + offset < hi]
    if not spans:
        return None
    # the window in pieces that no span boundary cuts; longest span first,
    # so the innermost span over a piece claims it last
    edges = np.unique(np.clip(
        [lo, hi] + [t for a, b, _ in spans for t in (a, b)], lo, hi))
    middle = (edges[:-1] + edges[1:]) / 2
    spans.sort(key=lambda s: s[0] - s[1])
    owner = np.full(len(middle), -1)
    for i, (a, b, _) in enumerate(spans):
        owner[(middle >= a) & (middle <= b)] = i
    # idle nanoseconds before each edge: flat between the gaps, rising
    # one for one inside them
    gap_start, gap_end = program_spans.idle_gaps_ns(run.trace)
    at = np.ravel(np.column_stack((gap_start, gap_end)))
    before = np.ravel(np.column_stack((
        np.concatenate(([0.0], np.cumsum(gap_end - gap_start)[:-1])),
        np.cumsum(gap_end - gap_start))))
    idle = np.diff(np.interp(edges, at, before)) if len(at) else \
        np.zeros(len(middle))
    out = {}
    for i in np.unique(owner[idle > 0]):
        name = spans[i][2] if i >= 0 else "_no_span_"
        out[name] = out.get(name, 0.0) + float(idle[owner == i].sum()) / 1e9
    return out


def read(run, names):
    by_span = gaps_by_span(run)
    if by_span is None:
        return None
    idle = sum(v for k, v in by_span.items()
               if program_spans.matches(k, names))
    return 100.0 * idle / reduce.window_seconds(run.trace)
