"""Milliseconds of the program's spans named ``name`` in the window, the
median over them; ``less`` lists name patterns (``serve/step``,
``*/fetch``) whose time, where nested inside such a span, is taken off
first.  ``serve/loop`` less its dispatches and fetches is the host's own
work in a serving step; ``serve/decode`` less the ``serve/prefill`` a
finished request lets in is the decode step alone."""

import statistics

from chipbench.reducers import program_spans


def read(run, name, less=()):
    spans = program_spans.window_spans(run)
    if spans is None:
        return None
    kids = program_spans.children_of(spans) if less else {}
    values = [(s.t1_ns - s.t0_ns
               - (program_spans.nested_ns(s, kids, less) if less else 0))
              for s in spans if s.name == name]
    return statistics.median(values) / 1e6 if values else None
