"""Milliseconds of the program's ``name`` spans per 1024 tokens of their
``tokens_attr`` attribute, summed over the window: for ``serve/prefill``
and ``bucket``, what a thousand dispatched prompt positions cost, padding
included."""

from chipbench.reducers import program_spans


def read(run, name, tokens_attr):
    spans = program_spans.window_spans(run)
    if spans is None:
        return None
    found = [s for s in spans if s.name == name and s.attrs
             and tokens_attr in s.attrs]
    tokens = sum(s.attrs[tokens_attr] for s in found)
    if not tokens:
        return None
    return sum(s.t1_ns - s.t0_ns for s in found) / 1e6 / (tokens / 1024.0)
