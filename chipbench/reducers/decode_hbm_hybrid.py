"""Share of the chip's memory bandwidth that the decode steps of a server
with state-space layers could not do without: the bytes a decode dispatch
must move whatever implements it, over the time a decode step takes (the
median of the program's ``serve/decode`` spans less a ``serve/prefill``
nested in one: what ``decode_ms.*`` reads) times the published bandwidth,
in %.

A dispatch must read every weight once (``n_params`` x 2 bytes), read and
write the recurrent state and the convolution's last inputs of the slots
it serves (the program's own ``state_bytes`` of the dispatch, from shapes
and dtypes), and read the keys and values of its live contexts in the
attention layers (the dispatch's ``contexts``).  Activations, logits and
the new K/V rows are left out: the share is a floor's.  It may not pass
100.

The median span and not the spans' sum: the serve loop runs one step
ahead, and a decode dispatch that a prefill is launched behind is waited
for by the PREFILL's fetch, so the ``serve/decode`` span of the step after
it is a launch alone (2-3 ms where a step is 32; about three spans in ten
under this cell's traffic, and over their sum the share read 80 where a
step's own time gives 61).  None where the program's dispatches carry no
``state_bytes`` (a program without such layers), or it keeps no span
ring."""

import statistics

from chipbench.reducers import program_spans


def dispatch_bytes(disp, m):
    """Bytes one decode dispatch must move."""
    kv = 2 * sum(disp["contexts"]) * m["kv_heads"] * m["head_dim"] \
        * m["kv_bytes"] * m["attn_layers"]
    return 2 * m["n_params"] + disp["state_bytes"] + kv


def read(run):
    m = run.model
    found = [d for s in run.steps for d in s["dispatches"]
             if d["phase"] == "decode" and "state_bytes" in d
             and "contexts" in d]
    spans = program_spans.window_spans(run)
    if not found or spans is None or "attn_layers" not in m:
        return None
    kids = program_spans.children_of(spans)
    steps = [s.t1_ns - s.t0_ns
             - program_spans.nested_ns(s, kids, ["serve/prefill"])
             for s in spans if s.name == "serve/decode"]
    if not steps or not statistics.median(steps):
        return None
    seconds = len(found) * statistics.median(steps) / 1e9
    return 100.0 * sum(dispatch_bytes(d, m) for d in found) / seconds \
        / run.chips / run.peaks["hbm_bytes_per_s"]
