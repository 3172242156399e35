"""Share of the chip's memory bandwidth that the decode steps of a server
with linear-attention and block-sparse attention layers could not do
without: the bytes a decode dispatch must move whatever implements it,
over the time a decode step takes (the median of the program's
``serve/decode`` spans less a ``serve/prefill`` nested in one: what
``decode_ms.*`` reads, and ``decode_hbm_hybrid``'s reason for the median)
times the published bandwidth, in %.

A dispatch must read every weight once (``n_params`` x 2 bytes), read and
write the matrix state of the slots it serves (the program's own
``state_bytes`` of the dispatch, from shapes and dtypes), and in the
sparse layers read the keys and values its queries ATTENDED (the
program's own ``selected`` of the dispatch, summed over rows and sparse
layers: the selected blocks' keys up to the query, or a whole context
under ``dense_len``) and the compressed keys of its contexts (one every
``stride`` tokens and key/value head).  Activations, logits, the new K/V
rows and the part of a selected block past the query are left out: the
share is a floor's.  It may not pass 100.  None where the program's
dispatches carry no ``state_bytes`` or no ``selected`` (a program without
such layers), or it keeps no span ring."""

import statistics

from chipbench.reducers import program_spans


def dispatch_bytes(disp, m):
    """Bytes one decode dispatch must move."""
    row = m["kv_heads"] * m["head_dim"] * m["kv_bytes"]
    compressed = sum(c // m["stride"] for c in disp["contexts"]
                     if c >= m["dense_len"]) * m["sparse_layers"]
    return 2 * m["n_params"] + disp["state_bytes"] \
        + (2 * disp["selected"] + compressed) * row


def read(run):
    m = run.model
    found = [d for s in run.steps for d in s["dispatches"]
             if d["phase"] == "decode" and "state_bytes" in d
             and "selected" in d and "contexts" in d]
    spans = program_spans.window_spans(run)
    if not found or spans is None or "lin_layers" not in m:
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
