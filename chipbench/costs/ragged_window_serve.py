"""Least seconds of the ragged paged-attention kernel over the traced
dispatches of a model whose layers are sliding-window or full attention
(``run.model``: ``window_layers``, ``full_layers``, ``window``), all
layers: the same work whatever implements it.

A layer of either kind, a call: the causal-and-windowed products (a query
at context ``c`` meets ``min(c, window)`` keys in a window layer, ``c`` in
a full one; QK^T and PV), or the bytes of the pages that hold those keys
(a decode step: the pages from the one with the oldest key inside the
window to the one with the newest; a prefill from an empty context: every
page of the prompt, once) plus queries in and results out, whichever
takes the chip longer."""

from chipbench import roofline


def _attended(n, window):
    """Keys met by the queries at contexts 1..n under ``window``."""
    m = min(n, window)
    return m * (m + 1) // 2 + max(n - window, 0) * window


def call(new, contexts, model, window=None):
    """(flops, bytes) of one layer's call: ``new`` queries a sequence at
    the end of each of ``contexts`` (a prefill: one context, ``new`` of
    it)."""
    heads, kv_heads = model["heads"], model["kv_heads"]
    head_dim, page = model["head_dim"], model["page_size"]
    item = model["kv_bytes"]
    limit = window or max(contexts, default=0) + 1
    flops = nbytes = 0
    for context in contexts:
        rows = min(new, context)
        pairs = _attended(context, limit) - _attended(context - rows, limit)
        flops += 4 * pairs * heads * head_dim
        oldest = max(context - rows + 1 - limit, 0)
        pages = (context - 1) // page - oldest // page + 1
        nbytes += 2 * pages * page * kv_heads * head_dim * item
        nbytes += 2 * rows * heads * head_dim * item
    return flops, nbytes


def least_seconds(run):
    model, total = run.model, 0.0
    for step in run.traced_steps:
        for d in step["dispatches"]:
            if d["phase"] == "prefill" and "real" in d:
                new, contexts = d["real"], [d["context"]]
            elif d["phase"] == "decode" and "contexts" in d:
                new, contexts = 1, d["contexts"]
            else:
                continue
            for layers, window in ((model["full_layers"], None),
                                   (model["window_layers"],
                                    model["window"])):
                total += layers * roofline.bound_seconds(
                    *call(new, contexts, model, window), run.peaks)[0]
    return total
