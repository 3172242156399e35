"""Model FLOP/s utilisation of a server whose layers are linear attention
(a matrix state a head) or block-sparse attention: the operations THE
MODEL needs for the tokens the window's dispatches brought (whatever an
implementation does to get them), per second of the window's steps, over
the chip's bf16 peak.

A real token needs two operations a weight of every layer it passes: a
linear layer's q, k, v, gate and output projections, a sparse layer's
query, key, value, gate and output projections, every layer's SwiGLU.  In
a sparse layer a query at position ``t`` of a request whose context is
``dense_len`` or more needs QK^T and PV over the keys of its SELECTED
blocks alone (all causal keys while the blocks number ``topk`` or fewer,
then ``topk - 1`` whole blocks and its own part-filled one), and its
heads' scores over the compressed keys it can see (one every ``stride``
tokens); under ``dense_len``, over every causal key and no compressed
one.  The products a masked prefill spends on blocks it then discards are
NOT credited.  In a linear layer the recurrence, ``6 x inner x head
width`` (the decay, the outer product added, the read through q).  The
head is one row a prefill and one a decoded token.  No padding, no idle
slot.  Sizes from the family's ``model_sizes``; the same steady steps as
``serve_mfu_pct``.  None without dispatches or for a model of another
shape."""

import numpy as np

from chipbench.reducers.serve_mfu_pct import _steady


def token_macs(m):
    """Multiply-adds a token of the weights, all layers."""
    d, wide = m["hidden"], m["heads"] * m["head_dim"]
    sparse = 3 * d * wide + 2 * d * m["kv_heads"] * m["head_dim"]
    return m["sparse_layers"] * sparse \
        + m["lin_layers"] * 5 * d * m["lin_inner"] \
        + m["n_layers"] * 3 * d * m["ffn"]


def attended(positions, context, m):
    """(keys attended, compressed keys scored) by the queries at
    ``positions`` (an array) of a request whose context is ``context``,
    one sparse layer."""
    t = np.asarray(positions, np.int64)
    if context < m["dense_len"]:
        return int(np.sum(t + 1)), 0
    kept = np.where(t // m["block"] < m["topk"], t + 1,
                    (m["topk"] - 1) * m["block"] + t % m["block"] + 1)
    seen = np.maximum((t + 1 - m["kernel"]) // m["stride"] + 1, 0)
    return int(np.sum(kept)), int(np.sum(seen))


def read(run):
    m = run.model
    steps = _steady(run.steps)
    if "lin_layers" not in m or not steps:
        return None
    per_key = 2 * m["heads"] * m["head_dim"]    # a key attended, a layer
    per_ckey = m["heads"] * m["head_dim"]       # a compressed key scored
    recurrence = 6 * m["lin_inner"] * m["lin_head_dim"]   # a token, a layer
    per_token = token_macs(m)
    flops = 0.0
    for step in steps:
        for disp in step["dispatches"]:
            if disp["phase"] == "prefill" and "real" in disp:
                n, head_rows = disp["real"], 1
                start = disp["context"] - disp["real"]
                keys, ckeys = attended(np.arange(start, disp["context"]),
                                       disp["context"], m)
            elif disp["phase"] == "decode" and "contexts" in disp:
                n = head_rows = len(disp["contexts"])
                rows = [attended([c - 1], c, m) for c in disp["contexts"]]
                keys = sum(kept for kept, _ in rows)
                ckeys = sum(seen for _, seen in rows)
            else:
                continue
            flops += 2.0 * (n * per_token + m["sparse_layers"]
                            * (keys * per_key + ckeys * per_ckey)
                            + head_rows * m["hidden"] * m["vocab"]) \
                + n * m["lin_layers"] * recurrence
    busy = sum(s["t1"] - s["t0"] for s in steps)
    if not busy or not flops:
        return None
    return 100.0 * flops / busy / run.chips / run.peaks["bf16_flops_per_s"]
