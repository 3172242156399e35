"""Model FLOP/s utilisation of a server whose layers are state-space
(Mamba-2) or attention: the operations THE MODEL needs for the tokens the
window's dispatches brought (whatever an implementation does to get them),
per second of the window's steps, over the chip's bf16 peak.

A real token needs two operations a weight of every layer it passes: a
state-space layer's in and out projections and its convolution, an
attention layer's query, key, value and output projections, every layer's
SwiGLU; in an attention layer QK^T and PV over the ``c`` keys of its
context; in a state-space layer the recurrence, ``6 x inner x state`` (the
decay, the outer product added, the read through C); and the head, one row
a prefill and one a decoded token.  No padding, no idle slot.  Sizes from
the family's ``model_sizes``; the same steady steps as ``serve_mfu_pct``.
None without dispatches or for a model of another shape."""

from chipbench.reducers.serve_mfu_pct import _steady


def token_macs(m):
    """Multiply-adds a token of the weights, all layers."""
    d = m["hidden"]
    attention = 2 * d * m["heads"] * m["head_dim"] \
        + 2 * d * m["kv_heads"] * m["head_dim"]
    mixer = d * (m["ssm_inner"] + m["ssm_conv_dim"] + m["ssm_heads"]) \
        + m["ssm_inner"] * d + m["ssm_conv"] * m["ssm_conv_dim"]
    return m["attn_layers"] * attention + m["ssm_layers"] * mixer \
        + m["n_layers"] * 3 * d * m["ffn"]


def read(run):
    m = run.model
    steps = _steady(run.steps)
    if "ssm_layers" not in m or not steps:
        return None
    per_key = 2 * m["heads"] * m["head_dim"]    # a key attended, a layer
    recurrence = 6 * m["ssm_inner"] * m["ssm_state"]    # a token, a layer
    per_token = token_macs(m)
    flops = 0.0
    for step in steps:
        for disp in step["dispatches"]:
            if disp["phase"] == "prefill" and "real" in disp:
                n, head_rows = disp["real"], 1
                end, start = disp["context"], disp["context"] - disp["real"]
                keys = (end * (end + 1) - start * (start + 1)) // 2
            elif disp["phase"] == "decode" and "contexts" in disp:
                n = head_rows = len(disp["contexts"])
                keys = sum(disp["contexts"])
            else:
                continue
            flops += 2.0 * (n * per_token + m["attn_layers"] * keys * per_key
                            + head_rows * m["hidden"] * m["vocab"]) \
                + n * m["ssm_layers"] * recurrence
    busy = sum(s["t1"] - s["t0"] for s in steps)
    if not busy or not flops:
        return None
    return 100.0 * flops / busy / run.chips / run.peaks["bf16_flops_per_s"]
