"""Model FLOP/s utilisation of a server whose layers are sliding-window or
full attention with a share of routed experts: the matrix products THE
MODEL needs for the tokens the window's dispatches brought (whatever an
implementation does to get them), per second of the window's steps, over
the chip's bf16 peak.

A token at context ``c`` (keys up to and including itself) needs, a layer:
the attention projections (query, key, value, gate, output); attention
over ``min(c, window)`` keys in a window layer and ``c`` in a full one
(QK^T and PV); then a dense SwiGLU, or the router over all published
experts, the shared expert and the (token, expert) pairs computed HERE
(the program's own count over its real tokens, ``expert_pairs``).  The
head is one row a prefill and one a decoded token.  Sizes from the
family's ``model_sizes``; the same steady steps as ``serve_mfu_pct``.
None without dispatches or for a model of another shape."""

from chipbench.costs.ragged_window_serve import _attended
from chipbench.reducers.serve_mfu_pct import _steady


def read(run):
    m = run.model
    steps = _steady(run.steps)
    if "window_layers" not in m or not steps:
        return None
    d, H, D = m["hidden"], m["heads"], m["head_dim"]
    proj = 3 * d * H * D + 2 * d * m["kv_heads"] * D
    per_key = 2 * H * D                         # a key attended, a layer
    expert = 3 * d * m["expert_ffn"]
    per_token = m["n_layers"] * proj \
        + m["dense_layers"] * 3 * d * m["dense_ffn"] \
        + m["expert_layers"] * (d * m["experts_published"]
                                + m["shared_experts"] * expert)
    flops = 0.0
    for step in steps:
        for disp in step["dispatches"]:
            if disp["phase"] == "prefill" and "real" in disp:
                n, head_rows = disp["real"], 1
                end, start = disp["context"], disp["context"] - disp["real"]
                window_keys = _attended(end, m["window"]) \
                    - _attended(start, m["window"])
                full_keys = (end * (end + 1) - start * (start + 1)) // 2
            elif disp["phase"] == "decode" and "contexts" in disp:
                n = head_rows = len(disp["contexts"])
                window_keys = sum(min(c, m["window"])
                                  for c in disp["contexts"])
                full_keys = sum(disp["contexts"])
            else:
                continue
            flops += 2.0 * (n * per_token
                            + m["window_layers"] * window_keys * per_key
                            + m["full_layers"] * full_keys * per_key
                            + disp.get("expert_pairs", 0) * expert
                            + head_rows * d * m["vocab"])
    busy = sum(s["t1"] - s["t0"] for s in steps)
    if not busy or not flops:
        return None
    return 100.0 * flops / busy / run.chips / run.peaks["bf16_flops_per_s"]
