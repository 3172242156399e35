"""Model FLOP/s utilisation of a latent-attention, sparse-attention,
routed-expert server: the matrix products THE MODEL needs for the tokens
the window's dispatches brought (whatever an implementation does to get
them), per second of the window's steps, over the chip's bf16 peak.

A token at context ``c`` (keys up to and including itself) needs, a layer:
the attention projections (query pair, joint latent, per-head
decompression once, output) and the indexer's; the main attention over
``min(c, index_topk)`` keys; the index scores over all ``c`` keys; then a
dense SwiGLU, or the router over all published experts, the shared expert
and the (token, expert) pairs computed HERE (the program's own count
over its real tokens, ``expert_pairs``).  The head is one row a prefill
and one a decoded token.  Sizes from the family's ``model_sizes``.

Over the window's whole steps, less the two in which the profiler of a
traced run starts and stops: ``serve_cell`` ticks its tracer inside the
iteration, and a stop that writes seconds of trace is no step's work (two
traced runs of one seed read 8.0 and 6.2 with it in, PR 32).  None without
dispatches."""


def _attended(n, k):
    """Keys attended by tokens at contexts 1..n under a top-k of k."""
    m = min(n, k)
    return m * (m + 1) // 2 + max(n - k, 0) * k


def _steady(steps):
    """The steps whose iteration did not switch the profiler: a step's
    ``traced`` says whether it was on AFTER the iteration's tick."""
    before = [False] + [s.get("traced", False) for s in steps[:-1]]
    return [s for s, was in zip(steps, before)
            if s.get("traced", False) == was]


def read(run):
    m = run.model
    steps = _steady(run.steps)
    if "kv_rank" not in m or not steps:
        return None
    d, H, L = m["hidden"], m["heads"], m["n_layers"]
    qk = m["nope"] + m["rope"]
    proj = d * m["q_rank"] + m["q_rank"] * H * qk \
        + d * (m["kv_rank"] + m["rope"]) \
        + m["kv_rank"] * H * (m["nope"] + m["v_dim"]) + H * m["v_dim"] * d \
        + m["q_rank"] * m["index_heads"] * m["index_dim"] \
        + d * m["index_dim"] + d * m["index_heads"]
    per_key = H * (qk + m["v_dim"])             # a key attended, a layer
    per_index_key = m["index_heads"] * m["index_dim"]
    expert = 3 * d * m["expert_ffn"]
    per_token = L * proj + m["dense_layers"] * 3 * d * m["dense_ffn"] \
        + m["expert_layers"] * (d * m["experts_published"]
                                + m["shared_experts"] * expert)
    flops = 0.0
    for step in steps:
        for disp in step["dispatches"]:
            if disp["phase"] == "prefill" and "real" in disp:
                n = disp["real"]
                attended = _attended(n, m["index_topk"])
                context = n * (n + 1) // 2
                heads_rows = 1
            elif disp["phase"] == "decode" and "contexts" in disp:
                n = len(disp["contexts"])
                attended = sum(min(c, m["index_topk"])
                               for c in disp["contexts"])
                context = sum(disp["contexts"])
                heads_rows = n
            else:
                continue
            flops += 2.0 * (n * per_token + L * attended * per_key
                            + L * context * per_index_key
                            + disp.get("expert_pairs", 0) * expert
                            + heads_rows * d * m["vocab"])
    busy = sum(s["t1"] - s["t0"] for s in steps)
    if not busy or not flops:
        return None
    return 100.0 * flops / busy / run.chips / run.peaks["bf16_flops_per_s"]
