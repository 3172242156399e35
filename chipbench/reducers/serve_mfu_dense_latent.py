"""Model FLOP/s utilisation of a server whose attention is latent and
DENSE (no key selection: DeepSeek-V3, Kimi-K2) with a share of routed
experts: the matrix products THE MODEL needs for the tokens the window's
dispatches brought (whatever an implementation does to get them: absorbed
or decompressed, chunked or whole), per second of the window's steps, over
the chip's bf16 peak.

A token at context ``c`` (keys up to and including itself) needs, a layer:
the five attention projections (query pair, joint latent, per-head
decompression of its own entry once, output); attention over ALL ``c``
keys at ``nope + rope`` a head for the scores and ``v_dim`` for the
values; then a dense SwiGLU, or the router over all published experts, the
shared expert and the (token, expert) pairs computed HERE (the program's
own count over its real tokens, ``expert_pairs``).  A prefill dispatch is
a chunk: its ``real`` tokens end at ``context``, so they meet the keys of
every earlier chunk too.  The head is one row a sampled prefill and one a
decoded token.  A chunk that nothing is sampled from (``head_rows`` 0:
every chunk of a prompt but its last) needs its tokens' cache entries and
no output: the last layer's joint latent projection, which its cache write
reads, and nothing else of that layer (no query, attention, output
projection or feed-forward; of the program's ``expert_pairs``, counted
over all expert layers, the last layer's share is left out).  Sizes from
the family's ``model_sizes``; the same steady steps as ``serve_mfu_pct``.
None without dispatches or for a model of another shape."""

from chipbench.reducers.serve_mfu_pct import _steady


def read(run):
    m = run.model
    steps = _steady(run.steps)
    if not m.get("dense_attention") or "kv_rank" not in m or not steps:
        return None
    d, H, L = m["hidden"], m["heads"], m["n_layers"]
    qk = m["nope"] + m["rope"]
    proj = d * m["q_rank"] + m["q_rank"] * H * qk \
        + d * (m["kv_rank"] + m["rope"]) \
        + m["kv_rank"] * H * (m["nope"] + m["v_dim"]) + H * m["v_dim"] * d
    per_key = H * (qk + m["v_dim"])             # a key attended, a layer
    expert = 3 * d * m["expert_ffn"]
    per_token = L * proj + m["dense_layers"] * 3 * d * m["dense_ffn"] \
        + m["expert_layers"] * (d * m["experts_published"]
                                + m["shared_experts"] * expert)
    # what the last layer costs a token beyond the entry it caches
    routed_last = m["expert_layers"] > 0
    last_layer = proj - d * (m["kv_rank"] + m["rope"]) + (
        d * m["experts_published"] + m["shared_experts"] * expert
        if routed_last else 3 * d * m["dense_ffn"])
    flops = 0.0
    for step in steps:
        for disp in step["dispatches"]:
            pairs, layers, token = disp.get("expert_pairs", 0), L, per_token
            if disp["phase"] == "prefill" and "real" in disp:
                n, head_rows = disp["real"], disp.get("head_rows", 1)
                end, start = disp["context"], disp["context"] - disp["real"]
                keys = (end * (end + 1) - start * (start + 1)) // 2
                if not head_rows:       # entries only: see above
                    layers, token = L - 1, per_token - last_layer
                    if routed_last:
                        pairs *= 1.0 - 1.0 / m["expert_layers"]
            elif disp["phase"] == "decode" and "contexts" in disp:
                n = head_rows = len(disp["contexts"])
                keys = sum(disp["contexts"])
            else:
                continue
            flops += 2.0 * (n * token + layers * keys * per_key
                            + pairs * expert + head_rows * d * m["vocab"])
    busy = sum(s["t1"] - s["t0"] for s in steps)
    if not busy or not flops:
        return None
    return 100.0 * flops / busy / run.chips / run.peaks["bf16_flops_per_s"]
