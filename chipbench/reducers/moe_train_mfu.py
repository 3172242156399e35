"""Model FLOP/s utilisation of a trainer whose layers are window / full
attention over a dropless expert layer of which the chip holds a share: the
operations the MODEL needs for the tokens trained, over the window's busy
seconds and the chip's bf16 peak.  6 a parameter and token for the
projections, the router and the head (forward 2, backward 4); 6 x 3 d f a
(token, expert) PAIR actually routed to a held expert, from the program's
own count (``reducers/moe_train_gauges.py``: ``mfu_pct.train``'s 6 N would
count every held expert for every token, four times too many here); the
attention products under each layer kind's mask, 12 H D a (query, key)
pair.  No recomputation and no tile padding is counted.  None where the
program sets no gauges."""

from chipbench.reducers import moe_train_gauges


def attended_pairs(seq, window):
    """(query, key) pairs of one causal sequence under a sliding window
    (0: every earlier key)."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def step_flops(cfg, seq, sequences, pairs):
    """Operations of one optimizer step over ``sequences`` sequences of
    ``seq`` tokens that routed ``pairs`` pairs to the held experts."""
    d, heads, dh = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["head_dim"]
    kv = cfg["num_key_value_heads"]
    layers = cfg["num_hidden_layers"]
    routed = cfg.get("published", {}).get("num_experts", cfg["num_experts"])
    per_token = layers * (2 * d * heads * dh + 2 * d * kv * dh + d * routed) \
        + d * cfg["vocab_size"]
    attended = sum(
        attended_pairs(seq, cfg["sliding_window"]
                       if kind == "sliding_attention" else 0)
        for kind in cfg["layer_types"])
    return 6 * per_token * seq * sequences \
        + 6 * 3 * d * cfg["moe_intermediate_size"] * pairs \
        + 12 * heads * dh * attended * sequences


def read(run):
    found = moe_train_gauges.gauges()
    busy = sum(s["t1"] - s["t0"] for s in run.steps)
    if found is None or not busy:
        return None
    m = run.model
    flops = step_flops(run.config, m["seq"], m["gas"] * m["micro_batch"],
                       found["expert_pairs"])
    return 100.0 * flops * len(run.steps) / busy \
        / run.peaks["bf16_flops_per_s"]
