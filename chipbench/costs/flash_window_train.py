"""Least seconds of the flash-attention kernels of the traced optimizer
steps of a trainer whose layers are sliding-window or full attention by
``layer_types``, with grouped queries: the causal products under each
layer kind's mask (a window layer's query meets at most ``sliding_window``
keys), ``num_attention_heads`` query heads over ``num_key_value_heads`` of
``head_dim`` from the CONFIGURATION (the train runner's
``run.model["head_dim"]`` is hidden / heads, which is not this family's).
Products a kernel as ``roofline.flash_attention_calls`` counts them: the
forward 2, dq 3, dk/dv 4.  How often the forward runs a layer and
micro-batch is counted in the trace."""

from chipbench import reduce, roofline
from chipbench.reducers.moe_train_mfu import attended_pairs

KERNEL = "flash_attention_"


def layer_costs(cfg, batch, seq, window, itemsize=2):
    """{kernel: (flops, bytes)} of one layer's calls on ``batch``
    sequences."""
    heads, kv, dh = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    product = 2 * batch * heads * dh * attended_pairs(seq, window)
    q = batch * seq * heads * dh * itemsize
    k = batch * seq * kv * dh * itemsize
    return {"fwd": (2 * product, 2 * q + 2 * k),
            "dq": (3 * product, 3 * q + 2 * k),
            "dkv": (4 * product, 2 * q + 2 * k + 2 * k * 4 // itemsize)}


def least_seconds(run):
    m, cfg = run.model, run.config
    per_step = reduce.op_count(run.trace, "pallas", KERNEL) \
        / len(run.traced_steps)
    calls = max(3, round(per_step / (m["gas"] * m["n_layers"])))
    total = 0.0
    for kind in cfg["layer_types"]:
        cost = layer_costs(cfg, m["micro_batch"], m["seq"],
                           cfg["sliding_window"]
                           if kind == "sliding_attention" else 0)
        seconds = {k: roofline.bound_seconds(*v, run.peaks)[0]
                   for k, v in cost.items()}
        total += (calls - 2) * seconds["fwd"] + seconds["dq"] \
            + seconds["dkv"]
    return len(run.traced_steps) * m["gas"] * total
