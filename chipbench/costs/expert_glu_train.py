"""Least seconds of the grouped expert kernels of the traced optimizer
steps of a trainer: the forward (``grouped_expert_glu``) and the two of the
backward (``grouped_expert_glu_dx``, ``grouped_expert_glu_dw``).  Each
takes 3 products of 2 d f a REAL pair (the program's own count of the pairs
routed to the held experts: ``reducers/moe_train_gauges.py``; the gate and
up products the dx kernel runs again, and tile padding, are not work the
algorithm needs).  Bytes a call: every held expert's three leaves once
(all of them are chosen at a training batch), the rows in and out, and the
weights' gradients out in float32.  How often the forward runs for one
backward (once more under recomputation) is counted in the trace."""

from chipbench import reduce, roofline
from chipbench.reducers import moe_train_gauges

KERNEL = "grouped_expert_glu"


def call_costs(cfg, pairs, itemsize=2):
    """{kernel: (flops, bytes)} of one expert layer of one micro-batch
    that routed ``pairs`` pairs to the ``num_experts`` held."""
    d, f, held = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["num_experts"]
    flops = 3 * 2 * d * f * pairs
    leaves = 3 * d * f * min(held, pairs)
    wide, narrow = pairs * d * itemsize, pairs * f * itemsize
    return {"fwd": (flops, leaves * itemsize + 2 * wide),
            "dx": (flops, leaves * itemsize + 3 * wide + 3 * narrow),
            "dw": (flops, 2 * wide + 3 * narrow + leaves * 4)}


def least_seconds(run):
    found = moe_train_gauges.gauges()
    backward = reduce.op_count(run.trace, "pallas", KERNEL + "_dx")
    if found is None or not backward:
        return 0.0
    calls = run.model["n_layers"] * run.model["gas"]
    cost = call_costs(run.config, found["expert_pairs"] / calls)
    every = reduce.op_count(run.trace, "pallas", KERNEL)
    forward = every - backward \
        - reduce.op_count(run.trace, "pallas", KERNEL + "_dw")
    seconds = {k: roofline.bound_seconds(*v, run.peaks)[0]
               for k, v in cost.items()}
    per_call = round(forward / backward) * seconds["fwd"] \
        + seconds["dx"] + seconds["dw"]
    return len(run.traced_steps) * calls * per_call
