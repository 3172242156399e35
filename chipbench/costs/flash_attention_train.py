"""Least seconds of the flash-attention kernels of the traced optimizer
steps.  How often the forward kernel runs a layer and micro-batch (once,
or again in the backward pass under recomputation) is counted in the
trace."""

from chipbench import reduce, roofline


def least_seconds(run):
    m = run.model
    per_step = reduce.op_count(run.trace, "pallas") / len(run.traced_steps)
    calls = max(3, round(per_step / (m["gas"] * m["n_layers"])))
    return roofline.flash_attention_train_seconds(
        m, calls, len(run.traced_steps), run.peaks)
