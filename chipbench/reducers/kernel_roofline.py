"""A Pallas kernel's share of its roofline: the least seconds the chip
could take for the traced calls (``chipbench/roofline.py``, from shapes
and the published peaks) over the kernel's seconds in the device trace."""

from chipbench import reduce, roofline


def read(run, cost):
    if run.trace is None or not run.trace.ops or not run.traced_steps:
        return None
    kernel_s = sum(reduce.op_seconds(run.trace, "pallas").values())
    if not kernel_s:
        return None
    if cost == "flash_attention_train":
        m = run.model
        per_step = reduce.op_count(run.trace, "pallas") / len(run.traced_steps)
        calls = max(3, round(per_step / (m["gas"] * m["n_layers"])))
        least = roofline.flash_attention_train_seconds(
            m, calls, len(run.traced_steps), run.peaks)
    elif cost == "ragged_paged_serve":
        least = roofline.ragged_paged_serve_seconds(
            run.model, [d for s in run.traced_steps
                        for d in s["dispatches"]], run.peaks)
    else:
        raise ValueError(f"unknown cost function {cost!r}")
    return 100.0 * least / kernel_s
