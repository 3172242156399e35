"""A kernel cost a later PR might bring: the least seconds of the routed
experts' three matrix products over the window's steps.  Widths come from
the configuration file (``run.config``), what is held and chosen from the
family's ``model_sizes`` (``run.model``)."""

from chipbench import roofline


def least_seconds(run):
    d = run.config["hidden_size"]
    f = run.config["moe_intermediate_size"]
    tokens = sum(s["tokens"] for s in run.steps)
    flops = 2 * 3 * d * f * tokens * run.model["experts_per_token"]
    weights = 2 * 3 * d * f * run.model["experts_held"] * len(run.steps)
    return run.model["n_layers"] * roofline.bound_seconds(
        flops, weights, run.peaks)[0]
