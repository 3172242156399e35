"""Least seconds of the ragged paged-attention kernel over the traced
dispatches (the ``dispatches`` of the engine's step reports), all layers."""

from chipbench import roofline


def least_seconds(run):
    return roofline.ragged_paged_serve_seconds(
        run.model, [d for s in run.traced_steps for d in s["dispatches"]],
        run.peaks)
