"""Least seconds of the ragged paged-attention kernel over the traced
dispatches of a model of which only ``run.model["attn_layers"]`` layers
are attention (the others are state-space layers and run no such kernel):
``costs/ragged_paged_serve.py``'s reckoning (one layer's cost from
``roofline.ragged_paged_dispatch``: the model's heads and head size,
whatever rows the pools pack them into), times the attention layers where
that file's would take all of ``n_layers``."""

from chipbench import roofline


def least_seconds(run):
    return roofline.ragged_paged_serve_seconds(
        dict(run.model, n_layers=run.model["attn_layers"]),
        [d for s in run.traced_steps for d in s["dispatches"]], run.peaks)
