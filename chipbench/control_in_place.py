#!/usr/bin/env python3
"""``chipbench/control.py`` for a cell whose weights fill over half the
chip: the same control (the program serving weights rounded to 3 bits of
mantissa through ``serve_cell``'s own check prompts, held against the
float32 reference on the weights as seeded), with the rounding done IN
PLACE.  ``control.control_error`` makes the rounded weights while the sound
ones are alive; ``trinity-mini-ep8`` at the model's whole depth holds 8.55
GB of them on a 16 GB chip (the 16 layers it is cut to hold 4.23: this is
the control that was read on the chip at both depths, PERF.md section 7).
Here the seeded weights are donated to the rounding, so the chip never
holds two copies.

    python3 chipbench/control_in_place.py --workload <name> --seeds <n> [<n> ...]

Same lines, same last JSON object and same exit code as ``control.py``,
whose ``main`` and ``round_mantissa`` it runs (PERF.md keeps the
readings).
"""

import gc
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import control   # noqa: E402


def control_error(cell, seed, devices):
    """``control.control_error`` with one copy of the weights on the chip
    at any time."""
    import jax
    import deepspeed_tpu
    from chipbench import serve_cell, sut

    cfg = cell.config
    model = sut.build_model(cell)
    dtype = cfg["serve"]["dtype"]
    low = jax.jit(lambda tree: jax.tree_util.tree_map(
        lambda w: control.round_mantissa(w, 3), tree), donate_argnums=0)(
        sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices))
    engine = deepspeed_tpu.init_inference(
        model=model, params=low, dtype=dtype).create_serving_engine(
        max_batch=int(cell.mix["max_batch"]), **cfg["serve"]["engine"])
    served = serve_cell._serve_check_prompts(cell, engine, seed)
    del engine, low
    gc.collect()
    sound = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)
    return serve_cell._compare_with_reference(cell, served, sound)


def main(argv=None, require_tpu=True):
    control.control_error = control_error
    return control.main(argv, require_tpu)


if __name__ == "__main__":
    sys.exit(main())
