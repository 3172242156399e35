#!/usr/bin/env python3
"""A control by hand for a serving cell of a model with block-sparse
attention, beside ``chipbench/control_in_place.py`` (8-bit weights) and
``scripts/state_control.py`` (a lost state): the cell's own check
(``chipbench/serve_cell.py``: three prompts at the mix's quantiles, prefill
and 24 decoded tokens, against the float32 reference at ``LOGIT_TOL``),
served by a program whose selection is the FORCED blocks alone (the first
``init_blocks`` and those of the last ``window`` keys; every other block
scores the least and is no candidate), prefill and decode alike.  The
check has to come out NOT ok; if it does not, the cell's ``correct`` cannot
see a selection of the wrong blocks on this model's seeded weights
(PERF.md section 4 keeps the readings; the CPU tests that hold the
selection are ``tests/unit/test_block_sparse_attention.py`` and
``test_sala_serving.py``).

    python3 scripts/selection_control.py --workload <cell> --seeds <n> [<n> ...]

One JSON line a seed, exit code 1 if any control passed the check.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def forced_blocks_alone():
    """Replace the program's ``select_blocks`` by one that keeps the
    forced blocks' scores (``+inf``) and nothing else."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops import block_sparse_attention as bsa
    select = bsa.select_blocks

    def forced(*args, **kwargs):
        score, valid = select(*args, **kwargs)
        kept = jnp.isinf(score) & (score > 0)
        return jnp.where(kept, score, -jnp.inf), valid & kept

    bsa.select_blocks = forced


def control_error(cell, seed, devices):
    import deepspeed_tpu
    from chipbench import serve_cell, sut

    cfg = cell.config
    model = sut.build_model(cell)
    dtype = cfg["serve"]["dtype"]
    params = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)
    engine = deepspeed_tpu.init_inference(
        model=model, params=params, dtype=dtype).create_serving_engine(
        max_batch=int(cell.mix["max_batch"]), **cfg["serve"]["engine"])
    check = serve_cell._check_against_reference(cell, engine, params, seed)
    del engine, params
    gc.collect()
    return check


def main(argv=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import cells, device, serve_cell
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = cells.load_cell(args.workload)
    devices = device.require_devices(cell.chips, require_tpu)
    forced_blocks_alone()
    passed = False
    for seed in args.seeds:
        check = control_error(cell, seed, devices)
        passed = passed or check["ok"]
        print(json.dumps(dict(check, seed=seed, lost="selection",
                              logit_tol=serve_cell.LOGIT_TOL)), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
