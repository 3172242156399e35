#!/usr/bin/env python3
"""The control of a serving cell's ``correct``: the comparison has to FAIL
a computation in the nearest precision below the one the configuration
states.  The cells serve in bfloat16, so the control is 8-bit floats, and
the program has no such serving path of its own (its int8 weights do not
reach ``create_serving_engine``).  So the control is the program itself,
``ServingEngine`` with the cell's engine settings, serving weights rounded
to 3 bits of mantissa (float8 e4m3 with its exponent left free, as a
scaled 8-bit path would have it: 16 times bf16's rounding) through the
compared path: ``serve_cell``'s own check prompts, prefilled and decoded
through the paged cache, held against the float32 reference on the
weights as seeded.  It is a simulation of the lower precision by its
weights alone: activations and the cache stay bfloat16.  The rounding is
done on the bits: a cast to ``float8_e4m3fn`` and back is folded away by
the TPU's compiler (a v5e has no such type; such a control read 0.0 on the
chip, PR 26).

    python3 chipbench/control.py --workload <name> --seeds <n> [<n> ...]

prints, a seed, the control's largest row error beside ``LOGIT_TOL``, the
number ``serve_cell`` holds the program's rows to; the last line is one
JSON object.  Exit code 0 when every seed's control fails the tolerance
(the comparison can tell the two precisions apart), 1 otherwise.  The
benchmark's own runs never run this; PERF.md keeps the readings.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def round_mantissa(w, bits):
    """``w`` rounded (to nearest, ties to even) to ``bits`` bits of
    mantissa, in its own type."""
    import jax
    import jax.numpy as jnp
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(w.astype(jnp.float32), jnp.uint32)
    u = (u + ((1 << (drop - 1)) - 1) + ((u >> drop) & 1)) >> drop << drop
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(w.dtype)


def control_error(cell, seed, devices):
    """``serve_cell``'s check (``logit_error``, ``rows_compared``, ...) of
    the program serving the rounded weights, against the reference on the
    weights as seeded.
    The engine and the rounded weights are freed before the reference
    runs, so the control peaks no higher than a run of the cell."""
    import gc
    import jax
    import deepspeed_tpu
    from chipbench import serve_cell, sut

    cfg = cell.config
    model = sut.build_model(cell)
    dtype = cfg["serve"]["dtype"]
    sound = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)
    low = jax.jit(lambda tree: jax.tree_util.tree_map(
        lambda w: round_mantissa(w, 3), tree))(sound)
    del sound
    engine = deepspeed_tpu.init_inference(
        model=model, params=low, dtype=dtype).create_serving_engine(
        max_batch=int(cell.mix["max_batch"]), **cfg["serve"]["engine"])
    served = serve_cell._serve_check_prompts(cell, engine, seed)
    del engine, low
    gc.collect()
    sound = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)
    return serve_cell._compare_with_reference(cell, served, sound)


def main(argv=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import cells, device, serve_cell
    cell = cells.load_cell(args.workload)
    # the compile cache as run.py keeps it: the cell's programs are there
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = device.require_devices(cell.chips, require_tpu)
    errors = {}
    for seed in args.seeds:
        check = control_error(cell, seed, devices)
        errors[str(seed)] = check["logit_error"]
        print(f"chipbench control: seed {seed} the program on 8-bit float "
              f"weights reads {check['logit_error']:.4f} over "
              f"{check['rows_compared']} rows (limit "
              f"{serve_cell.LOGIT_TOL})", flush=True)
    failed_all = all(e > serve_cell.LOGIT_TOL for e in errors.values())
    print(json.dumps({"workload": args.workload, "control": "the program "
                      "serving weights at 3 bits of mantissa",
                      "logit_tol": serve_cell.LOGIT_TOL,
                      "control_error": errors,
                      "control_fails_every_seed": failed_all}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
