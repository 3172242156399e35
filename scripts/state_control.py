#!/usr/bin/env python3
"""A control by hand for a serving cell of a model with state-space or
linear-attention layers (``--lose state`` serves both: the matrix state of
a linear layer is the ``state`` leaf of its cache too), beside ``chipbench/control_in_place.py`` (8-bit weights): the
cell's own check (``chipbench/serve_cell.py``: three prompts at the mix's
quantiles, prefill and 24 decoded tokens, against the float32 reference at
``LOGIT_TOL``), except that the slots' recurrent STATE (``--lose state``),
or the convolution's last inputs (``--lose conv``), is zeroed once the
prompts are prefilled and before the first decode step.  The check has to
come out NOT ok; if it does not, the cell's ``correct`` cannot see a lost
state on this model (PERF.md section 4 keeps the readings).

    python3 scripts/state_control.py --workload <cell> --seeds <n> [<n> ...]

One JSON line a seed and loss, exit code 1 if any control passed the check.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control_error(cell, seed, devices, lose):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from chipbench import serve_cell, sut

    cfg = cell.config
    model = sut.build_model(cell)
    dtype = cfg["serve"]["dtype"]
    params = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)
    engine = deepspeed_tpu.init_inference(
        model=model, params=params, dtype=dtype).create_serving_engine(
        max_batch=int(cell.mix["max_batch"]), **cfg["serve"]["engine"])
    step, forgot = engine.step, []
    # x * 0 and not zeros_like(x): a donated buffer is reused only by a
    # program that reads it
    zero = jax.jit(lambda x: x * 0, donate_argnums=0)

    def forgetful_step():
        # the check's prompts are prefilled inline by ``add_request``: by
        # the first ``step()`` every one of them holds its prompt's state
        if not forgot:
            # in place: the chip has no room for a second pool of state
            ssm = engine.caches.ssm
            engine.caches = engine.caches._replace(ssm=ssm._replace(
                **{lose: zero(getattr(ssm, lose))}))
            forgot.append(True)
        return step()

    engine.step = forgetful_step
    check = serve_cell._check_against_reference(cell, engine, params, seed)
    del engine, params
    gc.collect()
    return check


def main(argv=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--lose", nargs="+", default=["state"],
                    choices=["state", "conv"])
    args = ap.parse_args(argv)
    from chipbench import cells, device, serve_cell
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = cells.load_cell(args.workload)
    devices = device.require_devices(cell.chips, require_tpu)
    passed = False
    for seed in args.seeds:
        for lose in args.lose:
            check = control_error(cell, seed, devices, lose)
            passed = passed or check["ok"]
            print(json.dumps(dict(check, seed=seed, lost=lose,
                                  logit_tol=serve_cell.LOGIT_TOL)),
                  flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
