#!/usr/bin/env python3
"""A control by hand for a serving cell of a model with a dropless expert
layer, beside ``chipbench/control_in_place.py`` (8-bit weights) and
``scripts/state_control.py`` (a lost state): the cell's own check
(``chipbench/serve_cell.py``: three prompts at the mix's quantiles, prefill
and 24 decoded tokens, against the float32 reference at ``LOGIT_TOL``) of
the program serving weights whose HELD EXPERTS' term is left out (their
``w_down`` zeroed in place; the router, the shared SwiGLU and the mixers as
seeded), held against the reference on the weights as seeded.  The check
has to come out NOT ok; if it does not, the cell's ``correct`` cannot see
the routed half of the feed-forward on this model (PERF.md section 4 keeps
the readings).

``--lose none`` serves the weights as seeded: the sound run, through the
same path.  Where the family's reference has ``logits_and_margins`` the
line also carries ``rows``: each compared row's own-token routing margin
beside its error over the prompt's scale, which is what the reference's
``MARGIN`` is set from (the rows a wider or narrower margin would leave
and their largest error can be read off one run).

    python3 scripts/expert_control.py --workload <cell> --seeds <n> [<n> ...]
        [--lose experts none]

One JSON line a seed and mode; exit code 1 if a control passed the check
or a sound run failed it.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _without_held_experts(params):
    """``params`` with every dropless expert layer's held ``w_down``
    zeroed, in place (the chip has no room for a second copy)."""
    import jax

    def lose(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        return leaf * 0 if keys[-2:] == ["moe", "w_down"] else leaf

    return jax.jit(lambda tree: jax.tree_util.tree_map_with_path(lose, tree),
                   donate_argnums=0)(params)


def _rows(cell, served, params):
    """[margin, error over the prompt's scale] of every compared row, and
    the largest reference logit of any."""
    import jax.numpy as jnp
    import numpy as np
    rows, largest = [], 0.0
    for ids, got in served:
        want, margin = cell.reference.logits_and_margins(
            params, jnp.asarray(ids), cell.config, last=len(got))
        want, margin = np.asarray(want)[0], np.asarray(margin)[0]
        top = float(np.max(np.abs(want)))
        largest = max(largest, top)
        rows += [[round(float(m), 5), round(float(e) / max(1.0, top), 5)]
                 for m, e in zip(margin, np.abs(got - want).max(-1))]
    return rows, largest


def control_error(cell, seed, devices, lose):
    import deepspeed_tpu
    from chipbench import serve_cell, sut

    cfg = cell.config
    model = sut.build_model(cell)
    dtype = cfg["serve"]["dtype"]
    params = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)
    if lose == "experts":
        params = _without_held_experts(params)
    engine = deepspeed_tpu.init_inference(
        model=model, params=params, dtype=dtype).create_serving_engine(
        max_batch=int(cell.mix["max_batch"]), **cfg["serve"]["engine"])
    served = serve_cell._serve_check_prompts(cell, engine, seed)
    del engine
    if lose == "experts":
        del params
        gc.collect()
        params = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)
    gc.collect()
    check = serve_cell._compare_with_reference(cell, served, params)
    if hasattr(cell.reference, "logits_and_margins"):
        check["rows"], check["largest_reference_logit"] = _rows(
            cell, served, params)
    return check


def main(argv=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--lose", nargs="+", default=["experts"],
                    choices=["experts", "none"])
    args = ap.parse_args(argv)
    from chipbench import cells, device, serve_cell
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = cells.load_cell(args.workload)
    devices = device.require_devices(cell.chips, require_tpu)
    wrong = False
    for seed in args.seeds:
        for lose in args.lose:
            check = control_error(cell, seed, devices, lose)
            wrong = wrong or check["ok"] == (lose != "none")
            print(json.dumps(dict(check, seed=seed, lost=lose,
                                  logit_tol=serve_cell.LOGIT_TOL)),
                  flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
