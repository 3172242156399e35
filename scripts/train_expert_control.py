#!/usr/bin/env python3
"""A control by hand for a training cell with expert layers, as
``chipbench/control.py`` is for the serving cells: the cell's own run
(``chipbench/run.py``), except that the ENGINE gets the seeded expert
leaves (``moe.w_gate | w_up | w_down``) rounded to 8-bit floats
(``float8_e4m3fn``) while the float32 reference keeps them as seeded.  The
run has to come out NOT correct by ``loss_rel_error``; if it does not, the
training ``correct`` cannot see a fault of that size in the experts
(PERF.md section 7).

    python3 scripts/train_expert_control.py --workload <cell> --seed <n>
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LEAVES = ("w_gate", "w_up", "w_down")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from chipbench import run

    initialize = deepspeed_tpu.initialize

    def coarse(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        if "moe" in names and names[-1] in LEAVES:
            return leaf.astype(jnp.float8_e4m3fn).astype(leaf.dtype)
        return leaf

    def with_coarse_experts(model_parameters=None, **kwargs):
        return initialize(model_parameters=jax.tree_util.tree_map_with_path(
            coarse, model_parameters), **kwargs)

    deepspeed_tpu.initialize = with_coarse_experts
    run.main(["--workload", args.workload, "--seed", str(args.seed),
              "--seconds", str(args.seconds), "--trace", "0"])


if __name__ == "__main__":
    main()
