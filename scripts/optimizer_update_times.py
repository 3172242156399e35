#!/usr/bin/env python3
"""Time of the optimizer update of one leaf, alone, on the chip.

    chiprun -- python scripts/optimizer_update_times.py
    chiprun -- python scripts/optimizer_update_times.py --shapes 16x2048x8192 \
        --noise engine threefry nearest

One JSON line a leaf shape and source of the rounding's noise: the
picoseconds a parameter that the engine's update of that leaf takes (what
``runtime/engine.py _apply_update`` runs under the ``optimizer`` scope: the
float32 norm of the gradients, ``tx.update`` of a bf16-moment AdamW,
``apply_updates`` and the overflow pick, constant as in an engine without
fp16; jitted with the state donated, host
clock over ``--iters`` calls that end in ``block_until_ready``), beside the
compiler's own account of the program: bytes and operations a parameter
(``cost_analysis()``) and the number of fusions.  18 bytes a parameter is
22 ps at the v5e's 819 GB/s.

The sources of noise: ``engine`` is the tree's own (``optimizers.py
_rounding_noise``), ``threefry`` two 16-bit ``jax.random.bits`` draws a leaf
(what the engine did until PR 55: 50 ps a parameter, the vector unit's pace),
``nearest`` a constant (nearest rounding: a yardstick, never a candidate: it
decays the second moment).  The default shapes are the largest stacked
leaves of ``train-pythia-1.4b-s2048``.  It runs nothing a cell
runs.  Needs a TPU: a timing from anything else says nothing, so there is no
fallback; ``--describe`` compiles for a described v5e instead and prints the
account without a time.
"""

import argparse
import contextlib
import json
import os
import re
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

SHAPES = ["16x2048x8192", "16x2048x6144", "50304x2048"]
OPTIMIZER = {"lr": 1e-4, "weight_decay": 0.0, "moment_dtype": "bfloat16"}
HBM_BYTES_PER_S = 819e9          # TPU v5e (chipbench/peaks.json)


def noise_sources():
    """{name: ``(seed, shape) -> uint32 word an element``}, low half for
    ``mu`` and high half for ``nu``; None is the tree's own."""
    import jax
    import jax.numpy as jnp

    def threefry(seed, shape):
        key = jax.random.fold_in(jax.random.key(0), seed)
        lo, hi = (jax.random.bits(jax.random.fold_in(key, half), shape,
                                  jnp.uint16).astype(jnp.uint32)
                  for half in (0, 1))
        return lo | (hi << 16)

    def nearest(seed, shape):
        return jnp.full(shape, 0x80008000, jnp.uint32)

    return {"engine": None, "threefry": threefry, "nearest": nearest}


def update_fn(noise):
    """The optimizer scope of the engine's step for one leaf, with the
    rounding's noise from ``noise`` (None: the tree's own)."""
    import jax
    import jax.numpy as jnp
    import optax
    from deepspeed_tpu.runtime import optimizers
    from deepspeed_tpu.runtime.engine import _global_norm_f32

    if noise is not None and not hasattr(optimizers, "_rounding_noise"):
        sys.exit("optimizer_update_times: this tree draws its noise from "
                 "jax.random: only --noise engine runs here")
    tx = optimizers.build_optimizer("adamw", dict(OPTIMIZER))

    def update(params, opt_state, grads):
        overflow = jnp.asarray(False)     # as the engine's without fp16
        norm = _global_norm_f32(grads)
        with contextlib.nullcontext() if noise is None else \
                mock.patch.object(optimizers, "_rounding_noise", noise):
            updates, new_opt = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        pick = lambda new, old: jax.tree_util.tree_map(     # noqa: E731
            lambda n, o: jnp.where(overflow, o, n), new, old)
        return pick(new_params, params), pick(new_opt, opt_state), norm

    return tx, jax.jit(update, donate_argnums=(0, 1))


def account(compiled, n):
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    text = compiled.as_text()
    return {"bytes_per_param": round(cost["bytes accessed"] / n, 2),
            "ops_per_param": round(cost["flops"] / n, 1),
            "fusions": len(re.findall(r"^\s*(?:ROOT )?%?[\w.-]+ = .* fusion\(",
                                      text, re.M)),
            "temp_mb": round(
                compiled.memory_analysis().temp_size_in_bytes / 1e6, 1)}


def measure(shape, name, noise, iters, described=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = int(np.prod(shape))
    tx, update = update_fn(noise)
    line = {"shape": "x".join(map(str, shape)), "noise": name, "params": n}
    if described is not None:
        at = lambda s: jax.tree_util.tree_map(               # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=described), s)
        params = {"w": jax.ShapeDtypeStruct(shape, jnp.float32)}
        args = at((params, jax.eval_shape(tx.init, params),
                   {"w": jax.ShapeDtypeStruct(shape, jnp.bfloat16)}))
        return dict(line, **account(update.lower(*args).compile(), n))
    keys = jax.random.split(jax.random.key(0), 2)
    params = {"w": jax.random.normal(keys[0], shape, jnp.float32) * 0.02}
    grads = {"w": jax.random.normal(keys[1], shape, jnp.bfloat16) * 1e-3}
    opt_state = tx.init(params)
    compiled = update.lower(params, opt_state, grads).compile()
    for _ in range(2):
        params, opt_state, norm = compiled(params, opt_state, grads)
    jax.block_until_ready(norm)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, norm = compiled(params, opt_state, grads)
    jax.block_until_ready((params, opt_state, norm))
    seconds = (time.perf_counter() - t0) / iters
    line.update(ms=round(seconds * 1e3, 3),
                ps_per_param=round(seconds / n * 1e12, 2),
                **account(compiled, n))
    line["hbm_pct"] = round(100 * line["bytes_per_param"] * n
                            / HBM_BYTES_PER_S / seconds, 1)
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="*", default=SHAPES,
                    help="leaf shapes, as 16x2048x8192")
    ap.add_argument("--noise", nargs="*",
                    default=["engine", "threefry", "nearest"])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--describe", action="store_true",
                    help="compile for a described v5e (no chip): the "
                         "compiler's account, no time")
    args = ap.parse_args(argv)
    described = None
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ["JAX_PLATFORMS"] = "cpu"
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        described = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    import jax
    if described is None and jax.default_backend() != "tpu":
        sys.exit(f"optimizer_update_times: needs a TPU, found "
                 f"{jax.default_backend()}")
    sources = noise_sources()
    for shape in args.shapes:
        for name in args.noise:
            print(json.dumps(measure(tuple(int(d) for d in shape.split("x")),
                                     name, sources[name], args.iters,
                                     described)), flush=True)


if __name__ == "__main__":
    main()
