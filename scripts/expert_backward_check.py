#!/usr/bin/env python3
"""The dropless expert layer's Pallas kernels against their jnp cousins ON
THE CHIP, at a training cell's shapes: ``dropless_held_experts`` forward
and, by ``jax.grad`` through its ``custom_vjp``, the gradients with respect
to the rows, the router's weights and the three (stacked) expert leaves,
once with ``impl="pallas"`` (``grouped_expert_glu``, ``_dx``, ``_dw``) and
once with ``impl="jnp"``.  The training ``correct`` reads one scalar and
cannot see a gradient (PERF.md section 7), and the CPU tests run the
kernels in interpret mode at toy shapes: this is the evidence the backward
has at the real size.  Prints one JSON line a dtype: the largest
difference of each result over the largest magnitude of the jnp one.

    python3 scripts/expert_backward_check.py            # N 8192, 16 of 64
    python3 scripts/expert_backward_check.py --tokens 64 --hidden 128 \\
        --width 128 --interpret                         # a CPU's size
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NAMES = ("out", "d_rows", "d_router", "d_gate", "d_up", "d_down")


def results(impl, dtype, args, key):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe import sharded_moe

    N, d, f = args.tokens, args.hidden, args.width
    k_h, k_r, k_g, k_u, k_d, k_c = jax.random.split(key, 6)
    h = jax.random.normal(k_h, (N, d), jnp.float32).astype(dtype)
    wg = jax.random.normal(k_r, (d, args.experts), jnp.float32) / d ** 0.5
    shape = (args.layers, args.held)
    experts = {
        "w_gate": jax.random.normal(k_g, shape + (d, f)) / d ** 0.5,
        "w_up": jax.random.normal(k_u, shape + (d, f)) / d ** 0.5,
        "w_down": jax.random.normal(k_d, shape + (f, d)) / f ** 0.5}
    experts = {n: w.astype(dtype) for n, w in experts.items()}
    # a cotangent with every element its own, as the layers above give
    cot = jax.random.normal(k_c, (N, d), jnp.float32)

    def out_of(h, wg, experts):
        chosen, weights = sharded_moe.dropless_route(
            h, wg, None, args.top_k, scoring="softmax", norm=True)
        out, load, rows = sharded_moe.dropless_held_experts(
            h, chosen, weights, experts, jax.nn.silu,
            layer=jnp.int32(args.layers - 1), impl=impl,
            interpret=args.interpret and impl == "pallas")
        return out, (load, rows)

    def scalar(h, wg, experts):
        out, counted = out_of(h, wg, experts)
        return jnp.sum(out * cot), (out, counted)

    grads, (out, (load, rows)) = jax.jit(
        jax.grad(scalar, argnums=(0, 1, 2), has_aux=True))(h, wg, experts)
    d_h, d_wg, d_e = grads
    last = args.layers - 1
    values = (out, d_h, d_wg, d_e["w_gate"][last], d_e["w_up"][last],
              d_e["w_down"][last])
    # the layers of the stack that were not read get no gradient
    others = max(float(jnp.abs(d_e[n][:last]).max()) if last else 0.0
                 for n in d_e)
    return [jax.device_get(v.astype(jnp.float32)) for v in values], \
        jax.device_get(load), int(rows), others


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--hidden", type=int, default=2304)
    ap.add_argument("--width", type=int, default=896)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.key(args.seed)
    for dtype in (jnp.float32, jnp.bfloat16):
        kernel, load, rows, others = results("pallas", dtype, args, key)
        plain, load_p, rows_p, others_p = results("jnp", dtype, args, key)
        line = {"dtype": jnp.dtype(dtype).name, "device":
                jax.devices()[0].device_kind, "pairs": int(load.sum()),
                "load_max": int(load.max()), "rows": rows,
                "same_routing": bool((load == load_p).all()
                                     and rows == rows_p),
                "unread_layers_grad_max": max(others, others_p)}
        for name, a, b in zip(NAMES, kernel, plain):
            line[name] = float(np.abs(a - b).max() / np.abs(b).max())
            line[name + "_max"] = float(np.abs(b).max())
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
