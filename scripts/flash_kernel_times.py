#!/usr/bin/env python3
"""Device time of each flash-attention kernel, alone, on the chip.

    chiprun -- python scripts/flash_kernel_times.py
    chiprun -- python scripts/flash_kernel_times.py --tiles 512x512 256x512

One JSON line a shape and tile pair: the milliseconds a call of
``flash_attention_fwd`` / ``_dq`` / ``_dkv`` takes (mean over ``--iters``
executions, read from the profiler's device line by the kernels' names, so
XLA's work around them is left out and given as ``xla``) and each kernel's
share of its own roofline (forward 2, dq 3, dk/dv 4 products of the pairs
attended to, as ``chipbench/roofline.py`` counts them, against the bf16
peak).  The shapes are the three training cells' own calls; ``--tiles``
overrides the picker (``pick_flash_tiles``).  This is how PR 49 measured
each change to the kernels before keeping it (PERF.md §6): copy the
kernel file, change one thing, load both here.  Needs a TPU: a timing from
anything else says nothing, so there is no fallback.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

from deepspeed_tpu.ops.pallas import flash_attention as flash  # noqa: E402

# the cells' calls: q shape, kv heads, window
SHAPES = {
    "train-pythia-1.4b-s2048": ((2, 2048, 16, 128), 16, None),
    "train-pythia-6.9b-fsdp4": ((1, 2048, 32, 128), 32, None),
    "train-mellum2-12b-ep4-s8192:full": ((1, 8192, 32, 128), 4, None),
    "train-mellum2-12b-ep4-s8192:window": ((1, 8192, 32, 128), 4, 1024),
}
PEAK_FLOPS = 197e12          # TPU v5e, bf16 (chipbench/peaks.json)
KERNELS = {"fwd": 2, "dq": 3, "dkv": 4}      # products a call


def kernel_ms(fn, args, iters):
    """{kernel | "xla": mean device milliseconds an execution of ``fn``}."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    trace_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(trace_dir)
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    total = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                kernel = next((k for k in KERNELS
                               if "flash_attention_" + k in event.name),
                              "xla")
                total[kernel] = total.get(kernel, 0.0) \
                    + event.duration_ns / 1e6 / iters
    shutil.rmtree(trace_dir, ignore_errors=True)
    return total


def measure(name, block_q, block_k, iters):
    shape, kv_heads, window = SHAPES[name]
    B, S, H, D = shape
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], shape, jnp.bfloat16)
    k, v = (jax.random.normal(key, (B, S, kv_heads, D), jnp.bfloat16)
            for key in keys[1:])

    def loss(q, k, v):
        out = flash.flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k,
            window=None if window is None else jnp.int32(window))
        return (out.astype(jnp.float32) * 0.01).sum()

    ms = kernel_ms(jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))),
                   (q, k, v), iters)
    product = 2 * B * H * D * flash.attended_pairs(S, True, window)
    tiles = flash.resolve_tiles(S, D, H // kv_heads, 2, block_q, block_k)
    plan = flash.flash_plan(S, *tiles, True, window)
    return {"shape": name, "block_q": tiles[0], "block_k": tiles[1],
            "ms": {k: round(v, 4) for k, v in sorted(ms.items())},
            "roofline_pct": {k: round(100 * n * product / PEAK_FLOPS
                                      / (ms[k] / 1e3), 2)
                             for k, n in KERNELS.items() if k in ms},
            "pairs_needed_over_visited": round(
                plan["pairs_needed"] / plan["pairs_visited"], 4)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--tiles", nargs="*", default=["picked"],
                    help="BQxBK pairs, or 'picked'")
    ap.add_argument("--iters", type=int, default=4)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        sys.exit(f"flash_kernel_times: needs a TPU, found "
                 f"{jax.default_backend()}")
    for name in args.shapes:
        for tiles in args.tiles:
            block_q, block_k = (None, None) if tiles == "picked" else \
                (int(x) for x in tiles.split("x"))
            print(json.dumps(measure(name, block_q, block_k, args.iters)),
                  flush=True)


if __name__ == "__main__":
    main()
