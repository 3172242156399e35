"""Communication micro-benchmark — the ``ds_bench`` CLI.

Parity: reference ``benchmarks/communication/run_all.py`` + ``bin/ds_bench``
(all_reduce / all_gather / reduce_scatter / all_to_all / broadcast / pt2pt
with ``--scan`` over sizes; reports latency, algbw, busbw).

TPU flavor: each collective is a ``shard_map``-wrapped ``jax.lax``
collective over a 1-D mesh of all local devices, jitted then timed with
``block_until_ready``.  Bus-bandwidth factors follow the standard
nccl-tests accounting.
"""

import argparse
import time
from functools import partial

import numpy as np

COLLECTIVES = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
               "broadcast", "pt2pt")


def _busbw_factor(coll, n):
    if coll == "all_reduce":
        return 2.0 * (n - 1) / n
    if coll in ("all_gather", "reduce_scatter", "all_to_all"):
        return (n - 1) / n
    return 1.0  # broadcast / pt2pt


def build_collective_fn(coll, mesh, axis="world"):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    n = mesh.shape[axis]

    if coll == "all_reduce":
        def body(x):
            return jax.lax.psum(x, axis)
        in_spec, out_spec = P(axis), P(axis)
    elif coll == "all_gather":
        def body(x):
            return jax.lax.all_gather(x, axis, tiled=True)
        in_spec, out_spec = P(axis), P(axis)
    elif coll == "reduce_scatter":
        def body(x):
            return jax.lax.psum_scatter(x, axis, tiled=True)
        in_spec, out_spec = P(axis), P(axis)
    elif coll == "all_to_all":
        def body(x):
            return jax.lax.all_to_all(x.reshape(n, -1), axis, 0, 0,
                                      tiled=True).reshape(-1)
        in_spec, out_spec = P(axis), P(axis)
    elif coll == "broadcast":
        def body(x):
            src = jax.lax.all_gather(x, axis, tiled=False)[0]
            return src
        in_spec, out_spec = P(axis), P(axis)
    elif coll == "pt2pt":
        def body(x):
            return jax.lax.ppermute(
                x, axis, [(i, (i + 1) % n) for i in range(n)])
        in_spec, out_spec = P(axis), P(axis)
    else:
        raise ValueError(f"unknown collective '{coll}'")

    fn = shard_map(body, mesh=mesh, in_specs=in_spec, out_specs=out_spec)
    return jax.jit(fn)


def run_collective(coll, size_bytes, mesh, axis="world", trials=20,
                   warmups=5, dtype="float32"):
    """Times one collective at one size; returns dict with latency/bw."""
    import jax
    import jax.numpy as jnp

    n = mesh.shape[axis]
    dt = jnp.dtype(dtype)
    count = max(n, int(size_bytes) // dt.itemsize)
    count -= count % n  # divisible by the axis for scatter/a2a
    if count == 0:
        count = n
    x = jnp.zeros((count,), dt)
    fn = build_collective_fn(coll, mesh, axis)
    out = jax.block_until_ready(fn(x))  # compile
    for _ in range(warmups):
        out = jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(trials):
        out = jax.block_until_ready(fn(x))
    elapsed = (time.perf_counter() - t0) / trials
    del out
    size = count * dt.itemsize
    algbw = size / elapsed  # B/s
    busbw = algbw * _busbw_factor(coll, n)
    return {"collective": coll, "size_bytes": size, "world": n,
            "latency_us": elapsed * 1e6, "algbw_GBps": algbw / 1e9,
            "busbw_GBps": busbw / 1e9}


def scan_sizes(min_pow=10, max_pow=24):
    return [2 ** p for p in range(min_pow, max_pow + 1)]


def print_header(coll, n):
    print(f"\n---- {coll}  (world={n}) " + "-" * 40)
    print(f"{'size':>12} {'latency(us)':>14} {'algbw(GB/s)':>13} "
          f"{'busbw(GB/s)':>13}")


def main(argv=None):
    parser = argparse.ArgumentParser(description="deepspeed_tpu comm bench")
    parser.add_argument("--collective", type=str, default="all_reduce",
                        choices=COLLECTIVES + ("all",))
    parser.add_argument("--scan", action="store_true",
                        help="sweep sizes 1KB..16MB")
    parser.add_argument("--size", type=int, default=2 ** 22,
                        help="payload bytes when not scanning")
    parser.add_argument("--trials", type=int, default=20)
    parser.add_argument("--warmups", type=int, default=5)
    parser.add_argument("--dtype", type=str, default="float32")
    parser.add_argument("--maxsize", type=int, default=24,
                        help="log2 of the largest scanned size")
    args = parser.parse_args(argv)

    import jax
    from jax.sharding import Mesh
    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("world",))

    colls = COLLECTIVES if args.collective == "all" else (args.collective,)
    sizes = scan_sizes(max_pow=args.maxsize) if args.scan else [args.size]
    results = []
    for coll in colls:
        print_header(coll, mesh.shape["world"])
        for size in sizes:
            r = run_collective(coll, size, mesh, trials=args.trials,
                               warmups=args.warmups, dtype=args.dtype)
            results.append(r)
            print(f"{r['size_bytes']:>12} {r['latency_us']:>14.1f} "
                  f"{r['algbw_GBps']:>13.2f} {r['busbw_GBps']:>13.2f}")
    return results


if __name__ == "__main__":
    main()
