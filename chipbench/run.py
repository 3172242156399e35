#!/usr/bin/env python3
"""The benchmark's one command (contract: BENCHMARK.json, PERF.md §2):

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs one cell on the machine it is started on: builds the system under
test from the cell's configuration with weights made from ``--seed``,
compares it with the plain float32 reference, warms up the cell's shapes
(all of that is ``setup_s``), measures for ``--seconds`` and prints one
JSON object as the last line of standard output.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window's first seconds.  No TPU, or fewer chips than
the cell asks for: one line on standard error, a non-zero exit, no result.
"""

import time

STARTED = time.perf_counter()     # set-up is counted from process start

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import cells, device, reduce
    cell = cells.load_cell(args.workload)
    # the program's one rule for the persistent compile cache: the
    # directory JAX_COMPILATION_CACHE_DIR names, else .jax_cache/ at the
    # root of this checkout; every program is cached, however small
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = device.require_devices(cell.chips, require_tpu)
    # off the chip (the tests' switch) the peaks are the v5e's: whatever is
    # divided by them there is arithmetic under test, never a result
    peaks = device.peaks_for(devices[0].device_kind if require_tpu
                             else "TPU v5 lite")

    from chipbench import serve_cell, train_cell
    kinds = {"pretrain": train_cell.run, "open_loop": serve_cell.run,
             "closed_loop": serve_cell.run}
    result = kinds[cell.mix["kind"]](
        cell, args.seed, args.seconds, bool(args.trace), STARTED, devices,
        peaks)

    run = result["run"]
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "device": result["device"]}
    if args.trace:
        line["metrics"] = cells.read_layer_metrics(cell, run)
        line["device"]["busy_s"] = reduce.busy_seconds(run.trace)
        line["device"]["window_s"] = reduce.window_seconds(run.trace)
        line["breakdown"] = reduce.breakdown(run.trace)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        line["metrics"] = {
            name: {"value": float(value), "unit": units[name]}
            for name, value in result["end_to_end"].items()
            if name in units and value is not None}
    print(json.dumps(line), flush=True)
    # each number compared beside its limit, as the last lines of standard
    # error (the log lines above go to standard output): of a run that is
    # not correct the driver's record keeps the end of this, and of the
    # output the last line's keys alone.  A runner that hands back no
    # ``compared`` prints the verdict alone.
    for name, value, limit in result.get("compared", ()):
        print(f"chipbench: {name} {value} (limit {limit})", file=sys.stderr)
    print(f"chipbench: correct {result['correct']}", file=sys.stderr,
          flush=True)


if __name__ == "__main__":
    main()
