"""A Pallas kernel's share of its roofline: the least seconds the chip
could take for the traced calls over the kernel's seconds in the device
trace.  The least seconds are ``chipbench/costs/<cost>.py``:
``least_seconds(run)``, from shapes and the published peaks
(``chipbench/roofline.py`` holds the arithmetic of the kernels there are),
so a new kernel brings its operations and bytes as a file of its own.
``kernel`` picks the kernel by the beginning of its name on the device's
line (``ragged_paged_attention_``, ``flash_attention_``: the program's
fixed names, docs/telemetry.md), for a cell that runs more than one;
without it every Pallas call of the trace is the kernel."""

import importlib

from chipbench import reduce


def read(run, cost, kernel=None):
    if run.trace is None or not run.trace.ops or not run.traced_steps:
        return None
    kernel_s = sum(reduce.op_seconds(run.trace, "pallas", kernel).values())
    if not kernel_s:
        return None
    try:
        costs = importlib.import_module("chipbench.costs." + cost)
    except ModuleNotFoundError as e:
        if e.name != "chipbench.costs." + cost:
            raise       # the cost file is there; something it imports is not
        raise ValueError(f"unknown cost function {cost!r}: no "
                         f"chipbench/costs/{cost}.py") from e
    return 100.0 * costs.least_seconds(run) / kernel_s
