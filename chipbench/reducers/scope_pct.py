"""Share of the device's busy time spent under one of the program's
``jax.named_scope``s (``select``, ``latent_attn``, ``experts``, ...) over
ALL of a server's programs, in %.

A server runs several compiled programs (decode, one prefill a bucket) whose
instructions share names (``fusion.3``), so an operation of the trace is
first given to the dispatch it ran in, by time: the serve loop fetches each
dispatch's logits before it launches the next, so what the device ran from
one launch to the next belongs to the first.  The dispatch's phase and shape
pick the program, and the program's own table
(``telemetry.op_scopes(site, arg_shapes)``) gives the scope of each of its
instructions.  None where the program has no such table (a commit from
before it), no trace, or no traced dispatch."""

import numpy as np

from chipbench import reduce
from chipbench.reducers import program_spans

SITES = {"prefill": "serve/prefill_fn", "decode": "serve/step_fn"}


def by_scope(run):
    """{scope: seconds} of the first device over the traced dispatches."""
    if run.trace is None or not run.trace.ops or not run.traced_steps:
        return None
    from deepspeed_tpu.monitor import telemetry
    offset = program_spans.clock_offset_ns(run)
    if offset is None or not hasattr(telemetry, "SERVE_SCOPES"):
        return None
    dispatches = sorted((d for s in run.traced_steps
                         for d in s["dispatches"] if d["phase"] in SITES),
                        key=lambda d: d["t0_ns"])
    if not dispatches:
        return None
    tables = {}
    for d in dispatches:
        key = (d["phase"], d["batch"], d["tokens"])
        if key not in tables:
            tables[key] = telemetry.op_scopes(
                SITES[d["phase"]], arg_shapes={1: (d["batch"], d["tokens"])})
    if not any(tables.values()):
        return None
    line = run.trace.ops[0]
    lo, hi = run.trace.window
    launched = np.asarray([d["t0_ns"] for d in dispatches]) + offset
    owner = np.searchsorted(launched, line.start, side="right") - 1
    length = np.clip(line.start + line.dur, lo, hi) - np.clip(line.start,
                                                              lo, hi)
    names = [label.rpartition(":") for label in run.trace.labels]
    body = np.asarray([opcode not in reduce.CONTAINERS
                       for _, _, opcode in names], bool)
    program = np.asarray([list(tables).index(
        (d["phase"], d["batch"], d["tokens"])) for d in dispatches])
    out = {}
    for p, table in enumerate(tables.values()):     # a program at a time
        mine = (owner >= 0) & (program[np.maximum(owner, 0)] == p) \
            & body[line.label] & (length > 0)
        seconds = np.bincount(line.label[mine], length[mine] / 1e9,
                              len(names))
        for (name, _, _), t in zip(names, seconds):
            if t:
                scope = table.get(name, "other")
                out[scope] = out.get(scope, 0.0) + float(t)
    return out


def read(run, scope):
    scopes = by_scope(run)
    busy = reduce.busy_seconds(run.trace) if scopes else 0.0
    if not busy:
        return None
    return 100.0 * scopes.get(scope, 0.0) / busy
