"""``scope_pct`` for a serve loop that does not wait for every dispatch
(the chunked policy: ``launch_order``): share of the device's busy time
spent under one of the program's ``jax.named_scope``s over ALL of a
server's programs, in %, each device operation given to the dispatch it
ran in by launch order, and its scope read from the table of THAT
dispatch's program (``telemetry.op_scopes(site, arg_shapes)``; a chunk
shape compiles two prefill programs, with the head and without, told
apart by the shape of the rows the head runs on).  None where the program
has no such table, no trace, no traced dispatch, or a trace on whose
``XLA Modules`` line the dispatches cannot be found."""

import numpy as np

from chipbench import reduce
from chipbench.reducers import launch_order


def table_of(program):
    from deepspeed_tpu.monitor import telemetry
    phase, batch, tokens, head_rows = program
    shapes = {1: (batch, tokens)}
    if phase == "prefill":
        shapes[5] = (batch, head_rows)      # the rows the head runs on
    return telemetry.op_scopes(launch_order.SITES[phase], arg_shapes=shapes)


def by_scope(run):
    """{scope: seconds} of the first device over the traced dispatches."""
    from deepspeed_tpu.monitor import telemetry
    if not hasattr(telemetry, "SERVE_SCOPES"):
        return None
    ex = launch_order.executions(run)
    if ex is None:
        return None
    programs = [launch_order.program_of(d) for d in ex.dispatches]
    tables = {p: table_of(p) for p in dict.fromkeys(programs)}
    if not any(tables.values()):
        return None
    names = [label.rpartition(":") for label in run.trace.labels]
    body = np.asarray([opcode not in reduce.CONTAINERS
                       for _, _, opcode in names], bool)
    length = ex.end - ex.start
    index = np.asarray([list(tables).index(p) for p in programs])
    out = {}
    for p, table in enumerate(tables.values()):     # a program at a time
        mine = (ex.owner >= 0) & (index[np.maximum(ex.owner, 0)] == p) \
            & body[ex.label] & (length > 0)
        seconds = np.bincount(ex.label[mine], length[mine] / 1e9,
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
