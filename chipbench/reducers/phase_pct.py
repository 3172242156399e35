"""Share of the device's busy time spent in one phase of the training step
(``fwd``, ``bwd``, ``remat``, ``loss_head``, ``optimizer``, ...), in %:
the trace's seconds per instruction, each instruction's phase from the
program's own table (``telemetry.op_scopes(site)``: parsed by the program
from the text of what it compiled, where every instruction carries its
``jax.named_scope`` path).  Instructions the table does not know count as
``other``."""

from chipbench import reduce


def by_phase(run, site):
    """{phase: seconds} over the traced window, or None."""
    if run.trace is None or not run.trace.ops:
        return None
    from deepspeed_tpu.monitor import telemetry
    if not hasattr(telemetry, "op_scopes"):
        return None
    table = telemetry.op_scopes(site)
    if not table:
        return None
    out = {}
    for label, seconds in reduce.op_seconds(run.trace).items():
        phase = table.get(label.rpartition(":")[0], "other")
        out[phase] = out.get(phase, 0.0) + seconds
    return out


def read(run, phase, site="engine/train_step"):
    phases = by_phase(run, site)
    busy = reduce.busy_seconds(run.trace) if phases else 0.0
    if not busy:
        return None
    return 100.0 * phases.get(phase, 0.0) / busy
