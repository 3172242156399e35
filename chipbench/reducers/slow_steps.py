"""The steps that ran long inside the window, from the PROGRAM's own record
of them (``get_telemetry().slow_steps``, docs/telemetry.md "The slow-step
record"): a step (one ``serve/loop``; for a trainer the time from one
``engine/train_batch`` open to the next) that took over three medians of
its kind and over that median by a quarter of a second leaves one record,
with where the time went.  ``field`` ``count`` is the records that lie
inside the run's whole steps (``run.steps[0]["t0"]`` .. ``[-1]["t1"]``:
set-up's own long periods lie before it); ``pct`` is 100 x the sum of
(step - its kind's median) over the window's seconds: the share of the
rate the run lost to them, in the unit of the end-to-end bounds.

Left out: a record that touches an iteration in which the profiler of a
traced run starts or stops (``serve_cell`` ticks its tracer inside the
iteration, ``train_cell`` between two steps, so from the end of the step
before to the end of the one whose ``traced`` changed): a stop that writes
25 s of trace is no step's work, as in ``serve_mfu_pct``.  None where the
program keeps no such record (a commit from before it was added); 0.0,
never None, where it keeps one and nothing ran long."""

from chipbench.reducers import program_spans


def _profiler_switches(steps):
    """(start, end), in seconds, around every tick that switched the
    profiler: a step's ``traced`` says whether it was on AFTER its tick."""
    out, before, last_end = [], False, None
    for step in steps:
        traced = step.get("traced", False)
        if traced != before:
            out.append((step["t0"] if last_end is None else last_end,
                        step["t1"]))
        before, last_end = traced, step["t1"]
    return out


def records(run):
    tel = program_spans.telemetry()
    if not hasattr(tel, "slow_steps") or not run.steps:
        return None
    switches = [(int(a * 1e9), int(b * 1e9))
                for a, b in _profiler_switches(run.steps)]
    return [r for r in tel.slow_steps(int(run.steps[0]["t0"] * 1e9),
                                      int(run.steps[-1]["t1"] * 1e9))
            if not any(r["t0_ns"] < b and r["t1_ns"] > a
                       for a, b in switches)]


def read(run, field):
    found = records(run)
    if found is None:
        return None
    if field == "count":
        return float(len(found))
    lost_ns = sum(r["t1_ns"] - r["t0_ns"] - r["median_ns"] for r in found)
    return 100.0 * lost_ns / 1e9 / (run.steps[-1]["t1"] - run.steps[0]["t0"])
