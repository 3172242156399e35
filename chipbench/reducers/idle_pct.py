"""1 - (union of device-operation intervals) / traced window, in %."""

from chipbench import reduce


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * (1.0 - reduce.busy_seconds(run.trace)
                    / reduce.window_seconds(run.trace))
