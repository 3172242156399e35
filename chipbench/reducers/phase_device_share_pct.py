"""Share of the traced window in which the device ran dispatches of one
``phase`` (``prefill``: what every decoding request waits while a chunk
runs), in %: the device's busy time inside those executions, cut by launch
order (``launch_order``), so it reads the work where the serve loop's own
``serve/prefill`` span, which closes at the launch of a chunk nothing is
sampled from, reads the launch."""

from chipbench import reduce
from chipbench.reducers import launch_order


def read(run, phase):
    found = launch_order.device_seconds(run, phase)
    window = reduce.window_seconds(run.trace) if found else 0.0
    return 100.0 * found[0] / window if window else None
