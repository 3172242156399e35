"""Share of the device's busy time spent in one kind of operation
(``pallas``, ``collective``, ``xla``), from the device trace; ``outside``
gives the share spent in every other kind."""

from chipbench import reduce


def read(run, kind, outside=False):
    if run.trace is None or not run.trace.ops:
        return None
    busy = reduce.busy_seconds(run.trace)
    if not busy:
        return None
    share = 100.0 * reduce.busy_seconds(run.trace, kind) / busy
    return 100.0 - share if outside else share
