"""Share of the device's busy time spent in one kind of operation
(``pallas``, ``collective``, ``xla``), from the device trace; ``outside``
gives the share spent in every other kind.  ``kernel`` narrows the kind to
the operations whose name on the device's line begins with it
(``ragged_paged_attention_``, ``flash_attention_``), for a cell that runs
more than one kernel."""

from chipbench import reduce


def read(run, kind, outside=False, kernel=None):
    if run.trace is None or not run.trace.ops:
        return None
    busy = reduce.busy_seconds(run.trace)
    if not busy:
        return None
    of_kind = reduce.busy_seconds(run.trace, kind, kernel)
    if kernel is not None and not of_kind:
        return None         # no such kernel ran: nothing to read
    share = 100.0 * of_kind / busy
    return 100.0 - share if outside else share
