"""Time in collective operations as a share of the traced window, per
device; ``exposed`` keeps only the part with no computation running on
that device."""

from chipbench import reduce


def read(run, exposed=False):
    if run.trace is None or not run.trace.ops:
        return None
    total, bare = reduce.collective_seconds(run.trace)
    return 100.0 * (bare if exposed else total) \
        / reduce.window_seconds(run.trace)
