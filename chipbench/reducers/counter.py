"""One of the run's counters, as counted."""


def read(run, name):
    return run.counters.get(name)
