"""Median host-clock milliseconds of the window's steps.  ``field``
``step_s`` reads the time inside the engine's ``step()`` alone (serving);
the default is the whole step, input included."""

import statistics


def read(run, field=None):
    if not run.steps:
        return None
    values = [s[field] if field else s["t1"] - s["t0"] for s in run.steps]
    return statistics.median(values) * 1000.0
