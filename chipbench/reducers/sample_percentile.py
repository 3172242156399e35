"""A percentile of one of the run's lists of samples (milliseconds)."""

import numpy as np


def read(run, sample, q):
    values = run.samples.get(sample)
    return float(np.percentile(values, q)) if values else None
