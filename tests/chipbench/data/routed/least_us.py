"""A reducer a later PR might bring: a cost file's least time, alone."""

import importlib


def read(run, cost):
    costs = importlib.import_module("chipbench.costs." + cost)
    return 1e6 * costs.least_seconds(run)
