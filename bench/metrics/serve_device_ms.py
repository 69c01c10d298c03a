"""Drivers, store gather and window folds: device time of the programs
each flush launched (ms per batch)."""

from bench.metrics._spans import mean_launched


def read(view):
    v = mean_launched(view, "bench.flush", 1)
    return None if v is None else v * 1e3
