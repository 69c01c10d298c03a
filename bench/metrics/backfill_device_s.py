"""Offline executors: device time of the programs each ``offline()``
call launched (s per call)."""

from bench.metrics._spans import mean_launched


def read(view):
    return mean_launched(view, "bench.offline", 1)
