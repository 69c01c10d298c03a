"""Offline planning on the host: each ``offline()`` span less the
device-busy time inside it (s per call)."""

from bench.metrics._spans import mean_host


def read(view):
    return mean_host(view, "bench.offline")
