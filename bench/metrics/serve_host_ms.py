"""Front end (encode, pad, split, dispatch, copy back): per flush, the
flush span less the device-busy time inside it (ms per batch)."""

from bench.metrics._spans import mean_host


def read(view):
    v = mean_host(view, "bench.flush")
    return None if v is None else v * 1e3
