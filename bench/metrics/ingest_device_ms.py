"""Store write path: device time of the programs each ingest apply
launched (ms per batch)."""

from bench.metrics._spans import mean_launched


def read(view):
    v = mean_launched(view, "bench.ingest", 1)
    return None if v is None else v * 1e3
