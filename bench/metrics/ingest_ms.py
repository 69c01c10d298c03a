"""Store write path: per 1,024-row apply, from its start until the
programs it launched (the merge) have finished on the device (ms per
batch)."""

from bench.metrics._spans import mean_launched


def read(view):
    v = mean_launched(view, "bench.ingest", 2)
    return None if v is None else v * 1e3
