"""Drivers, store gather and window folds: the median flush's wait for
its own programs and copy of the features back, from the program's
``repro.serve.fetch`` spans (ms per batch).  A flush queued behind
ingest merges waits for them here too; the median leaves those few out,
and ``loop_wait_share`` counts them."""

from bench.metrics._program import median_span


def read(view):
    v = median_span(view, "repro.serve.fetch")
    return None if v is None else v * 1e3
