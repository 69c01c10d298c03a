"""Store write path: the host's own work in each ingest apply (encoding
the rows, padding, upload and dispatch of the merge, the binlog,
retention), from the program's ``repro.ingest.apply`` span less the
``repro.ingest.wait`` inside it, the capacity check's wait for the
merges before it.  The merge itself runs on after the span (ms per
batch)."""

from bench.metrics._program import mean_self


def read(view):
    v = mean_self(view, "repro.ingest.apply", "repro.ingest.wait")
    return None if v is None else v * 1e3
