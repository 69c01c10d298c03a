"""Serve loop: the share (%) of the loop's flush and apply time in
which its thread waits for the device: the program's
``repro.serve.fetch`` spans (a flush's wait for its programs and any
merges queued before them) and ``repro.ingest.wait`` spans (an apply's
capacity check, which waits for the merges before it), over its
``repro.serve.flush`` and ``repro.ingest.apply`` spans.  The loop
serves nothing while it waits."""

from bench.metrics._program import time_share


def read(view):
    return time_share(view, ("repro.serve.fetch", "repro.ingest.wait"),
                      ("repro.serve.flush", "repro.ingest.apply"))
