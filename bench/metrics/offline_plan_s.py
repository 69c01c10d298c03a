"""Offline planning on the host, per ``offline()`` call: the content
fingerprint of the tables and the plan (32-bit casts, merge, sort, unit
plan, padded blocks), from the program's ``repro.offline.fingerprint``
and ``repro.offline.plan`` spans (s per call)."""

from bench.metrics._program import mean_per_parent


def read(view):
    return mean_per_parent(view, "repro.offline.call",
                           ("repro.offline.fingerprint",
                            "repro.offline.plan"))
