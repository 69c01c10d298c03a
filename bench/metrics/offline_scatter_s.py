"""Offline planning, per ``offline()`` call: copying the outputs back
and placing each unit's rows in base-row order, from the program's
``repro.offline.scatter`` span (s per call)."""

from bench.metrics._program import mean_per_parent


def read(view):
    return mean_per_parent(view, "repro.offline.call",
                           ("repro.offline.scatter",))
