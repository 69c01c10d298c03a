"""Offline executors: real rows over padded unit slots of the unit
plans lowered in the window (%), from the ``rows`` and ``slots`` stats
of the program's ``repro.offline.plan.group`` spans.  The rest of the
slots are padding the fold computes and throws away."""

from bench.metrics._program import stat_sum


def read(view):
    rows = stat_sum(view, "repro.offline.plan.group", "rows")
    slots = stat_sum(view, "repro.offline.plan.group", "slots")
    if not slots:
        return None
    return 100.0 * rows / slots
