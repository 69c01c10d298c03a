"""Serve loop: 99th percentile (nearest rank) of the wait from a
request's due time to the start of the flush that served it (ms); a
request never flushed counts at the window's ceiling."""

import math

import numpy as np


def read(view):
    w = view.layer.get("queue_wait_ms")
    if w is None or len(w) == 0:
        return None
    w = np.sort(w)
    return float(w[max(0, math.ceil(0.99 * len(w)) - 1)])
