"""Serve loop: 99th percentile (nearest rank) of the time from each
request's due time until its features are back (ms), over every request
due in the traced window; a shed or unanswered request counts at the
window's ceiling.  The serve mode's ``p99_ms``, read per layer where
host stalls swing it too far for a bound."""

import math

import numpy as np


def read(view):
    lat = view.layer.get("latency_ms")
    if lat is None or len(lat) == 0:
        return None
    lat = np.sort(lat)
    return float(lat[max(0, math.ceil(0.99 * len(lat)) - 1)])
