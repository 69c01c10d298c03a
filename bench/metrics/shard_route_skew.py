"""Key routing and shard fan-out: the requests routed to the busiest
shard of each flush over the mean of the shards', summed over the
window's flushes (%).  100 is even.  Every shard's sub-batch is padded
to the busiest one's count, so this is also the device slots a flush
runs per request routed, against an even split."""

import numpy as np


def read(view):
    counts = view.layer.get("shard_counts")
    if counts is None or len(counts) == 0:
        return None
    counts = np.asarray(counts)
    total = counts.sum()
    if total == 0:
        return None
    return float(100.0 * counts.shape[1] * counts.max(axis=1).sum() / total)
