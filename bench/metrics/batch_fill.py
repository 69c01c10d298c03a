"""Serve loop: requests served per slot of the flushed batches (%)."""


def read(view):
    st = view.layer.get("loop_stats")
    if not st:
        return None
    flushes = (st["size_flushes"] + st["deadline_flushes"]
               + st["forced_flushes"])
    if flushes == 0:
        return None
    return 100.0 * st["served"] / (flushes * view.layer["batch_size"])
