"""The served batch's share of its roofline (%): the least time the
bytes its windows need take at the chips' HBM bandwidth, over the device
time of the programs the flushes launched.  The bytes are counted from
the generated data by the reference module (``request_bytes``), whatever
the implementation reads; the device time is averaged over the chips, so
the bandwidth is that of all the cell's chips together."""


def read(view):
    t = view.trace
    if t is None or not t.busy:
        return None
    device_s = sum(r[1] for r in t.launched_in("bench.flush"))
    nbytes = view.layer.get("flush_bytes")
    if device_s <= 0 or not nbytes:
        return None
    bandwidth = view.peaks["hbm_bytes_per_s"] * view.layer.get("devices", 1)
    return 100.0 * nbytes / bandwidth / device_s
