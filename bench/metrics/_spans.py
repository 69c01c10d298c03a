"""Shared arithmetic of the span readers.  Each returns None where the
trace has no device plane or no such span."""


def _has_device(view) -> bool:
    return view.trace is not None and bool(view.trace.busy)


def mean_host(view, span: str):
    """Mean seconds per ``span`` in which the device was not busy: the
    span's host work (encode, pad, plan, dispatch, copy back)."""
    if not _has_device(view):
        return None
    pairs = view.trace.busy_in_spans(span)
    if not pairs:
        return None
    return sum(t - b for t, b in pairs) / len(pairs)


def mean_launched(view, span: str, which: int):
    """Mean per ``span`` of ``Reduced.launched_in``'s field ``which``:
    1 = device seconds of the programs it launched, 2 = seconds until
    those programs finished."""
    if not _has_device(view):
        return None
    rows = view.trace.launched_in(span)
    if not rows:
        return None
    return sum(r[which] for r in rows) / len(rows)


def idle_share(view):
    t = view.trace
    if not _has_device(view) or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
