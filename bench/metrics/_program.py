"""Shared reading of the program's own spans: the ``repro.*`` events
that ``src/repro/spans.py`` writes into the profiler's trace, with
their stats (the span's arguments).

They lie on the harness's host line (the program runs on the harness's
thread), which ``bench.trace.Reduced`` keeps as ``_host_line``; a
``Reduced`` without that attribute raises.  Each reader returns None
where the window holds no such span, as a program without these spans
gives."""

import bisect
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[float, float, Dict[str, object]]


def events(view, name: str) -> List[Event]:
    """``(start, end, stats)`` of each ``name`` span inside the window,
    in start order."""
    t = view.trace
    if t is None or t._host_line is None:
        return []
    out = []
    for e in t._host_line.events:
        if e.name == name:
            s = e.start_ns
            end = s + e.duration_ns
            if s >= t.w0 and end <= t.w1:
                out.append((s, end, dict(e.stats)))
    return sorted(out, key=lambda x: x[0])


def _inside(parents: Sequence[Event], kids: Sequence[Event]
            ) -> List[float]:
    """Per parent span, the nanoseconds of the ``kids`` inside it."""
    kids = sorted((s, e) for s, e, _ in kids)
    starts = [s for s, _ in kids]
    out = []
    for ps, pe, _ in parents:
        out.append(sum(e - s for s, e in
                       kids[bisect.bisect_left(starts, ps):
                            bisect.bisect_right(starts, pe)]
                       if e <= pe))
    return out


def mean_per_parent(view, parent: str, children: Sequence[str]
                    ) -> Optional[float]:
    """Mean seconds per ``parent`` span in the window of the
    ``children`` spans that lie inside it; None where the window holds
    no parent span or none of the children."""
    kids = [k for name in children for k in events(view, name)]
    parents = events(view, parent)
    if not kids or not parents:
        return None
    return sum(_inside(parents, kids)) / len(parents) * 1e-9


def mean_self(view, name: str, less: str) -> Optional[float]:
    """Mean seconds of the ``name`` spans less the ``less`` spans
    inside each."""
    parents = events(view, name)
    if not parents:
        return None
    inner = _inside(parents, events(view, less))
    return sum(e - s - i for (s, e, _), i in zip(parents, inner)) \
        / len(parents) * 1e-9


def median_span(view, name: str) -> Optional[float]:
    """Median seconds of the ``name`` spans in the window."""
    ev = events(view, name)
    if not ev:
        return None
    return statistics.median(e - s for s, e, _ in ev) * 1e-9


def time_share(view, parts: Sequence[str], wholes: Sequence[str]
               ) -> Optional[float]:
    """Seconds in the ``parts`` spans over seconds in the ``wholes``
    spans (%), each set disjoint in time; None where the window holds
    no ``wholes`` span."""
    def total(names):
        return sum(e - s for name in names for s, e, _ in events(view, name))

    whole = total(wholes)
    if not whole:
        return None
    return 100.0 * total(parts) / whole


def stat_sum(view, name: str, key: str) -> Optional[float]:
    """Sum of the stat ``key`` over the ``name`` spans in the window."""
    vals = [st[key] for _, _, st in events(view, name) if key in st]
    return float(sum(vals)) if vals else None
