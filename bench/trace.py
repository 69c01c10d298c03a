"""From a profiler trace to the benchmark's per-layer numbers.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  A device plane (``/device:TPU:<n>``) has a line of device
operations (``XLA Ops``, a loop's body nested inside the loop) and a
line of whole programs (``XLA Modules``); the harness's own spans
(``bench.*`` ``TraceAnnotation``) are events of a host plane.  All times
here are nanoseconds on the trace's clock.

Device-busy is the union of the operation intervals, so overlapping or
nested operations count once.  Dispatch is asynchronous: a program that
a span launches can run after the span has ended, and start inside the
next one (an ingest merge queued on the device runs on into the flushes
after it).  So a program is given to the span that *dispatched* it: the
host's ``PjitFunction(<name>)`` events on the harness's thread are
paired, per program name and device, in order, with the device's
``XLA Modules`` events of that program (``jit_<name>``), since one
device runs its programs in the order they were dispatched.
"""

from __future__ import annotations

import bisect
import glob
import math
import os
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Span = Tuple[str, float, float]

TPU_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
DISPATCH_PREFIX = "PjitFunction("


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` that a trace into ``log_dir`` wrote."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {found}")
    return found[0]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_ops(profile, plane: Callable[[str], bool] = None,
               line: Callable[[str], bool] = None,
               event: Callable[[str], bool] = None
               ) -> Dict[str, List[Span]]:
    """Operation events per device plane: ``{plane: [(name, start,
    end)]}``.  The selectors default to TPU planes and their ``XLA Ops``
    line; a test on a CPU trace passes its own."""
    plane = plane or (lambda n: n.startswith(TPU_PLANE))
    line = line or (lambda n: n == OPS_LINE)
    out: Dict[str, List[Span]] = {}
    for p in profile.planes:
        if not plane(p.name):
            continue
        evs = out.setdefault(p.name, [])
        for ln in p.lines:
            if not line(ln.name):
                continue
            for e in ln.events:
                if e.duration_ns > 0 and (event is None or event(e.name)):
                    evs.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return {k: v for k, v in out.items() if v}


def union(spans: Sequence[Span]) -> List[Interval]:
    """Sorted, disjoint intervals covering every span."""
    merged: List[List[float]] = []
    for _, s, e in sorted(spans, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` that ``merged`` covers."""
    total = 0.0
    first = max(0, bisect.bisect_right(merged, (lo, math.inf)) - 1)
    for i in range(first, len(merged)):
        s, e = merged[i]
        if e <= lo:
            continue
        if s >= hi:
            break
        total += min(e, hi) - max(s, lo)
    return total


def host_spans(profile, prefix: str = SPAN_PREFIX) -> List[Span]:
    """The harness's spans, in start order, from every host plane."""
    out = []
    for p in profile.planes:
        if p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return sorted(out, key=lambda x: x[1])


def program_of_module(name: str) -> str:
    """``jit__online_fn(123)`` -> ``_online_fn``: the jitted function a
    device module runs."""
    base = name.split("(", 1)[0]
    return base[len("jit_"):] if base.startswith("jit_") else base


def program_of_dispatch(name: str) -> Optional[str]:
    """``PjitFunction(insert_many)`` -> ``insert_many``; None for any
    other host event."""
    if not (name.startswith(DISPATCH_PREFIX) and name.endswith(")")):
        return None
    return name[len(DISPATCH_PREFIX):-1]


def dispatches(line) -> List[Span]:
    """(program, start, end) of each outermost ``PjitFunction`` event of
    one host line, in start order (the fast path and its fallback nest
    one call's events inside each other)."""
    evs = sorted(((program_of_dispatch(e.name), e.start_ns,
                   e.start_ns + e.duration_ns) for e in line.events),
                 key=lambda d: (d[1], -d[2]))
    out: List[Span] = []
    for d in evs:
        if d[0] is None:
            continue
        if out and d[2] <= out[-1][2] and d[0] == out[-1][0]:
            continue
        out.append(d)
    return out


def pair_dispatches(disp: Sequence[Span],
                    modules: Dict[str, List[Span]],
                    program_of: Callable[[str], str] = program_of_module):
    """Pair each dispatch with the module it ran on every device: per
    device and program name, the k-th dispatch with the k-th module.
    Returns ``(pairs, unpaired)``: ``pairs[i]`` lists ``(device, start,
    end)`` of dispatch ``i``'s modules; ``unpaired`` maps a program name
    to the device seconds of its modules that no dispatch in the trace
    accounts for."""
    by_name: Dict[str, List[int]] = defaultdict(list)
    for i, (name, _, _) in enumerate(disp):
        by_name[name].append(i)
    pairs: List[List[Tuple[str, float, float]]] = [[] for _ in disp]
    unpaired: Dict[str, float] = defaultdict(float)
    for dev, mods in modules.items():
        seen: Dict[str, int] = defaultdict(int)
        for name, s, e in sorted(mods, key=lambda m: m[1]):
            prog = program_of(name)
            k = seen[prog]
            seen[prog] += 1
            if k < len(by_name.get(prog, ())):
                pairs[by_name[prog][k]].append((dev, s, e))
            else:
                unpaired[prog] += (e - s) * 1e-9
    return pairs, dict(unpaired)


def _host_line_of(profile, prefix: str):
    for p in profile.planes:
        if p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            if any(e.name.startswith(prefix) for e in ln.events):
                return ln
    return None


def layout(profile) -> Dict[str, Dict[str, int]]:
    """Events per line per plane: what a trace holds, to print."""
    return {p.name: {ln.name: sum(1 for _ in ln.events) for ln in p.lines}
            for p in profile.planes}


class Reduced:
    """One traced window, reduced: per-device busy intervals and the
    harness's spans."""

    def __init__(self, profile, window: str = SPAN_PREFIX + "window",
                 plane=None, line=None, event=None, module_line=None,
                 module_event=None, program_of=None):
        self.layout = layout(profile)
        self.ops = device_ops(profile, plane, line, event)
        self.busy = {d: union(v) for d, v in self.ops.items()}
        self.modules = device_ops(
            profile, plane, module_line or (lambda n: n == MODULES_LINE),
            module_event or event)
        self.spans = host_spans(profile)
        win = [s for s in self.spans if s[0] == window]
        if len(win) != 1:
            raise ValueError(f"expected one '{window}' span, found "
                             f"{len(win)}")
        _, self.w0, self.w1 = win[0]
        self._host_line = _host_line_of(profile, SPAN_PREFIX)
        self.dispatches = (dispatches(self._host_line)
                           if self._host_line is not None else [])
        self._dispatch_starts = [d[1] for d in self.dispatches]
        self.launched, self.unpaired = pair_dispatches(
            self.dispatches, self.modules, program_of or program_of_module)

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def busy_s(self, lo: Optional[float] = None,
               hi: Optional[float] = None) -> float:
        """Device-busy seconds in ``[lo, hi)`` (the window by default),
        averaged over the devices that ran anything."""
        lo = self.w0 if lo is None else lo
        hi = self.w1 if hi is None else hi
        if not self.busy:
            return 0.0
        return sum(covered(b, lo, hi) for b in self.busy.values()) \
            / len(self.busy) * 1e-9

    def spans_named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s[0] == name
                and s[1] >= self.w0 and s[2] <= self.w1]

    def busy_in_spans(self, name: str) -> List[Tuple[float, float]]:
        """(span seconds, device-busy seconds inside it) per span."""
        return [((e - s) * 1e-9, self.busy_s(s, e))
                for _, s, e in self.spans_named(name)]

    def launched_in(self, name: str) -> List[Tuple[float, float, float]]:
        """Per ``name`` span: (span seconds, device seconds of the
        programs dispatched inside it, seconds from the span's start
        until the last of them or the span ends, whichever is later),
        averaged over the devices."""
        n_dev = max(1, len(self.busy))
        out = []
        for _, s, e in self.spans_named(name):
            i = bisect.bisect_left(self._dispatch_starts, s)
            j = bisect.bisect_left(self._dispatch_starts, e)
            mods = [m for p in self.launched[i:j] for m in p]
            dev = sum(m[2] - m[1] for m in mods) / n_dev
            end = max([e] + [m[2] for m in mods])
            out.append(((e - s) * 1e-9, dev * 1e-9, (end - s) * 1e-9))
        return out

    def programs(self) -> Dict[str, List[float]]:
        """Per program name in the window: [dispatches, device seconds
        paired with them, device seconds of modules left unpaired]."""
        out: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0, 0.0])
        for (prog, s, _), mods in zip(self.dispatches, self.launched):
            if self.w0 <= s < self.w1:
                out[prog][0] += 1
                out[prog][1] += sum(m[2] - m[1] for m in mods) * 1e-9
        for prog, sec in self.unpaired.items():
            out[prog][2] += sec
        return dict(out)

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` device operations that took most time in the
        window, summed by name and averaged over devices."""
        tot: Dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for name, s, e in evs:
                tot[name] += max(0.0, min(e, self.w1) - max(s, self.w0))
        n = max(1, len(self.ops))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, t / n * 1e-9] for name, t in top if t > 0]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Device-idle seconds in the window, summed by the innermost
        host event (on the harness's thread) at each gap's middle; the
        ``k`` largest."""
        merged = union([("", s, e) for b in self.busy.values()
                        for s, e in b])
        gaps, at = [], self.w0
        for s, e in merged:
            if s > at:
                gaps.append((at, min(s, self.w1)))
            at = max(at, e)
            if at >= self.w1:
                break
        if at < self.w1:
            gaps.append((at, self.w1))
        host = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in self._host_line.events)
                      if self._host_line is not None else (),
                      key=lambda h: (h[1], -h[2]))
        # events of one thread nest, so a stack swept along the gaps'
        # midpoints holds the events open at each one, innermost on top
        tot: Dict[str, float] = defaultdict(float)
        stack: List[Span] = []
        j = 0
        for lo, hi in gaps:
            mid = (lo + hi) / 2
            while j < len(host) and host[j][1] <= mid:
                while stack and stack[-1][2] <= host[j][1]:
                    stack.pop()
                stack.append(host[j])
                j += 1
            while stack and stack[-1][2] <= mid:
                stack.pop()
            tot[stack[-1][0] if stack else "host: no event"] += hi - lo
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, t * 1e-9] for name, t in top]
