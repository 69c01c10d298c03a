"""Named spans of the engine's host work, written into the JAX profiler's
trace.

``span("serve.flush")`` is a ``jax.profiler.TraceAnnotation`` named
``repro.serve.flush``; keyword arguments become the event's stats.  The
profiler's trace is the only sink: the spans share the device trace's
clock, and cost one object each while no trace is being recorded.  A
count known only at the span's end, and worth computing only while a
trace records, is added with ``set_metadata``::

    with span("offline.plan.group") as s:
        ...
        if s.is_enabled():
            s.set_metadata(rows=n)

Spans mark host work at the granularity of a call, a batch or a phase
(never a row, a unit or a request) and carry only ints and short
strings.  They stay outside jitted functions, so no program changes.
"""

from __future__ import annotations

import jax

__all__ = ["span"]


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A trace span ``repro.<name>`` with ``args`` as its stats."""
    return jax.profiler.TraceAnnotation("repro." + name, **args)
