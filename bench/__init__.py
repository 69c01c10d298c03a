"""On-chip benchmark of the feature engine (``python3 bench/run.py``).

Everything that measures lives here: traffic generation, the plain
reference that decides ``correct``, the reduction from profiler traces
to metrics and the table of device peaks.  ``BENCHMARK.json`` at the
root of the checkout names the cells; each cell's configuration,
traffic mix and per-layer metric readers are found by name under
``configs/``, ``traffic/`` and ``metrics/``.
"""
