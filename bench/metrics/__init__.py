"""Per-layer metric readers, one file per metric named as in
``BENCHMARK.json``.  Each defines ``read(view) -> float | None``: the
metric from ``view.trace`` (``bench.trace.Reduced``), ``view.layer``
(the window mode's records) and ``view.peaks``, or None where the
run has nothing to read it from."""
