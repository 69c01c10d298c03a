"""Backfill window: whole ``FeatureEngine.offline()`` calls, back to
back, until the window's seconds have passed.

Each call gets the history with every timestamp moved by a new amount:
content the engine has not planned before (so each call pays the whole
backfill, planning included), and the same features.  The metric is the
feature rows of all calls over their summed wall time.

Traffic keys: ``sample_rows``, ``trace_seconds``.
"""

from __future__ import annotations

import time

import numpy as np

from bench import compare
from bench.harness import Outcome


def run(ctx) -> Outcome:
    from bench import system

    cfg, mix, seed, data = ctx.cfg, ctx.mix, ctx.seed, ctx.data
    ref_mod = ctx.reference
    seconds = min(ctx.seconds, mix["trace_seconds"]) if ctx.trace \
        else ctx.seconds
    t_phase = time.perf_counter()
    hist = data.history(cfg, seed)
    base, order_col = ref_mod.BASE_TABLE, ref_mod.ORDER_COLUMN
    n_rows = len(hist[base][order_col])

    print(f"compile cache: {system.enable_compile_cache()}", flush=True)
    tables = system.make_tables(data.SCHEMA, data.DICTS, hist)
    t_data = time.perf_counter()
    engine = system.build_engine(ctx.cfg["sql"], tables, cfg, ctx.devices)
    # warm: one whole call compiles every program the calls run
    engine.offline(system.shift_tables(tables, order_col, 0))
    t_warm = time.perf_counter()
    setup_s = time.perf_counter() - ctx.t_start
    print(f"setup_s={setup_s} (start to data {t_phase - ctx.t_start}, "
          f"data and tables {t_data - t_phase}, warm-up offline() "
          f"{t_warm - t_data})",
          flush=True)

    rng = np.random.default_rng([seed, 11])
    sample = np.sort(rng.choice(n_rows, min(mix["sample_rows"], n_rows),
                                replace=False))
    outs = []
    calls = []          # (start, end)
    shift = 1
    with ctx.window(), ctx.annotate("bench.window"):
        t_end = time.perf_counter() + seconds
        while not calls or time.perf_counter() < t_end:
            shifted = system.shift_tables(tables, order_col, shift)
            t0 = time.perf_counter()
            with ctx.annotate("bench.offline"):
                off = engine.offline(shifted)
            t1 = time.perf_counter()
            calls.append((t0, t1))
            outs.append({k: np.asarray(v)[sample] for k, v in off.items()})
            del off, shifted
            shift += 1
    memory_peak = ctx.memory_peak_bytes()
    busy = sum(e - s for s, e in calls)
    call_s = sorted(e - s for s, e in calls)
    print(f"offline_calls={len(calls)} call_s min={call_s[0]} "
          f"median={call_s[len(call_s) // 2]} max={call_s[-1]}", flush=True)

    t0 = time.perf_counter()
    q = {c: v[sample] for c, v in hist[base].items()}
    want = ref_mod.Reference(hist).features(q)
    exact_bad, gap, detail = 0, 0.0, {}
    for got in outs:
        b, g = compare.against_reference(got, want, ref_mod.FEATURES,
                                         detail)
        exact_bad, gap = exact_bad + b, max(gap, g)
    print(f"per feature: {detail}", flush=True)
    control = None
    if ctx.control:
        low = ref_mod.Reference(hist, lowp=True).features(q)
        control = compare.against_reference(compare.as_returned(low), want,
                                            ref_mod.FEATURES)
    print(f"reference: {len(sample)} sampled rows x {len(outs)} calls in "
          f"{time.perf_counter() - t0} s", flush=True)
    checks = {"exact_mismatch": (exact_bad, 0),
              "float_gap": (gap, cfg["limits"]["float_gap"])}
    return Outcome(
        metrics={"backfill_rows_per_s": len(calls) * n_rows / busy,
                 "setup_s": setup_s},
        attempted=len(calls), failed=0, checks=checks,
        memory_peak_bytes=memory_peak,
        layer={"calls": len(calls), "control": control})
