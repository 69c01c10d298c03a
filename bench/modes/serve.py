"""Open-loop serving window: requests on a fixed Poisson schedule, an
optional writer that keeps the ingest queue full, one thread.

The generator runs in the loop's own thread.  Each request is submitted
with ``now=`` its due time, and timed from its due time until its
features come back, so a stall of the loop counts against every request
it delays.  How late the generator submitted is printed.  A shed request
(``AdmissionError``) counts as failed and as slower than any limit.

Traffic keys: ``rate_per_s``, ``batch_size``, ``max_wait_ms``,
``slo_ms``, ``max_queue``, ``ingest_queue_rows``, ``ingest`` (null, or
``batch_rows``, ``tables`` in rotation, ``max_batches``),
``sample_requests``, ``trace_seconds``, ``drain_s``.
"""

from __future__ import annotations

import math
import time
from typing import Dict

import numpy as np

from bench import compare
from bench.harness import BenchError, Outcome


def arrivals(rate: float, n: int, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of ``n`` requests: a
    Poisson process whose gaps are the same ``n`` exponential quantiles
    for every seed, in an order drawn from the seed."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return np.cumsum(np.random.default_rng([seed, 9]).permutation(gaps))


def _wait_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        if left > 2e-3:
            time.sleep(left - 1e-3)


class Writer:
    """Keeps the loop's ingest queue topped up to its bound with
    pre-built batches, and counts what was enqueued."""

    def __init__(self, mix, batches, client_rows):
        spec = mix["ingest"]
        self.tables = spec["tables"]
        self.rows = client_rows
        self.batches = batches
        self.batch_rows = spec["batch_rows"]
        self.bound = mix["ingest_queue_rows"]
        self.sent = 0           # batches enqueued
        self.sent_rows = 0

    def table(self, j: int) -> str:
        return self.tables[j % len(self.tables)]

    def send_one(self, loop, now: float) -> None:
        loop.ingest(self.table(self.sent), self.rows[self.sent], now=now)
        self.sent_rows += len(self.rows[self.sent])
        self.sent += 1

    def top_up(self, loop, applied_rows: int, now: float) -> None:
        while (self.sent < len(self.rows) and
               self.sent_rows - applied_rows + self.batch_rows <= self.bound):
            self.send_one(loop, now)

    def batches_sent(self):
        """``(table, columns)`` of every batch enqueued, in apply order."""
        return [(self.table(j), self.batches[j]) for j in range(self.sent)]


def run(ctx) -> Outcome:
    import jax

    from bench import system

    cfg, mix, seed, data = ctx.cfg, ctx.mix, ctx.seed, ctx.data
    ref_mod = ctx.reference
    seconds = min(ctx.seconds, mix["trace_seconds"]) if ctx.trace \
        else ctx.seconds
    rate = float(mix["rate_per_s"])
    batch = int(mix["batch_size"])

    # ---- traffic, all from the seed, before the program sees any ----
    t_phase = time.perf_counter()
    hist = data.history(cfg, seed)
    ing = mix.get("ingest")
    n_ing_batches = ing["max_batches"] if ing else 0
    ing_rows = n_ing_batches * (ing["batch_rows"] if ing else 0)
    batches = (data.ingest_stream(cfg, seed, n_ing_batches,
                                  ing["batch_rows"], ing["tables"])
               if ing else [])
    n_req = int(math.ceil(rate * seconds * 1.25)) + 2 * batch
    req = data.requests(cfg, seed, n_req, ing_rows)
    # a sharded engine's warm-up wants a full batch's worth on one shard
    warm = data.requests(cfg, seed, 2 * batch * cfg.get("shards", 1),
                         ing_rows, stream=3)
    due_off = arrivals(rate, n_req, seed)
    rows = data.client_rows(req)
    warm_rows = data.client_rows(warm)
    writer = (Writer(mix, batches, [data.client_rows(b) for b in batches])
              if ing else None)
    sample = _answers_to_keep(ctx, hist, req, rate * seconds)
    keep = set(sample.tolist())

    # ---- the deployment ----------------------------------------------
    print(f"compile cache: {system.enable_compile_cache()}", flush=True)
    tables = system.make_tables(data.SCHEMA, data.DICTS, hist)
    t_data = time.perf_counter()
    engine = system.build_engine(ctx.cfg["sql"], tables, cfg, ctx.devices)
    for name, t in tables.items():
        engine.bulk_load(name, t)
    loop = system.build_loop(engine, mix)
    placed = system.store_devices(engine)
    print(f"store arrays on {len(placed)} devices: "
          f"{sorted(str(d) for d in placed)}", flush=True)
    if "shards" in cfg and placed != set(ctx.devices):
        raise BenchError(f"the store of {cfg['shards']} shards lies on "
                         f"{len(placed)} devices, not one per chip")
    t_load = time.perf_counter()

    # ---- warm every shape the window uses ------------------------------
    classes = system.serving_pad_classes(engine, batch)
    print(f"request pad classes to warm (static retrace bound): {classes}",
          flush=True)
    for idx in system.warm_batches(engine, warm[ref_mod.KEY_COLUMN],
                                   classes):
        for j in idx:
            loop.submit(warm_rows[j], now=time.perf_counter())
        loop.flush(now=time.perf_counter())
    if writer is not None:
        # applies until every table of the rotation has had one: the
        # merge compiles per table (its columns)
        seen = set()
        while seen != set(writer.tables):
            seen.add(writer.table(writer.sent))
            writer.send_one(loop, time.perf_counter())
            loop.step(now=time.perf_counter())
    jax.block_until_ready(engine.store.tables)
    loop.results.clear()
    t_warm = time.perf_counter()

    # ---- the window ----------------------------------------------------
    n = n_req
    done = np.full(n, np.nan)
    flush_at = np.full(n, np.nan)
    acked_at = np.zeros(n, np.int64)
    lag = np.zeros(n)
    shed = np.zeros(n, bool)
    results: Dict[int, dict] = {}
    rid_of: Dict[int, int] = {}
    flushes = []        # (start, end, request indices)
    applies = []        # (start, end, rows)
    setup_s = time.perf_counter() - ctx.t_start
    print(f"setup_s={setup_s} (start to traffic "
          f"{t_phase - ctx.t_start}, traffic and tables {t_data - t_phase}, "
          f"engine and bulk_load {t_load - t_data}, warm-up "
          f"{t_warm - t_load})",
          flush=True)

    def submit_due(upto: float, now: float, i: int) -> int:
        while i < n and due[i] <= upto:
            try:
                rid_of[loop.submit(rows[i], now=due[i])] = i
            except system.AdmissionError:
                shed[i] = True
            lag[i] = now - due[i]
            i += 1
        return i

    def step(now: float, record: bool) -> bool:
        applied = loop.stats["ingest_rows"]
        if loop.batcher.ready(now):
            t0 = time.perf_counter()
            with ctx.annotate("bench.flush"):
                out = loop.step(now=now)
            t1 = time.perf_counter()
            idx = []
            for rid, f in out.items():
                # a client that has its answer drops it from the loop
                loop.results.pop(rid, None)
                i = rid_of.pop(rid)
                done[i], flush_at[i], acked_at[i] = t1, t0, applied
                if i in keep:
                    results[i] = f
                idx.append(i)
            if record:
                flushes.append((t0, t1, idx))
            return True
        if writer is not None and writer.sent_rows > applied:
            t0 = time.perf_counter()
            with ctx.annotate("bench.ingest"):
                loop.step(now=now)
            t1 = time.perf_counter()
            if record:
                applies.append((t0, t1, loop.stats["ingest_rows"] - applied))
            return True
        return False

    with ctx.window():
        with ctx.annotate("bench.window"):
            t_win = time.perf_counter() + 1e-3
            due = t_win + due_off
            t_end = t_win + seconds
            _wait_until(t_win)
            i = 0
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    break
                i = submit_due(now, now, i)
                if writer is not None:
                    writer.top_up(loop, loop.stats["ingest_rows"], now)
                if not step(time.perf_counter(), True):
                    nxt = min(due[i] if i < n else t_end,
                              loop.batcher.next_flush_at(), t_end)
                    _wait_until(nxt)
            t_stop = time.perf_counter()
            # the applies dispatched in the window, finished on the device
            jax.block_until_ready(engine.store.tables)
            t_applied = time.perf_counter()
        # requests due in the window are all submitted and answered; the
        # writer stops
        i = submit_due(t_end, time.perf_counter(), i)
        drain_end = time.perf_counter() + mix["drain_s"]
        while rid_of and time.perf_counter() < drain_end:
            now = time.perf_counter()
            if not step(now, False):
                _wait_until(min(loop.batcher.next_flush_at(), drain_end))
    n_due = int((due < t_end).sum())
    memory_peak = ctx.memory_peak_bytes()

    lat_ms = (done[:n_due] - due[:n_due]) * 1e3
    # a shed or unanswered request is slower than any answer can be
    ceiling_ms = (seconds + mix["drain_s"]) * 1e3
    lat_ms = np.where(np.isnan(lat_ms), ceiling_ms, lat_ms)
    unanswered = int((~shed[:n_due] & np.isnan(done[:n_due])).sum())
    n_shed = int(shed[:n_due].sum())
    p99 = _nearest_rank(lat_ms, 0.99)
    applied_in = sum(r for _, _, r in applies)
    print(f"requests_due={n_due} shed={n_shed} unanswered={unanswered} "
          f"p50_ms={_nearest_rank(lat_ms, 0.5)} "
          f"p95_ms={_nearest_rank(lat_ms, 0.95)} p99_ms={p99} "
          f"max_ms={_nearest_rank(lat_ms, 1.0)} (shed and unanswered at "
          f"{ceiling_ms})",
          flush=True)
    half = due[:n_due] < t_win + seconds / 2
    halves = [_nearest_rank(lat_ms[m], 0.99) for m in (half, ~half)]
    print(f"p99_ms by half of the window: {halves}", flush=True)
    print(f"generator_lag_ms p50={float(np.median(lag[:n_due]) * 1e3)} "
          f"p99={float(np.quantile(lag[:n_due], 0.99) * 1e3)} "
          f"max={float(lag[:n_due].max() * 1e3)}", flush=True)
    steps = ([(t0, t1, "flush") for t0, t1, _ in flushes]
             + [(t0, t1, "apply") for t0, t1, _ in applies])
    longest = sorted(steps, key=lambda f: f[0] - f[1])[:5]
    longest = [(round(t0 - t_win, 3), round((t1 - t0) * 1e3, 1), kind)
               for t0, t1, kind in longest]
    print(f"longest steps (s into the window, ms, kind): {longest}",
          flush=True)
    print(f"flushes={len(flushes)} ingest_applies={len(applies)} "
          f"ingest_rows={applied_in} window_s={t_stop - t_win} "
          f"loop_stats={loop.stats}", flush=True)

    # ---- correctness: a sample of the answers, against the reference ----
    sample = sample[~np.isnan(done[sample])]
    applied_batches = writer.batches_sent() if writer else []
    t0 = time.perf_counter()
    reference = ref_mod.Reference(hist, applied_batches)
    q = {c: v[sample] for c, v in req.items()}
    want = reference.features(q, acked_at[sample])
    got = compare.stack_rows([results[i] for i in sample],
                             ref_mod.FEATURES)
    detail: dict = {}
    exact_bad, gap = compare.against_reference(got, want, ref_mod.FEATURES,
                                               detail)
    print(f"per feature: {detail}", flush=True)
    control = None
    if ctx.control:
        low = ref_mod.Reference(hist, applied_batches, lowp=True)
        control = compare.against_reference(
            compare.as_returned(low.features(q, acked_at[sample])), want,
            ref_mod.FEATURES)
    checks = {"exact_mismatch": (exact_bad, 0),
              "float_gap": (gap, cfg["limits"]["float_gap"]),
              "unanswered": (unanswered, 0)}
    print(f"reference: {len(sample)} sampled requests in "
          f"{time.perf_counter() - t0} s", flush=True)
    checks["offline_mismatch"] = (_offline_check(
        ctx, engine, tables, req, sample, acked_at[sample], results,
        applied_batches), 0)

    wait_ms = (flush_at[:n_due] - due[:n_due]) * 1e3
    layer = {"queue_wait_ms": np.where(np.isnan(wait_ms), ceiling_ms,
                                       wait_ms),
             "latency_ms": lat_ms,
             "loop_stats": dict(loop.stats), "batch_size": batch,
             "control": control, "p99_halves": halves,
             "devices": len(ctx.devices)}
    if ctx.trace:
        in_flush = np.asarray([i for _, _, idx in flushes for i in idx],
                              np.int64)
        qf = {c: v[in_flush] for c, v in req.items()}
        layer["flush_bytes"] = float(ref_mod.request_bytes(
            reference.window_rows(qf, acked_at[in_flush])).sum())
        if "shards" in cfg:
            # per flush, the requests routed to each shard
            owner = system.key_owners(engine, req[ref_mod.KEY_COLUMN])
            layer["shard_counts"] = np.stack(
                [np.bincount(owner[idx], minlength=cfg["shards"])
                 for _, _, idx in flushes])
    metrics = {"p99_ms": p99, "setup_s": setup_s}
    if writer is not None:
        metrics["ingest_rows_per_s"] = applied_in / (t_applied - t_win)
    return Outcome(metrics=metrics, attempted=n_due,
                   failed=n_shed + unanswered, checks=checks,
                   memory_peak_bytes=memory_peak, layer=layer)


def _nearest_rank(values: np.ndarray, q: float) -> float:
    """The nearest-rank ``q`` quantile (0 of nothing)."""
    if len(values) == 0:
        return 0.0
    v = np.sort(values)
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def _answers_to_keep(ctx, hist, req, expected: float) -> np.ndarray:
    """Which answers to keep for the comparison, drawn from the seed
    before the window: ``sample_requests`` of the requests certainly due
    in it, with the one of the user whose history is longest."""
    ref_mod, key = ctx.reference, ctx.reference.KEY_COLUMN
    n_sure = max(1, min(len(req[key]), int(0.9 * expected)))
    rng = np.random.default_rng([ctx.seed, 11])
    k = min(ctx.mix["sample_requests"], n_sure)
    hist_n = np.bincount(hist[ref_mod.BASE_TABLE][key],
                         minlength=ctx.cfg["n_users"])
    longest = int(np.argmax(hist_n[req[key][:n_sure]]))
    return np.unique(np.append(rng.choice(n_sure, k, replace=False),
                               longest))


def _offline_check(ctx, engine, tables, req, sample, acked, results,
                   applied) -> int:
    """The deployment's guarantee, ``served_equals_offline``: rows of
    served answers that are not bit for bit what ``offline()`` gives for
    the same rows.

    One ``offline()`` call over the users of the sampled answers, one
    answer per user (the first drawn): each user's history, the ingest
    rows the engine had acknowledged when that answer's batch launched
    (``acked``, counted across ``applied`` in apply order), and the
    request itself as the last row of the base table.  Every function of
    the script reads only the rows of its own key, so the other users'
    rows change nothing of these features."""
    ref_mod = ctx.reference
    key, base = ref_mod.KEY_COLUMN, ref_mod.BASE_TABLE
    users, first = np.unique(req[key][sample], return_index=True)
    idx, ack = sample[first], acked[first]
    parts = {name: [{c: v[np.isin(t.columns[key], users)]
                     for c, v in t.columns.items()}]
             for name, t in tables.items()}
    at = 0
    for name, cols in applied:
        n = len(cols[key])
        u = cols[key]
        pos = np.searchsorted(users, u).clip(0, len(users) - 1)
        keep = (users[pos] == u) & (at + np.arange(n) < ack[pos])
        parts[name].append({c: v[keep] for c, v in cols.items()})
        at += n
    n_base = sum(len(p[key]) for p in parts[base])
    parts[base].append({c: req[c][idx] for c in parts[base][0]})
    off_tables = {}
    for name, t in tables.items():
        cols = {c: np.concatenate([p[c] for p in parts[name]]).astype(
            t.columns[c].dtype) for c in t.columns}
        off_tables[name] = type(t)(t.schema, cols, dicts=t.dicts)
    t0 = time.perf_counter()
    off = engine.offline(off_tables)
    rows = n_base + np.arange(len(idx))
    got = compare.stack_rows([results[i] for i in idx], ref_mod.FEATURES)
    want = {k: np.asarray(off[k])[rows] for k in ref_mod.FEATURES}
    bad = compare.bitwise_mismatch(got, want)
    print(f"offline check: {len(idx)} answers of as many users, "
          f"{len(off_tables[base])} {base} rows, offline() "
          f"{time.perf_counter() - t0} s, {bad} rows differ", flush=True)
    return bad
