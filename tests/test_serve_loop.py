"""Deterministic serving-loop harness tests (ISSUE 7).

Everything time-dependent runs under an injected ``VirtualClock``, so
batching, admission, SLO, and snapshot-swap behavior are asserted as
exact properties in tier-1 — not observed in benchmarks.  Includes the
deterministic twin of the hypothesis batcher properties
(tests/test_batcher_props.py) and the record/replay bitwise gates.
"""

import math

import numpy as np
import pytest

from repro.core import verify_consistency
from repro.data.synthetic import make_action_tables
from repro.serve import (AdmissionError, FeatureEngine, RequestBatcher,
                         ServeLoop, VirtualClock)
from repro.serve.trace import (load_trace, outputs_in_base_order,
                               record_consistency_trace, replay,
                               save_trace, store_state_arrays)

RAW_SQL = """
SELECT sum(price) OVER w AS s, count(price) OVER w AS c,
       max(price) OVER w AS mx, min(price) OVER w AS mn
FROM actions
WINDOW w AS (PARTITION BY userid ORDER BY ts
             ROWS_RANGE BETWEEN 10s PRECEDING AND CURRENT ROW)
"""


def _tables(n=60, seed=3, users=4, horizon=600_000, int_prices=False):
    tables = make_action_tables(n_actions=n, n_orders=0, n_users=users,
                                horizon_ms=horizon, seed=seed,
                                with_profile=False)
    if int_prices:
        for t in tables.values():
            t.columns["price"] = np.floor(t.columns["price"]).astype(
                np.float32)
    return tables


@pytest.fixture(scope="module")
def loop_tables():
    return _tables()


@pytest.fixture(scope="module")
def int_tables():
    return _tables(int_prices=True)


# ===================================================================
# deadline-aware batcher — deterministic twin of test_batcher_props.py
# ===================================================================

def test_batcher_deadline_pulls_flush_forward():
    b = RequestBatcher(batch_size=8, max_wait_ms=5.0, slo_ms=50.0)
    b.submit("a", now=0.0)                       # flush at 0.005 (wait)
    assert not b.ready(now=0.004)
    assert b.ready(now=0.005)                    # staleness bound hit
    b.next_batch(now=0.005)
    # a tight per-request deadline beats max_wait
    b.submit("b", now=1.0, deadline_ms=2.0)
    assert b.next_flush_at() == pytest.approx(1.002)
    assert not b.ready(now=1.001)
    assert b.ready(now=1.002)
    ids, payloads, n_real = b.next_batch(now=1.002)
    assert n_real == 1 and len(payloads) == 8
    assert b.deadline_flushes == 2 and b.size_flushes == 0


def test_batcher_count_only_mode_never_time_flushes():
    b = RequestBatcher(batch_size=4, max_wait_ms=None)
    b.submit("a", now=0.0)
    assert not b.ready(now=1e9)                  # no flush point at all
    assert math.isinf(b.next_flush_at())
    for p in "bcd":
        b.submit(p, now=1e9)
    assert b.ready(now=1e9)                      # count flush still works
    _, _, n = b.next_batch(now=1e9)
    assert n == 4 and b.size_flushes == 1


def test_batcher_invariants_deterministic_interleaving():
    """Deterministic twin of the hypothesis properties: id order,
    staleness bound at decision time, padded-slot accounting."""
    b = RequestBatcher(batch_size=3, max_wait_ms=10.0, slo_ms=40.0)
    now = 0.0
    emitted, pad_expected = [], 0
    script = [("submit", 0.001), ("submit", 0.002), ("advance", 0.004),
              ("batch",), ("submit", 0.0), ("submit", 0.001),
              ("submit", 0.0), ("batch",), ("advance", 0.02),
              ("submit", 0.0), ("advance", 0.011), ("batch",)]
    for op, *arg in script:
        if op == "submit":
            b.submit(f"p{len(emitted)}", now=now)
            now += arg[0]
        elif op == "advance":
            now += arg[0]
        else:
            # staleness invariant: once the oldest queued request is
            # max_wait old, ready() MUST be true; conversely ready()
            # False implies the oldest is younger than max_wait
            if b.queue:
                age_ms = (now - b.queue[0].enqueued_at) * 1e3
                if age_ms >= b.max_wait_ms or len(b.queue) >= b.batch_size:
                    assert b.ready(now=now)
                if not b.ready(now=now):
                    assert age_ms < b.max_wait_ms
            ids, payloads, n_real = b.next_batch(now=now)
            if n_real:
                assert len(payloads) == b.batch_size
                pad_expected += b.batch_size - n_real
                emitted.extend(ids)
    assert emitted == sorted(emitted)            # request-id order kept
    assert b.padded_slots == pad_expected        # every slot accounted


# ===================================================================
# serving loop: deadline flush, admission, SLO accounting
# ===================================================================

def _loop(tables, **kw):
    eng = FeatureEngine(RAW_SQL, tables, capacity=512)
    clock = VirtualClock()
    kw.setdefault("slo_ms", 50.0)
    kw.setdefault("max_wait_ms", 5.0)
    kw.setdefault("batch_size", 4)
    loop = ServeLoop(eng, clock=clock, **kw)
    return eng, clock, loop


def test_loop_flushes_on_deadline_not_only_count(loop_tables):
    eng, clock, loop = _loop(loop_tables)
    a = loop_tables["actions"]
    loop.ingest("actions", [a.row(i) for i in range(20)])
    loop.drain_ingest()
    r1 = loop.submit(dict(a.row(30)))
    r2 = loop.submit(dict(a.row(31)))
    assert loop.step() == {}                     # 2 < 4 and fresh
    clock.advance(0.0051)
    out = loop.step()                            # staleness flush
    assert set(out) == {r1, r2}
    assert loop.stats["deadline_flushes"] == 1
    # a full batch flushes immediately, no deadline needed
    rids = [loop.submit(dict(a.row(32))) for _ in range(4)]
    out = loop.step()
    assert set(out) == set(rids)
    assert loop.stats["size_flushes"] == 1
    # scalar parity: the loop serves the same bytes as direct calls
    ref = eng.request_batch([dict(a.row(30))])[0]
    for k in ref:
        np.testing.assert_array_equal(np.asarray(loop.results[r1][k]),
                                      np.asarray(ref[k]))


def test_loop_admission_sheds_with_typed_error(loop_tables):
    eng, clock, loop = _loop(loop_tables, max_queue=3, batch_size=8)
    a = loop_tables["actions"]
    rids = [loop.submit(dict(a.row(i))) for i in range(3)]
    n_before = eng.n_requests
    with pytest.raises(AdmissionError) as ei:
        loop.submit(dict(a.row(3)))
    assert ei.value.queued == 3 and ei.value.max_queue == 3
    assert loop.stats["shed"] == 1
    out = loop.run_until_idle()
    # shed request never reached the fold path: only admitted requests
    # were computed and only their ids have results
    assert sorted(out) == sorted(rids)
    assert eng.n_requests == n_before + 3
    assert loop.stats["served"] == 3


def test_loop_slo_miss_accounting(loop_tables):
    eng, clock, loop = _loop(loop_tables, slo_ms=10.0,
                             service_model=lambda n: 2.0)
    a = loop_tables["actions"]
    loop.submit(dict(a.row(0)))                  # deadline at t=10ms
    clock.advance(0.009)                         # flush at 9ms
    loop.step()                                  # +2ms service = 11ms
    assert loop.stats["deadline_misses"] == 1
    assert loop.latency_percentiles()["TP50"] == pytest.approx(11.0)
    loop.submit(dict(a.row(1)))
    clock.advance(0.006)                         # 6ms + 2ms = 8ms < SLO
    loop.step()
    assert loop.stats["deadline_misses"] == 1
    assert loop.latency_percentiles()["max_ms"] == pytest.approx(11.0)


# ===================================================================
# snapshot double buffer
# ===================================================================

def test_store_snapshot_is_immutable_view(loop_tables):
    eng = FeatureEngine(RAW_SQL, loop_tables, capacity=512)
    a = loop_tables["actions"]
    eng.ingest_many("actions", [a.row(i) for i in range(10)])
    snap = eng.store.snapshot()
    n0 = snap.n_rows("actions")
    eng.ingest_many("actions", [a.row(i) for i in range(10, 30)])
    assert eng.store.n_rows("actions") == 30
    assert snap.n_rows("actions") == n0          # frozen
    v = snap.version
    snap.refresh()
    assert snap.version == v + 1
    assert snap.n_rows("actions") == 30          # atomic re-cut


def test_inflight_requests_not_stalled_or_dirtied_by_ingest(int_tables):
    """The snapshot-swap gate: requests queued before a bulk
    ingest_many + compaction serve EXACTLY the bytes they would have
    with no concurrent write — and the write becomes visible only
    after the swap.  Integer-valued prices keep float sums exact
    through the compaction anchor move, so EXACT means bitwise."""
    tables = int_tables
    a = tables["actions"]
    history = [a.row(i) for i in range(20)]
    late = [a.row(i) for i in range(20, 50)]
    probe = [dict(a.row(55)), dict(a.row(56))]

    # reference: an engine that never sees the late ingest
    ref = FeatureEngine(RAW_SQL, tables, capacity=512)
    ref.ingest_many("actions", history)
    want = ref.request_batch([dict(r) for r in probe])

    eng = FeatureEngine(RAW_SQL, tables, capacity=512, retention="auto",
                        compact_every=8)          # compaction fires too
    clock = VirtualClock()
    loop = ServeLoop(eng, clock=clock, batch_size=2, max_wait_ms=5.0)
    loop.ingest("actions", history)
    loop.drain_ingest()
    rids = [loop.submit(dict(r)) for r in probe]  # queued, in flight
    # a bulk write + retention/compaction lands while they wait
    loop.ingest("actions", late)
    # requests outrank ingest: the full batch flushes FIRST, from the
    # pre-ingest snapshot (the live store already has pending writes
    # queued behind it, plus compaction when applied)
    out = loop.step()
    assert set(out) == set(rids)
    assert loop.stats["ingest_applies"] == 1      # only the history
    for got, ref_f in zip([out[r] for r in rids], want):
        for k in ref_f:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref_f[k]), err_msg=k)
    # after the swap, the same probe sees the late rows
    swaps = loop.stats["snapshot_swaps"]
    loop.run_until_idle()
    assert loop.stats["snapshot_swaps"] == swaps + 1
    ref.ingest_many("actions", late)
    want2 = ref.request_batch([dict(r) for r in probe])
    rids2 = [loop.submit(dict(r)) for r in probe]
    out2 = loop.step()
    for got, ref_f in zip([out2[r] for r in rids2], want2):
        for k in ref_f:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref_f[k]), err_msg=k)


def test_ingest_backpressure_applies_inline(loop_tables):
    eng, clock, loop = _loop(loop_tables, ingest_queue_rows=16)
    a = loop_tables["actions"]
    loop.ingest("actions", [a.row(i) for i in range(10)])
    assert loop.stats["ingest_applies"] == 0      # buffered
    loop.ingest("actions", [a.row(i) for i in range(10, 30)])
    # 30 rows > 16: the WRITER paid — queue drained inline
    assert loop.stats["backpressure_applies"] >= 1
    assert loop._ingest_q_rows <= 16
    assert eng.store.n_rows("actions") >= 10


def test_sharded_loop_snapshot_parity(loop_tables):
    a = loop_tables["actions"]
    rows = [a.row(i) for i in range(30)]
    probe = [dict(a.row(40 + i)) for i in range(4)]
    ref = FeatureEngine(RAW_SQL, loop_tables, capacity=512)
    ref.ingest_many("actions", rows)
    want = ref.request_batch([dict(r) for r in probe])
    eng = FeatureEngine(RAW_SQL, loop_tables, capacity=512, n_shards=2)
    loop = ServeLoop(eng, clock=VirtualClock(), batch_size=4)
    loop.ingest("actions", rows)
    loop.drain_ingest()
    rids = [loop.submit(dict(r)) for r in probe]
    out = loop.step()
    for rid, ref_f in zip(rids, want):
        for k in ref_f:
            np.testing.assert_array_equal(np.asarray(out[rid][k]),
                                          np.asarray(ref_f[k]), err_msg=k)


# ===================================================================
# record / replay
# ===================================================================

def test_trace_replay_bitwise_with_eviction(int_tables, tmp_path):
    """Record a mixed request/ingest/compaction trace, replay it twice
    (through a JSON roundtrip), assert outputs AND final store state
    are bitwise identical, and gate the replayed outputs against
    offline() via verify_consistency(bitwise=True)."""
    tables = int_tables

    def factory():
        return FeatureEngine(RAW_SQL, tables, capacity=256,
                             retention="auto", compact_every=16)

    eng = factory()
    loop0, events, rids = record_consistency_trace(eng, tables)
    # the trace really contained evictions (compaction mid-trace)
    assert eng.store.n_rows("actions") < len(tables["actions"])

    path = str(tmp_path / "trace.json")
    save_trace(events, path)
    events2 = load_trace(path)
    kw = dict(batch_size=1, max_wait_ms=0.0, slo_ms=1e6)
    lp1 = replay(events2, factory, **kw)
    lp2 = replay(events2, factory, **kw)

    cs = eng.cs
    out0 = outputs_in_base_order(loop0, rids, tables, cs)
    out1 = outputs_in_base_order(lp1, rids, tables, cs)
    out2 = outputs_in_base_order(lp2, rids, tables, cs)
    for k in out1:
        np.testing.assert_array_equal(out1[k], out2[k], err_msg=k)
        np.testing.assert_array_equal(out0[k], out1[k], err_msg=k)
    for (pa, xa), (pb, xb) in zip(store_state_arrays(lp1.engine),
                                  store_state_arrays(lp2.engine)):
        assert pa == pb
        np.testing.assert_array_equal(xa, xb, err_msg=pa)
    # the replayed serving trace is held to the paper's headline gate
    rep = verify_consistency(cs, tables, bitwise=True,
                             online_outputs=out1)
    assert rep.passed and rep.bitwise_equal, str(rep)


def test_replay_reproduces_shedding_decisions(loop_tables):
    a = loop_tables["actions"]

    def factory():
        return FeatureEngine(RAW_SQL, loop_tables, capacity=512)

    from repro.serve.trace import TraceRecorder
    rec = TraceRecorder()
    clock = VirtualClock()
    loop = ServeLoop(factory(), clock=clock, recorder=rec, batch_size=8,
                     max_queue=2, max_wait_ms=5.0)
    shed = 0
    for i in range(4):
        try:
            loop.submit(dict(a.row(i)), now=clock.now())
        except AdmissionError:
            shed += 1
    clock.advance(0.01)
    loop.step()
    assert shed == 2
    lp2 = replay(rec.events, factory, batch_size=8, max_queue=2,
                 max_wait_ms=5.0)
    assert lp2.stats["shed"] == shed
    assert sorted(lp2.results) == sorted(loop.results)


# ===================================================================
# latency-stats hygiene (satellite: pollution fix + edge cases)
# ===================================================================

def test_latency_stats_requests_only(loop_tables):
    """Regression: ingest timing must never appear in (or deflate) the
    request percentiles; request samples are real completion times, not
    amortized dt/B shares."""
    eng = FeatureEngine(RAW_SQL, loop_tables, capacity=512)
    a = loop_tables["actions"]
    eng.ingest_many("actions", [a.row(i) for i in range(25)])
    eng.ingest("actions", a.row(25))
    assert eng.latency_percentiles() == {}        # ingest left no samples
    ist = eng.ingest_stats()
    assert ist["rows"] == 26 and ist["calls"] == 2
    assert ist["TP99"] >= ist["TP50"] > 0
    feats = eng.request_batch([dict(a.row(30 + i)) for i in range(6)])
    assert len(feats) == 6
    samples = list(eng.latencies_ms)
    assert len(samples) == 6
    # one batch -> one real completion time shared by all its requests
    assert len(set(samples)) == 1 and samples[0] > 0
    eng.reset_stats()
    assert eng.latency_percentiles() == {} and eng.ingest_stats() == {}
    assert eng.rows_ingested == 0


def test_latency_percentile_edge_cases(loop_tables):
    eng = FeatureEngine(RAW_SQL, loop_tables, capacity=512,
                        latency_window=8)
    assert eng.latency_percentiles() == {}        # empty -> {}, no keys
    a = loop_tables["actions"]
    for _ in range(3):
        eng.request_batch([dict(a.row(i)) for i in range(4)])
    assert len(eng.latencies_ms) == 8             # deque bounded
    assert len(eng.ingest_ms) <= 8
    pct = eng.latency_percentiles()
    assert set(pct) == {"TP50", "TP90", "TP95", "TP99"}
    loop = ServeLoop(eng, clock=VirtualClock())
    assert loop.latency_percentiles() == {}       # loop: same contract


def test_loop_latencies_bounded_like_the_engine(loop_tables):
    """The loop keeps the same number of recent samples as its engine,
    not one per request for the life of the loop."""
    eng = FeatureEngine(RAW_SQL, loop_tables, capacity=512,
                        latency_window=8)
    loop = ServeLoop(eng, clock=VirtualClock(), batch_size=4)
    a = loop_tables["actions"]
    for rnd in range(3):
        for i in range(4):
            loop.submit(dict(a.row(4 * rnd + i)))
        loop.flush()
    assert loop.stats["served"] == 12
    assert len(loop.latencies) == 8
    assert set(loop.latency_percentiles()) == {"TP50", "TP99", "TP999",
                                               "max_ms"}


def test_fused_loop_no_retrace_across_snapshot_swaps(loop_tables):
    """Executable reuse (ISSUE 9 tentpole c): under ServeLoop traffic a
    fused engine serves every batch through the megakernel fast path,
    and snapshot refreshes / repeated flushes of the same pad class
    never retrace — one jitted executable per (B-pad, backend) class,
    traced exactly once."""
    eng = FeatureEngine(RAW_SQL, loop_tables, capacity=512,
                        fused_fold=True)
    clock = VirtualClock()
    loop = ServeLoop(eng, clock=clock, batch_size=4, max_wait_ms=5.0,
                     slo_ms=50.0)
    a = loop_tables["actions"]
    loop.ingest("actions", [a.row(i) for i in range(20)])
    loop.drain_ingest()
    for rnd in range(3):
        # a full batch (B=4) and a partial flush (pads to B=2): two
        # pad classes, both revisited every round
        for i in range(4):
            loop.submit(dict(a.row(20 + 4 * rnd + i)))
        loop.step()
        loop.submit(dict(a.row(40 + rnd)))
        loop.submit(dict(a.row(44 + rnd)))
        clock.advance(0.0051)
        loop.step()
        # a bulk write + snapshot swap between rounds
        loop.ingest("actions", [a.row(47 + rnd)])
        loop.run_until_idle()
    assert loop.stats["snapshot_swaps"] >= 3
    fast_fns = {k: fn for k, fn in eng.cs._online_fns.items()
                if "online_fast" in k}
    # the fused engine actually routed through the fast path: one
    # cached executable per pad class (B=4 and B=2), each traced once
    assert len(fast_fns) == 2, sorted(fast_fns)
    for key, fn in fast_fns.items():
        assert fn._cache_size() == 1, (key, fn._cache_size())
    # staged batch driver never engaged
    assert not any(k[2] == "online_batch" for k in eng.cs._online_fns)
