"""Batched online execution: vmapped request path, bulk store ingest,
batched pre-agg maintenance, and the fused unit-fold megakernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compile_script, parse
from repro.core.functions import AddLeaf, DrawdownLeaf, EWLeaf, MaxLeaf
from repro.core.preagg import PreAgg
from repro.core.window import WindowSpec
from repro.data.synthetic import make_action_tables
from repro.serve.batcher import RequestBatcher
from repro.serve.engine import FeatureEngine
from repro.storage import timestore

PREAGG_SQL = """
SELECT sum(price) OVER w AS s, count(price) OVER w AS c,
       min(price) OVER w AS mn, max(price) OVER w AS mx,
       ew_avg(price, 0.5) OVER w AS ew
FROM actions
WINDOW w AS (PARTITION BY userid ORDER BY ts
             ROWS_RANGE BETWEEN 3000s PRECEDING AND CURRENT ROW)
OPTIONS (long_windows = "w:100s")
"""

ADDITIVE_SQL = """
SELECT sum(price) OVER w AS s, avg(price) OVER w AS a,
  count(price) OVER w AS c,
  distinct_count(category) OVER w AS dc,
  avg_cate_where(price, quantity > 1, category) OVER w AS ca,
  price * 2 AS dp
FROM actions
WINDOW w AS (UNION orders PARTITION BY userid ORDER BY ts
             ROWS_RANGE BETWEEN 3s PRECEDING AND CURRENT ROW)
"""


def _encoded_batch(eng, rows):
    need = eng._need[eng.cs.script.base_table]
    keys = [eng._encode("actions", eng.key_col, r[eng.key_col])
            for r in rows]
    ts = [int(r[eng.cs.script.order_column]) for r in rows]
    values = {c: [float(eng._encode("actions", c, r[c])) for r in rows]
              for c in need}
    return keys, ts, values, need


# ------------------------------------------------------- online_batch


def test_online_batch_bitexact_raw(action_tables, micro_sql):
    eng = FeatureEngine(micro_sql, action_tables, capacity=1024)
    o, a = action_tables["orders"], action_tables["actions"]
    eng.ingest_many("orders", [o.row(i) for i in range(60)])
    eng.ingest_many("actions", [a.row(i) for i in range(40)])

    rows = [a.row(100 + i) for i in range(7)]
    keys, ts, values, need = _encoded_batch(eng, rows)
    batch = eng.cs.online_batch(eng.store, keys, ts, values)
    for i in range(len(rows)):
        single = eng.cs.online(eng.store, keys[i], ts[i],
                               {c: values[c][i] for c in need})
        for k in single:
            np.testing.assert_array_equal(
                np.asarray(batch[k][i]), np.asarray(single[k]), err_msg=k)


def test_online_batch_bitexact_preagg():
    tables = make_action_tables(n_actions=200, n_orders=0, n_users=4,
                                horizon_ms=12_000_000, seed=4,
                                with_profile=False)
    eng = FeatureEngine(PREAGG_SQL, tables, capacity=512, use_preagg=True)
    a = tables["actions"]
    eng.ingest_many("actions", [a.row(i) for i in range(120)])

    rows = [a.row(150 + i) for i in range(5)]
    keys, ts, values, need = _encoded_batch(eng, rows)
    batch = eng.cs.online_batch(eng.store, keys, ts, values,
                                preagg_states=eng.pre_states)
    for i in range(len(rows)):
        single = eng.cs.online(eng.store, keys[i], ts[i],
                               {c: values[c][i] for c in need},
                               preagg_states=eng.pre_states)
        for k in single:
            np.testing.assert_array_equal(
                np.asarray(batch[k][i]), np.asarray(single[k]), err_msg=k)


def test_online_batch_with_last_join(action_tables):
    sql = """
    SELECT price, profile.age AS age, sum(price) OVER w AS s
    FROM actions
    LAST JOIN profile ORDER BY ts ON actions.userid = profile.userid
    WINDOW w AS (PARTITION BY userid ORDER BY ts
                 ROWS_RANGE BETWEEN 5s PRECEDING AND CURRENT ROW)
    """
    eng = FeatureEngine(sql, action_tables, capacity=1024)
    p, a = action_tables["profile"], action_tables["actions"]
    eng.ingest_many("profile", [p.row(i) for i in range(len(p))])
    eng.ingest_many("actions", [a.row(i) for i in range(30)])
    rows = [a.row(40 + i) for i in range(4)]
    keys, ts, values, need = _encoded_batch(eng, rows)
    batch = eng.cs.online_batch(eng.store, keys, ts, values)
    for i in range(len(rows)):
        single = eng.cs.online(eng.store, keys[i], ts[i],
                               {c: values[c][i] for c in need})
        for k in single:
            np.testing.assert_array_equal(
                np.asarray(batch[k][i]), np.asarray(single[k]), err_msg=k)


# ------------------------------------------------------- bulk store ingest


@pytest.mark.parametrize("seed,n_new", [(0, 13), (1, 1), (2, 32), (3, 54)])
def test_put_many_equals_sequential_put(seed, n_new):
    """One merged batch == n_new sorted single inserts, ties (same key
    and ts) in arrival order, a full store included (10 + 54 = 64)."""
    rng = np.random.default_rng(seed)
    cap = 64
    s1 = timestore.OnlineStore(cap)
    s2 = timestore.OnlineStore(cap)
    for s in (s1, s2):
        s.create_table("t", {"v": np.float32, "c": np.int32})
        for i in range(10):
            s.put("t", i % 4, int(rng.integers(0, 50)) if s is s1 else 0,
                  {"v": float(i), "c": i})
    # same seed history for both stores
    s2.tables["t"] = s1.tables["t"]
    keys = rng.integers(0, 4, size=n_new).astype(np.int32)
    ts = rng.integers(0, 50, size=n_new).astype(np.int32)
    cols = {"v": rng.normal(size=n_new).astype(np.float32),
            "c": np.arange(n_new, dtype=np.int32)}
    for i in range(n_new):
        s1.put("t", int(keys[i]), int(ts[i]),
               {"v": float(cols["v"][i]), "c": int(cols["c"][i])})
    off = s2.put_many("t", keys, ts, cols)
    assert off == 10
    for f in ("keys", "ts"):
        np.testing.assert_array_equal(np.asarray(s1.tables["t"][f]),
                                      np.asarray(s2.tables["t"][f]))
    for c in ("v", "c"):
        np.testing.assert_array_equal(
            np.asarray(s1.tables["t"]["cols"][c]),
            np.asarray(s2.tables["t"]["cols"][c]))
    assert s1.n_rows("t") == s2.n_rows("t") == 10 + n_new
    assert s2._binlog_offset == 10 + n_new


def _lexsort_merge(state, keys, ts, values):
    """Plain NumPy reference of ``insert_many``: the live rows and the
    batch's real rows ordered by (key, ts, old before new, arrival),
    cut to capacity; the rest padding (INT_MAX keys and ts, zeros)."""
    cap, live = state["keys"].shape[0], int(state["count"])
    real = np.asarray(keys) != timestore.INT_MAX
    n = int(real.sum())
    k = np.concatenate([np.asarray(state["keys"])[:live], keys[real]])
    t = np.concatenate([np.asarray(state["ts"])[:live], ts[real]])
    new = np.repeat([0, 1], [live, n])
    arrival = np.concatenate([np.arange(live), np.arange(n)])
    o = np.lexsort((arrival, new, t, k))[:cap]
    out = {"keys": np.full(cap, timestore.INT_MAX, np.int32),
           "ts": np.full(cap, timestore.INT_MAX, np.int32), "cols": {},
           "count": live + n}
    out["keys"][:o.size], out["ts"][:o.size] = k[o], t[o]
    for c, v in values.items():
        col = np.zeros(cap, v.dtype)
        col[:o.size] = np.concatenate(
            [np.asarray(state["cols"][c])[:live], v[real]])[o]
        out["cols"][c] = col
    return out


def _assert_store_bitwise(got, want):
    """Whole arrays, padding slots included; floats by their bits."""
    for f in ("keys", "ts"):
        np.testing.assert_array_equal(np.asarray(got[f]), want[f], f)
    for c, v in want["cols"].items():
        np.testing.assert_array_equal(
            np.asarray(got["cols"][c]).view(np.int32), v.view(np.int32), c)
    assert int(got["count"]) == want["count"]


def _random_batch(rng, m, n):
    """An m-row padded batch with n real rows at random slots, ties in
    (key, ts), and junk in the padding rows' columns (never merged)."""
    keys = np.full(m, timestore.INT_MAX, np.int32)
    ts = np.full(m, timestore.INT_MAX, np.int32)
    slots = rng.permutation(m)[:n]
    keys[slots] = rng.integers(0, 5, n)
    ts[slots] = rng.integers(0, 6, n)
    values = {"v": rng.normal(size=m).astype(np.float32),
              "c": rng.integers(-9, 9, m).astype(np.int32)}
    return keys, ts, values


@pytest.mark.parametrize("m", [1, 2, 4, 8, 16, 32, 64])
@pytest.mark.parametrize("cap", [37, 200])
def test_insert_many_equals_lexsort_merge(cap, m):
    """Random multi-batch histories with ties, from an all-padding first
    batch until a batch fills the store exactly: after every merge the
    whole store is bitwise the NumPy lexsort merge."""
    rng = np.random.default_rng(cap * 100 + m)
    state = timestore.make_state(cap, {"v": jnp.float32, "c": jnp.int32})
    n = 0
    while int(state["count"]) < cap:
        keys, ts, values = _random_batch(rng, m, n)
        want = _lexsort_merge(state, keys, ts, values)
        state = timestore.insert_many(
            state, jnp.asarray(keys), jnp.asarray(ts),
            {c: jnp.asarray(v) for c, v in values.items()}, n)
        _assert_store_bitwise(state, want)
        left = cap - int(state["count"])
        n = left if left <= m else int(rng.integers(0, m + 1))


@pytest.mark.parametrize("m", [1, 16])
def test_insert_many_stacked_equals_per_shard(m):
    """The vmapped merge over 4 stacked shards, each with its own history
    and batch, is ``insert_many`` run on each shard."""
    rng = np.random.default_rng(m)
    cap, shards, blocks = 50, [], []
    for s in range(4):
        st = timestore.make_state(cap, {"v": jnp.float32, "c": jnp.int32})
        for _ in range(3):
            n = int(rng.integers(0, m + 1))
            k, t, v = _random_batch(rng, m, n)
            st = timestore.insert_many(st, k, t, v, n)
        n = int(rng.integers(0, m + 1)) if s else 0     # shard 0: padding
        shards.append(st)
        blocks.append(_random_batch(rng, m, n) + (n,))
    stack = lambda *xs: np.stack([np.asarray(x) for x in xs])
    got = timestore.insert_many_stacked(
        jax.tree_util.tree_map(stack, *shards),
        stack(*[b[0] for b in blocks]), stack(*[b[1] for b in blocks]),
        {c: stack(*[b[2][c] for b in blocks]) for c in ("v", "c")},
        stack(*[b[3] for b in blocks]))
    for s, (st, (k, t, v, n)) in enumerate(zip(shards, blocks)):
        want = timestore.insert_many(st, k, t, v, n)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(
                np.asarray(a).view(np.int32), np.asarray(b)[s].view(np.int32))


def test_put_many_overflow_and_empty():
    st = timestore.OnlineStore(8)
    st.create_table("t", {"v": np.float32})
    with pytest.raises(ValueError):
        st.put_many("t", np.arange(9), np.arange(9),
                    {"v": np.zeros(9, np.float32)})
    off = st.put_many("t", np.zeros((0,)), np.zeros((0,)),
                      {"v": np.zeros((0,), np.float32)})
    assert off == 0 and st.n_rows("t") == 0


def test_ingest_many_overflow_releases_guard(action_tables, micro_sql):
    eng = FeatureEngine(micro_sql, action_tables, capacity=4)
    a = action_tables["actions"]
    used_before = eng.guard.used
    with pytest.raises(ValueError):
        eng.ingest_many("actions", [a.row(i) for i in range(8)])
    assert eng.guard.used == used_before   # failed bulk put charges nothing


def test_online_batch_pads_to_pow2_one_compile(action_tables, micro_sql):
    """Varying batch sizes in the same pow2 bracket share one jitted fn
    and padding never changes real rows' results."""
    from repro.core import compiler as C

    eng = FeatureEngine(micro_sql, action_tables, capacity=512)
    o, a = action_tables["orders"], action_tables["actions"]
    eng.ingest_many("orders", [o.row(i) for i in range(30)])
    rows = [a.row(60 + i) for i in range(7)]
    keys, ts, values, need = _encoded_batch(eng, rows)
    out7 = eng.cs.online_batch(eng.store, keys, ts, values)
    assert all(v.shape[0] == 7 for v in out7.values())
    misses0 = C.cache_stats()["misses"]
    out5 = eng.cs.online_batch(eng.store, keys[:5], ts[:5],
                               {c: values[c][:5] for c in need})
    assert C.cache_stats()["misses"] == misses0   # 5 pads to 8: cache hit
    for k in out7:
        np.testing.assert_array_equal(out5[k], out7[k][:5], err_msg=k)
    with pytest.raises(ValueError):
        eng.cs.online_batch(eng.store, [], [], {c: [] for c in need})


def test_preagg_update_many_equals_sequential():
    spec = WindowSpec("w", "k", "ts", preceding=10_000)
    leaves = {
        "sum:x": AddLeaf("sum:x", lambda env: jnp.asarray(env["x"])),
        "max:x": MaxLeaf("max:x", lambda env: jnp.asarray(env["x"])),
        "ew:x": EWLeaf("ew:x", lambda env: jnp.asarray(env["x"]),
                       decay=0.6),
        "dd:x": DrawdownLeaf("dd:x", lambda env: jnp.asarray(env["x"])),
    }
    pa = PreAgg(spec=spec, leaves=leaves, bucket_ms=100, window_ms=10_000,
                n_keys=8, value_cols=("x",), fanout=4)
    rng = np.random.default_rng(1)
    n = 37
    keys = rng.integers(0, 8, size=n).astype(np.int32)
    ts = np.sort(rng.integers(0, 5_000, size=n)).astype(np.int32)
    xs = rng.normal(size=n).astype(np.float32) + 2.0

    s_seq = pa.init_state()
    for i in range(n):
        s_seq = pa.update(s_seq, jnp.int32(keys[i]), jnp.int32(ts[i]),
                          {"x": jnp.float32(xs[i])})
    s_bat = pa.update_many(pa.init_state(), keys, ts, {"x": xs})
    # BITWISE: the scalar path routes through the batched ordered fold
    # with B=1 and the batched fold seeds every (key, bucket) group from
    # the slot's pre-batch value, so the combine sequences are identical
    # (the seed-era associative-scan last-ULP divergence is gone)
    for lvl in ("fine", "coarse"):
        for k in leaves:
            np.testing.assert_array_equal(np.asarray(s_seq[lvl][k]),
                                          np.asarray(s_bat[lvl][k]),
                                          err_msg=f"{lvl}/{k}")
        np.testing.assert_array_equal(
            np.asarray(s_seq[f"{lvl}_epoch"]),
            np.asarray(s_bat[f"{lvl}_epoch"]))
    # incremental batch on top of existing state
    s_a = pa.update_many(s_bat, keys[:5], ts[:5] + 6_000, {"x": xs[:5]})
    s_b = s_bat
    for i in range(5):
        s_b = pa.update(s_b, jnp.int32(keys[i]), jnp.int32(ts[i] + 6_000),
                        {"x": jnp.float32(xs[i])})
    for lvl in ("fine", "coarse"):
        for k in leaves:
            np.testing.assert_array_equal(np.asarray(s_a[lvl][k]),
                                          np.asarray(s_b[lvl][k]),
                                          err_msg=f"inc {lvl}/{k}")


# ------------------------------------------------ batch_windowfold kernel


def test_batch_windowfold_kernel_matches_ref():
    from repro.kernels.batch_windowfold import batch_windowfold
    from repro.kernels.batch_windowfold.ref import batch_windowfold_ref

    rng = np.random.default_rng(2)
    for c, f, b in ((64, 1, 3), (500, 9, 37), (130, 17, 130)):
        keys = np.sort(rng.integers(0, 16, size=c)).astype(np.int32)
        ts = rng.integers(0, 10_000, size=c).astype(np.int32)
        vals = rng.normal(size=(c, f)).astype(np.float32)
        qkey = rng.integers(0, 16, size=b).astype(np.int32)
        qt1 = rng.integers(0, 10_000, size=b).astype(np.int32)
        qt0 = qt1 - rng.integers(0, 3_000, size=b).astype(np.int32)
        args = tuple(jnp.asarray(x) for x in
                     (keys, ts, vals, qkey, qt0, qt1))
        ref = np.asarray(batch_windowfold_ref(*args))
        pal = np.asarray(batch_windowfold(*args, use_pallas=True,
                                          interpret=True))
        np.testing.assert_allclose(pal, ref, rtol=1e-5, atol=1e-5)
        brute = np.zeros((b, f), np.float32)
        for i in range(b):
            m = (keys == qkey[i]) & (ts >= qt0[i]) & (ts <= qt1[i])
            brute[i] = vals[m].sum(axis=0)
        np.testing.assert_allclose(ref, brute, rtol=1e-4, atol=1e-4)


def test_online_batch_fast_matches_batched_path(action_tables):
    """The fused megakernel path is BITWISE the vmapped batch driver."""
    eng = FeatureEngine(ADDITIVE_SQL, action_tables, capacity=1024)
    o, a = action_tables["orders"], action_tables["actions"]
    eng.ingest_many("orders", [o.row(i) for i in range(80)])
    eng.ingest_many("actions", [a.row(i) for i in range(60)])
    cs = eng.cs
    ok, why = cs.fast_batch_eligible()
    assert ok, why
    rows = [a.row(100 + i) for i in range(11)]
    keys, ts, values, _ = _encoded_batch(eng, rows)
    ref = cs.online_batch(eng.store, keys, ts, values)
    for use_pallas in (False, True):
        fast = cs.online_batch_fast(eng.store, keys, ts, values,
                                    use_pallas=use_pallas)
        for k in ref:
            np.testing.assert_array_equal(
                np.asarray(fast[k]), np.asarray(ref[k]),
                err_msg=f"{k} pallas={use_pallas}")


def test_online_batch_fast_serves_every_leaf_family(action_tables,
                                                    micro_sql):
    """The unit-fold megakernel lifted the old additive-only
    eligibility: ROWS frames, min/max, drawdown, ew_avg, topn all serve
    through the fused path now, bitwise vs ``online_batch``."""
    eng = FeatureEngine(micro_sql, action_tables, capacity=1024)
    ok, why = eng.cs.fast_batch_eligible()
    assert ok, why
    o, a = action_tables["orders"], action_tables["actions"]
    eng.ingest_many("orders", [o.row(i) for i in range(60)])
    eng.ingest_many("actions", [a.row(i) for i in range(40)])
    rows = [a.row(100 + i) for i in range(9)]
    keys, ts, values, _ = _encoded_batch(eng, rows)
    ref = eng.cs.online_batch(eng.store, keys, ts, values)
    fast = eng.cs.online_batch_fast(eng.store, keys, ts, values)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(fast[k]),
                                      np.asarray(ref[k]), err_msg=k)


# --------------------------------------------------- serving integration


def test_batcher_empty_queue_regression():
    b = RequestBatcher(4)
    assert b.next_batch() == ([], [], 0)          # no IndexError
    assert b.batches_emitted == 0
    b.submit("x")
    ids, payloads, n = b.next_batch()
    assert n == 1 and payloads == ["x"] * 4       # tail padded


def test_engine_submit_flush_matches_scalar(action_tables, micro_sql):
    eng = FeatureEngine(micro_sql, action_tables, capacity=1024,
                        batch_size=4)
    ref_eng = FeatureEngine(micro_sql, action_tables, capacity=1024)
    o, a = action_tables["orders"], action_tables["actions"]
    for e in (eng, ref_eng):
        e.ingest_many("orders", [o.row(i) for i in range(40)])
    reqs = [a.row(10 + i) for i in range(6)]
    rids = [eng.submit_request(dict(r)) for r in reqs]
    out = eng.flush()
    assert sorted(out) == sorted(rids)
    assert not eng.batcher.queue
    assert eng.batcher.padded_slots == 2          # 6 reqs, batches of 4
    assert eng.n_requests == 6                    # padding isn't load
    for rid, r in zip(rids, reqs):
        ref = ref_eng.request(dict(r))
        for k in ref:
            np.testing.assert_array_equal(out[rid][k], np.asarray(ref[k]),
                                          err_msg=k)


def test_engine_key_col_resolved_once(action_tables, micro_sql):
    eng = FeatureEngine(micro_sql, action_tables, capacity=64)
    assert eng.key_col == "userid"


def test_engine_latencies_bounded(action_tables, micro_sql):
    eng = FeatureEngine(micro_sql, action_tables, capacity=256,
                        latency_window=10)
    a = action_tables["actions"]
    for _ in range(14):
        eng.request(dict(a.row(5)))
    assert len(eng.latencies_ms) == 10
    pct = eng.latency_percentiles()
    assert set(pct) == {"TP50", "TP90", "TP95", "TP99"}
    assert all(v >= 0 for v in pct.values())


# --------------------------------------------- adaptive hierarchy stats


def test_observe_query_wired_into_request_path():
    tables = make_action_tables(n_actions=120, n_orders=0, n_users=4,
                                horizon_ms=12_000_000, seed=4,
                                with_profile=False)
    eng = FeatureEngine(PREAGG_SQL, tables, capacity=256, use_preagg=True,
                        batch_size=4)
    a = tables["actions"]
    eng.ingest_many("actions", [a.row(i) for i in range(60)])
    pa = eng.cs.windows[0].preagg
    assert pa.query_stats["queries"] == 0
    eng.request(dict(a.row(70)))                  # scalar path
    assert pa.query_stats["queries"] == 1
    for i in range(5):                            # batched path
        eng.submit_request(dict(a.row(80 + i)))
    eng.flush()
    # only the 5 real requests count (batch padding is stats-invisible)
    assert pa.query_stats["queries"] == 1 + 5


def test_advice_transitions_under_synthetic_workload():
    spec = WindowSpec("w", "k", "ts", preceding=100_000)
    leaf = AddLeaf("sum:x", lambda env: jnp.asarray(env["x"]))
    pa = PreAgg(spec=spec, leaves={"sum:x": leaf}, bucket_ms=1000,
                window_ms=100_000, n_keys=4, value_cols=("x",), fanout=4)
    assert pa.suggest_hierarchy()["advice"] == "keep"
    # every query spans ~25 coarse buckets (> 4 * fanout): the top level
    # is too fine for the live traffic -> grow the hierarchy
    for ts in range(400_000, 400_032):
        pa.observe_query(ts)
    s = pa.suggest_hierarchy()
    assert s["coarse_per_query"] > 4 * pa.fanout
    assert s["advice"] == "add-coarser-level"
