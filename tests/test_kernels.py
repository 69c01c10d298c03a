"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes kernel bodies on CPU), plus the unit-fold
megakernel parity suite (fused ref / Pallas-interpret vs the staged
``fold_unit`` engine, bitwise)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:          # property tests skip; parity suite still runs
    HAVE_HYP = False

from repro.core import compile_script, parse, verify_consistency
from repro.core.lowering import windows as W
from repro.kernels.segagg import ops as segagg_ops
from repro.kernels.chunked_scan import ops as scan_ops
from repro.kernels.feature_hash import ops as hash_ops
from repro.kernels.flash_decode import ops as fd_ops
from repro.kernels.unit_fold import ops as uf_ops

from examples.leaf_families import SQL as UNIT_SQL


# ------------------------------------------------------------------ segagg

@pytest.mark.parametrize("n,f,s", [(64, 4, 8), (1000, 16, 50),
                                   (257, 1, 3), (512, 33, 128)])
def test_segagg_shapes(n, f, s):
    rng = np.random.default_rng(n)
    vals = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    segs = jnp.asarray(np.sort(rng.integers(0, s, n)).astype(np.int32))
    a = segagg_ops.segagg(vals, segs, s, use_pallas=True)
    b = segagg_ops.segagg(vals, segs, s, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


def test_segagg_unsorted_and_out_of_range():
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.standard_normal((100, 3)).astype(np.float32))
    segs = jnp.asarray(rng.integers(-2, 12, 100).astype(np.int32))
    a = segagg_ops.segagg(vals, segs, 10, use_pallas=True)
    b = segagg_ops.segagg(vals, segs, 10, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


def test_bucket_build_counts():
    ts = jnp.asarray([0, 10, 20, 20, 35], jnp.int32)
    vals = jnp.ones((5, 1), jnp.float32) * 2.0
    out = segagg_ops.bucket_build(vals, ts, bucket_ms=10, n_buckets=4)
    np.testing.assert_allclose(np.asarray(out[:, 1]), [1, 1, 2, 1])
    np.testing.assert_allclose(np.asarray(out[:, 0]), [2, 2, 4, 2])


# ------------------------------------------------------------ chunked_scan

@pytest.mark.parametrize("b,t,d,chunk", [(1, 64, 8, 16), (2, 300, 32, 128),
                                         (3, 128, 1, 128), (2, 1000, 7, 64)])
def test_chunked_scan_shapes(b, t, d, chunk):
    rng = np.random.default_rng(t)
    a = jnp.asarray(rng.uniform(0.3, 1.0, (b, t, d)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((b, t, d)).astype(np.float32))
    y1 = scan_ops.linear_scan(a, x, use_pallas=True, chunk=chunk)
    y2 = scan_ops.linear_scan(a, x, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)


if HAVE_HYP:
    @given(t=st.integers(2, 80), d=st.integers(1, 9))
    @settings(max_examples=10, deadline=None)
    def test_chunked_scan_property(t, d):
        rng = np.random.default_rng(t * 100 + d)
        a = jnp.asarray(rng.uniform(0.2, 0.99, (1, t, d))
                        .astype(np.float32))
        x = jnp.asarray(rng.standard_normal((1, t, d)).astype(np.float32))
        y1 = np.asarray(scan_ops.linear_scan(a, x, use_pallas=True,
                                             chunk=16))
        # sequential oracle
        h = np.zeros((d,), np.float32)
        an, xn = np.asarray(a)[0], np.asarray(x)[0]
        for i in range(t):
            h = an[i] * h + xn[i]
            np.testing.assert_allclose(y1[0, i], h, rtol=2e-3, atol=2e-3)


def test_ew_avg_equivalence():
    """ew_avg's monoid == the chunked scan recurrence (DESIGN.md §2)."""
    from repro.core.functions import EWLeaf
    import jax.numpy as jnp

    decay = 0.8
    xs = np.random.default_rng(0).uniform(1, 5, 20).astype(np.float32)
    a = jnp.full((1, 20, 1), decay)
    y = scan_ops.linear_scan(a, jnp.asarray(xs)[None, :, None],
                             use_pallas=True, chunk=16)
    leaf = EWLeaf("ew", lambda env: jnp.asarray(env["x"]), decay=decay)
    state = leaf.identity()
    for v in xs:
        state = leaf.combine(state, leaf.lift({"x": jnp.asarray([v])})[0])
    np.testing.assert_allclose(float(y[0, -1, 0]), float(state[0]),
                               rtol=1e-4)


# ------------------------------------------------------------ feature_hash

@pytest.mark.parametrize("shape,dim", [((64,), 1024), ((16, 7), 1 << 20),
                                       ((3, 5, 2), 997)])
def test_feature_hash_shapes(shape, dim):
    rng = np.random.default_rng(42)
    codes = jnp.asarray(rng.integers(0, 1 << 30, shape).astype(np.int32))
    h1 = hash_ops.feature_hash(codes, dim, use_pallas=True)
    h2 = hash_ops.feature_hash(codes, dim, use_pallas=False)
    assert bool(jnp.all(h1 == h2))
    assert bool(jnp.all((h1 >= 0) & (h1 < dim)))


def test_feature_hash_determinism_and_spread():
    codes = jnp.arange(10000, dtype=jnp.int32)
    h = np.asarray(hash_ops.feature_hash(codes, 4096, use_pallas=True))
    h2 = np.asarray(hash_ops.feature_hash(codes, 4096, use_pallas=True))
    assert (h == h2).all()
    # avalanche: bucket occupancy near-uniform
    counts = np.bincount(h, minlength=4096)
    assert counts.max() < 25                      # ~2.4 expected


# ------------------------------------------------------------ flash_decode

@pytest.mark.parametrize("b,h,s,d", [(1, 2, 128, 32), (2, 4, 700, 64),
                                     (3, 1, 1024, 128)])
def test_flash_decode_shapes(b, h, s, d):
    rng = np.random.default_rng(s)
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    lens = jnp.asarray(rng.integers(1, s + 1, b).astype(np.int32))
    o1 = fd_ops.decode_attention(q, k, v, lens, use_pallas=True)
    mask = jnp.arange(s)[None, :] < lens[:, None]
    o2 = fd_ops.decode_attention_ref(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4,
                               atol=1e-4)


def test_flash_decode_shard_merge_is_exact():
    """Partial merge across KV shards == full attention: the model-layer
    instance of the paper's aggregator merge (DESIGN.md §2)."""
    rng = np.random.default_rng(7)
    b, h, s, d = 2, 4, 512, 64
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
    lens = jnp.asarray([s, s - 30], jnp.int32)
    full = fd_ops.decode_attention(q, k, v, lens, use_pallas=True)
    parts = []
    n_shards = 4
    c = s // n_shards
    for i in range(n_shards):
        shard_len = jnp.clip(lens - i * c, 0, c)
        parts.append(fd_ops.decode_partials(
            q, k[:, i * c:(i + 1) * c], v[:, i * c:(i + 1) * c],
            shard_len, use_pallas=True))
    acc = parts[0]
    for p in parts[1:]:
        acc = fd_ops.merge_partials(acc, p)
    merged = fd_ops.finalize_partials(*acc)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------- unit_fold megakernel



@pytest.fixture(scope="module")
def unit_case():
    """One window group covering every leaf family x frame shape, plus a
    padded unit env with a garbage invalid tail (the fused gather writes
    identity there; parity must hold anyway)."""
    cs = compile_script(UNIT_SQL, distinct_hll_p=None)
    (members,) = W.group_windows(cs.windows)
    rng = np.random.default_rng(0)
    r = 37
    ts = np.sort(rng.integers(0, 20000, r)).astype(np.int32)
    price = rng.normal(2.0, 1.5, r).astype(np.float32)
    item = rng.integers(0, 9, r).astype(np.int32)
    valid = np.ones(r, bool)
    valid[-7:] = False
    price[~valid] = 99.0
    env = {"ts": jnp.asarray(ts), "price": jnp.asarray(price),
           "item": jnp.asarray(item), "__valid__": jnp.asarray(valid)}
    specs = [m.node.spec for m in members]
    leaves = {}
    for m in members:
        for k, leaf in W.unique_leaves(m.aggs).items():
            leaves.setdefault(k, leaf)
    return members, specs, leaves, env


def _assert_unit_parity(members, staged, fused, batch=None):
    for mi, m in enumerate(members):
        for k in W.unique_leaves(m.aggs):
            a = np.asarray(staged[mi][k])
            b = np.asarray(fused[mi][k])
            if batch is None:
                np.testing.assert_array_equal(
                    a, b, err_msg=f"{m.node.spec.name}/{k}")
            else:
                for u in range(batch):
                    np.testing.assert_array_equal(
                        a, b[u], err_msg=f"{m.node.spec.name}/{k}[{u}]")


def test_unit_fold_ref_parity(unit_case):
    """Fused XLA ref == staged fold_unit, bitwise, all leaves/frames."""
    members, specs, leaves, env = unit_case
    staged = W.fold_unit(members, env)
    fused = uf_ops.unit_fold(specs, leaves, env, order_by="ts",
                             use_pallas=False, interpret=True)
    _assert_unit_parity(members, staged, fused)


def test_unit_fold_pallas_parity(unit_case):
    """Pallas kernel (interpret mode, batched unit axis) == the JITTED
    staged path.  Jitted, not eager: XLA constant-folds ew_avg's
    ``log(decay)`` to different bits than the eager op, and every
    production driver runs jitted."""
    members, specs, leaves, env = unit_case
    staged = jax.jit(lambda e: W.fold_unit(members, e))(env)
    u = 3
    env_b = {k: jnp.stack([v] * u) for k, v in env.items()}
    fused = uf_ops.unit_fold(specs, leaves, env_b, order_by="ts",
                             use_pallas=True, interpret=True)
    _assert_unit_parity(members, staged, fused, batch=u)


def test_unit_fold_single_query_parity(unit_case):
    """Online-style single-request query position, bitwise."""
    members, specs, leaves, env = unit_case
    p = jnp.int32(env["ts"].shape[0] - 8)
    staged = W.fold_unit(members, env, queries=p[None])
    fused = uf_ops.unit_fold(specs, leaves, env, p[None], order_by="ts",
                             use_pallas=False)
    _assert_unit_parity(members, staged, fused)


@pytest.mark.parametrize("n_shards", [None, 2])
def test_fused_fold_consistency_gate(action_tables, micro_sql, n_shards):
    """verify_consistency(bitwise=True) with the megakernel driving both
    executors: scalar online replay vs offline (n_shards=None) and
    sharded batch serving vs offline_sharded (n_shards=2)."""
    cs = compile_script(parse(micro_sql), tables=action_tables,
                        fused_unit_fold=True)
    rep = verify_consistency(cs, action_tables, n_shards=n_shards,
                             bitwise=True)
    assert rep.passed and rep.bitwise_equal, str(rep)


def test_fused_offline_bitwise_vs_staged(action_tables, micro_sql):
    """Cross-impl gate: the fused-flag offline run reproduces the staged
    offline run bit for bit on every feature."""
    staged = compile_script(parse(micro_sql), tables=action_tables)
    fused = compile_script(parse(micro_sql), tables=action_tables,
                           fused_unit_fold=True)
    a, b = staged.offline(action_tables), fused.offline(action_tables)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


# ------------------------------------------------- lane-tiling edge shapes

from repro.kernels.unit_fold import ref as uf_ref
from repro.kernels.unit_fold.kernel import LANES

EDGE_SQL = """
SELECT sum(price) OVER wa AS s, min(price) OVER wa AS mn,
       sum(price) OVER wb AS sb
FROM actions
WINDOW wa AS (PARTITION BY uid ORDER BY ts
              ROWS BETWEEN 5 PRECEDING AND CURRENT ROW),
  wb AS (PARTITION BY uid ORDER BY ts
         ROWS_RANGE BETWEEN 2s PRECEDING AND CURRENT ROW)
"""

SOLO_SQL = """
SELECT sum(price) OVER wa AS s
FROM actions
WINDOW wa AS (PARTITION BY uid ORDER BY ts
              ROWS BETWEEN 5 PRECEDING AND CURRENT ROW)
"""


def _edge_case(sql):
    cs = compile_script(sql, distinct_hll_p=None)
    (members,) = W.group_windows(cs.windows)
    specs = [m.node.spec for m in members]
    leaves = {}
    for m in members:
        for k, leaf in W.unique_leaves(m.aggs).items():
            leaves.setdefault(k, leaf)
    return members, specs, leaves


def _edge_env(r, seed, n_valid=None):
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, 10_000, r)).astype(np.int32)
    price = rng.normal(0.0, 2.0, r).astype(np.float32)
    valid = np.zeros(r, bool)
    valid[:r if n_valid is None else n_valid] = True
    price[~valid] = 123.0                 # garbage in invalid slots
    return {"ts": jnp.asarray(ts), "price": jnp.asarray(price),
            "__valid__": jnp.asarray(valid)}


def _assert_tile_parity(members, envs, fused, queries=None):
    staged_fn = jax.jit(
        lambda e, q: W.fold_unit(members, e, queries=q))
    r = envs[0]["ts"].shape[0]
    for i, env in enumerate(envs):
        q = (jnp.arange(r, dtype=jnp.int32) if queries is None
             else queries[i])
        staged = staged_fn(env, q)
        for mi, m in enumerate(members):
            for k in W.unique_leaves(m.aggs):
                np.testing.assert_array_equal(
                    np.asarray(staged[mi][k]),
                    np.asarray(fused[mi][k][i]),
                    err_msg=f"unit={i} member={mi} {k}")


@pytest.mark.parametrize("u", [1, LANES - 1, LANES, LANES + 1])
def test_unit_fold_lane_tile_unit_counts(u):
    """Tile-boundary unit counts (1, LANES-1, LANES, LANES+1): the
    padded sentinel lanes never leak into real units' results."""
    members, specs, leaves = _edge_case(EDGE_SQL)
    envs = [_edge_env(13, seed=u * 100 + i, n_valid=13 - (i % 3))
            for i in range(u)]
    env_b = {k: jnp.stack([e[k] for e in envs]) for k in envs[0]}
    fused = uf_ops.unit_fold(specs, leaves, env_b, order_by="ts",
                             use_pallas=True, interpret=True)
    _assert_tile_parity(members, envs, fused)


def test_unit_fold_lane_tile_single_member_group():
    """A one-member, one-leaf group (solo lane, Mg=1) through the tiled
    kernel."""
    members, specs, leaves = _edge_case(SOLO_SQL)
    envs = [_edge_env(9, seed=i) for i in range(3)]
    env_b = {k: jnp.stack([e[k] for e in envs]) for k in envs[0]}
    fused = uf_ops.unit_fold(specs, leaves, env_b, order_by="ts",
                             use_pallas=True, interpret=True)
    _assert_tile_parity(members, envs, fused)


def test_unit_fold_lane_tile_single_query():
    """Q=1 (the online request shape) across a ragged tile."""
    members, specs, leaves = _edge_case(EDGE_SQL)
    u = LANES + 1
    envs = [_edge_env(11, seed=i, n_valid=11 - (i % 4)) for i in range(u)]
    env_b = {k: jnp.stack([e[k] for e in envs]) for k in envs[0]}
    q = jnp.asarray([[3 + (i % 5)] for i in range(u)], jnp.int32)
    fused = uf_ops.unit_fold(specs, leaves, env_b, q, order_by="ts",
                             use_pallas=True, interpret=True)
    _assert_tile_parity(members, envs, fused, queries=q)


def test_unit_fold_lane_tile_empty_unit():
    """A unit with zero valid rows folds to pure identities — parity
    with the staged fold on the same all-invalid env."""
    members, specs, leaves = _edge_case(EDGE_SQL)
    envs = [_edge_env(8, seed=i, n_valid=0 if i == 2 else 8)
            for i in range(LANES)]
    env_b = {k: jnp.stack([e[k] for e in envs]) for k in envs[0]}
    fused = uf_ops.unit_fold(specs, leaves, env_b, order_by="ts",
                             use_pallas=True, interpret=True)
    _assert_tile_parity(members, envs, fused)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_unit_fold_blocks_parity_with_padded_units(unit_case, use_pallas):
    """The relayout-free blocks entry (flat columns + (U, R) gather
    index, halos, sentinel pad slots, one fully padded-out unit) is
    bitwise the staged fold of each gathered unit.  Covers both lift
    placements: wide leaf groups prelift the flat rows, narrow groups
    lift from the gathered raw columns.  Honors the §6.2 layout
    invariant the producer (lowering.windows.fused_prelift) guarantees:
    every flat row except the trailing sentinel is valid."""
    members, specs, leaves, env = unit_case
    n = int(np.asarray(env["__valid__"]).sum())  # valid prefix length
    flat_env = {
        "ts": jnp.concatenate([env["ts"][:n],
                               jnp.asarray([uf_ref.INT_MAX], jnp.int32)]),
        "price": jnp.concatenate([env["price"][:n],
                                  jnp.zeros(1, jnp.float32)]),
        "item": jnp.concatenate([env["item"][:n],
                                 jnp.zeros(1, jnp.int32)]),
        "__valid__": jnp.concatenate([jnp.ones(n, bool),
                                      jnp.zeros(1, bool)]),
    }
    r = 16
    idx = np.full((4, r), n, np.int64)      # sentinel-initialized
    idx[0, :r] = np.arange(r)               # plain block
    idx[1, :r] = np.arange(8, 8 + r)        # overlapping halo block
    idx[2, :n - 20] = np.arange(20, n)      # partial block, sentinel tail
    # idx[3] stays all-sentinel: a fully padded-out unit
    idx = jnp.asarray(idx)
    # jitted on both sides: XLA constant-folds ew_avg's log(decay) to
    # different bits than the eager op (see test_unit_fold_pallas_parity)
    fused = jax.jit(lambda fe, ix: uf_ops.unit_fold_blocks(
        specs, leaves, fe, ix, order_by="ts",
        use_pallas=use_pallas, interpret=True))(flat_env, idx)
    staged_fn = jax.jit(lambda e: W.fold_unit(members, e))
    for u in range(idx.shape[0]):
        env_u = {c: v[idx[u]] for c, v in flat_env.items()}
        staged = staged_fn(env_u)
        for mi, m in enumerate(members):
            for k in W.unique_leaves(m.aggs):
                np.testing.assert_array_equal(
                    np.asarray(staged[mi][k]),
                    np.asarray(fused[mi][k][u]),
                    err_msg=f"unit={u} {k}")


# ------------------------------------------------------ dispatch policy

def test_dispatch_cpu_autodetect_falls_back_to_ref():
    from repro.kernels import dispatch
    if dispatch.tpu_available():
        pytest.skip("TPU backend: autodetect selects the compiled kernel")
    assert dispatch.resolve(None, None) == (False, True)


def test_dispatch_forced_interpret_runs_off_tpu():
    from repro.kernels import dispatch
    assert dispatch.resolve(True, True) == (True, True)
    assert dispatch.resolve(True, None)[0] is True   # interpret follows
    if not dispatch.tpu_available():
        assert dispatch.resolve(True, None)[1] is True


def test_dispatch_backend_init_error_propagates(monkeypatch):
    """A backend that fails to initialise is an error, not "no TPU":
    autodetect must not quietly fall back to interpret mode."""
    from repro.kernels import dispatch

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    dispatch.tpu_available.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="initialize backend"):
            dispatch.resolve(None, None)
    finally:
        dispatch.tpu_available.cache_clear()


def test_kernel_entries_have_no_interpret_default():
    """Every ``*_pallas`` entry takes ``interpret`` as a required
    keyword: only ``dispatch.resolve`` picks a default."""
    import inspect

    from repro.kernels.batch_windowfold.kernel import \
        batch_windowfold_pallas
    from repro.kernels.chunked_scan.kernel import linear_scan_pallas
    from repro.kernels.feature_hash.kernel import feature_hash_pallas
    from repro.kernels.flash_decode.kernel import decode_partials_pallas
    from repro.kernels.segagg.kernel import segagg_pallas
    from repro.kernels.unit_fold.kernel import unit_fold_pallas

    for fn in (batch_windowfold_pallas, linear_scan_pallas,
               feature_hash_pallas, decode_partials_pallas, segagg_pallas,
               unit_fold_pallas):
        p = inspect.signature(inspect.unwrap(fn)).parameters["interpret"]
        assert p.kind is p.KEYWORD_ONLY, fn
        assert p.default is p.empty, fn


def test_dispatch_compiled_pallas_off_tpu_raises_typed_error(unit_case):
    from repro.kernels import dispatch
    if dispatch.tpu_available():
        pytest.skip("TPU backend lowers the compiled kernel")
    members, specs, leaves, env = unit_case
    with pytest.raises(dispatch.PallasUnsupportedError,
                       match="unit_fold_pallas"):
        uf_ops.unit_fold(specs, leaves, env, order_by="ts",
                         use_pallas=True, interpret=False)
