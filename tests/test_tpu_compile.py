"""Compile (never run) the main path's TPU programs for a described v5e.

The TPU compiler is installed even where no chip is attached: it lowers
for a topology that is only described.  These tests make Mosaic and XLA
refuse here, at no chip time, what interpret mode accepts and the chip
would not — unsupported gathers, untiled blocks, too much VMEM, a store
that does not fit the device.  Shapes are the ones ``chip_smoke.py``
serves at: 64-request batches over a 2**20-row store whose UNION window
gathers 2 x 256 + 1 history rows per request (pow2: 1024).

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and test
workers that decide at import time whether these tests exist would
collect different test sets.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.data.synthetic import make_action_tables
from repro.kernels import dispatch
from repro.kernels.feature_hash.kernel import feature_hash_pallas
from repro.kernels.unit_fold import kernel as uf_kernel
from repro.kernels.unit_fold import ops as uf_ops
from repro.core import compile_script
from repro.core.lowering import drivers
from repro.core.lowering import windows as W
from repro.serve.engine import FeatureEngine

from examples.leaf_families import SQL as UNIT_SQL

SMOKE_CAPACITY = 1 << 20
BATCH = 64
ONLINE_ROWS = 1024
HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _unit_plan():
    cs = compile_script(UNIT_SQL, distinct_hll_p=None)
    (members,) = W.group_windows(cs.windows)
    leaves = {}
    for m in members:
        for k, leaf in W.unique_leaves(m.aggs).items():
            leaves.setdefault(k, leaf)
    return uf_ops.plan_for([m.node.spec for m in members], leaves, "ts")


@pytest.mark.parametrize("u,rows,nq", [
    (BATCH, ONLINE_ROWS, 1),     # online fast path: one query per unit
    (16, 256, 256),              # offline block: every row queried
])
def test_unit_fold_kernel_lowers_for_v5e(one_chip, u, rows, nq):
    """Every leaf family of ``UNIT_SQL`` (stacked add/min/max scans and
    sparse tables, EW scan, drawdown tree) in one Mosaic kernel."""
    plan, ident = _unit_plan()
    widths = [g.width for g in plan.groups]
    assert {g.kind for g in plan.groups} == {"scan", "sparse", "tree"}

    def fold(ts, q, *rest):
        n = len(widths)
        return uf_kernel.unit_fold_pallas(
            plan, list(rest[:n]), list(rest[n:]), ts, q, r_real=rows,
            interpret=False)

    args = ([_sds((u, rows), jnp.int32, one_chip),
             _sds((u, nq), jnp.int32, one_chip)]
            + [_sds((u, rows, w), jnp.float32, one_chip) for w in widths]
            + [_sds((1, w), jnp.float32, one_chip) for w in widths])
    compiled = jax.jit(fold).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_feature_hash_kernel_lowers_for_v5e(one_chip):
    fn = functools.partial(feature_hash_pallas, dim=1 << 20,
                           interpret=False)
    compiled = jax.jit(fn).lower(
        _sds((64,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def smoke_engine():
    from examples.quickstart import SQL

    tables = make_action_tables(n_actions=200, n_orders=100, n_users=16,
                                horizon_ms=100_000, seed=0)
    return FeatureEngine(SQL, tables, capacity=64)


def _store_shapes(engine, sharding):
    """The engine's store tables as shapes at the smoke's capacity."""
    def grow(x):
        shape = (SMOKE_CAPACITY,) + x.shape[1:] if x.ndim else x.shape
        return _sds(shape, x.dtype, sharding)
    return jax.tree_util.tree_map(grow, engine.store.tables)


def _request_shapes(engine, sharding):
    base = engine.cs.script.base_table
    vals = {c: _sds((BATCH,), jnp.float32, sharding)
            for c in engine._need[base]}
    return (_sds((BATCH,), jnp.int32, sharding),
            _sds((BATCH,), jnp.int32, sharding), vals)


def test_staged_online_step_fits_one_chip(one_chip, smoke_engine):
    """The jitted staged ``online_batch`` step over a 2**20-row store
    compiles for v5e, and its arguments, outputs and temporaries fit
    one chip's memory."""
    cs = smoke_engine.cs
    step = jax.jit(jax.vmap(
        functools.partial(cs._online_fn, use_preagg=False),
        in_axes=(None, 0, 0, 0, None)))
    compiled = step.lower(_store_shapes(smoke_engine, one_chip),
                          *_request_shapes(smoke_engine, one_chip),
                          {}).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, mem


def test_fused_online_step_holds_unit_fold_kernel(one_chip, smoke_engine,
                                                  monkeypatch):
    """The fused serving step at the same size lowers with the compiled
    unit-fold kernel inside.  Dispatch sees this host's CPU, so the test
    tells it a TPU is the backend."""
    monkeypatch.setattr(dispatch, "tpu_available", lambda: True)
    step = jax.jit(functools.partial(
        drivers.online_fast_fn, smoke_engine.cs, use_pallas=True,
        interpret=False))
    compiled = step.lower(_store_shapes(smoke_engine, one_chip),
                          *_request_shapes(smoke_engine, one_chip)
                          ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES, mem


def _sorted_shapes(hlo_text: str):
    """Operand shapes of every ``sort`` in compiled HLO text."""
    import re

    return [re.findall(r"s32\[([\d,]+)\]", line.split(" sort(")[0])
            for line in hlo_text.splitlines() if " sort(" in line]


def _index_moves(hlo_text: str):
    """(line, shapes) of every ``gather`` and ``scatter`` in compiled HLO
    text: the shapes a gather's indices reach (its result) and a
    scatter's indices and updates."""
    import re

    ins = re.compile(r"\s+(?:ROOT )?%(\S+) = (.+?[\]})]) ([a-z][\w-]*)\(")
    types = {m.group(1): m.group(2) for m in map(ins.match,
                                                 hlo_text.splitlines()) if m}
    moves = []
    for line in hlo_text.splitlines():
        m = ins.match(line)
        if m and m.group(3) == "gather":
            moves.append((line, [m.group(2)]))
        elif m and m.group(3) == "scatter":
            args = re.findall(r"%(\S+?)[,)]", line.split(" scatter(")[1])
            moves.append((line, [types.get(a, "") for a in args[1:3]]))
    return moves


@pytest.fixture(scope="module", params=[1, 4])
def store_merge(request, topo):
    """(store rows per shard, batch pad, compiled HLO text) of the ingest
    merge (``insert_many``, and its stacked form over one shard per chip)
    at the smoke's store size."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.storage import timestore

    n_shards = request.param
    rows, m = SMOKE_CAPACITY // n_shards, 512
    if n_shards == 1:
        sh, lead, fn = (SingleDeviceSharding(topo.devices[0]), (),
                        timestore.insert_many)
    else:
        mesh = Mesh(np.asarray(topo.devices), ("shard",))
        sh, lead, fn = (NamedSharding(mesh, P("shard")), (n_shards,),
                        timestore.insert_many_stacked)
    cols = ("a", "b", "c")
    state = {"keys": _sds(lead + (rows,), jnp.int32, sh),
             "ts": _sds(lead + (rows,), jnp.int32, sh),
             "cols": {c: _sds(lead + (rows,), jnp.float32, sh)
                      for c in cols},
             "count": _sds(lead, jnp.int32, sh)}
    batch = _sds(lead + (m,), jnp.int32, sh)
    compiled = fn.lower(
        state, batch, batch,
        {c: _sds(lead + (m,), jnp.float32, sh) for c in cols},
        _sds(lead, jnp.int32, sh)).compile()
    return rows, m, compiled.as_text()


def test_store_merge_sorts_only_the_batch(store_merge):
    """The ingest merge sorts only the incoming rows: a sort over the
    2**20-row store takes minutes to compile for a TPU, once per
    follower device."""
    _, m, hlo = store_merge
    sorted_shapes = _sorted_shapes(hlo)
    assert sorted_shapes, "the batch is no longer sorted"
    for shapes in sorted_shapes:
        for shape in shapes:
            assert int(shape.split(",")[-1]) == m, sorted_shapes


def test_store_merge_streams_the_store(store_merge):
    """The ingest merge moves the store by static shifts: no gather or
    scatter by a store-length index array, and no loop at all (its
    binary searches are unrolled).  A search of every slot and a gather
    per column took ~2 s per 1,024-row apply at 2**23 rows on a v5e."""
    import re

    rows, _, hlo = store_merge
    moves = _index_moves(hlo)
    assert moves, "the parser finds no gather or scatter"
    store = re.compile(rf"[\[,]{rows}[\],]")
    assert not [line for line, shapes in moves
                if any(store.search(s) for s in shapes)]
    assert " while(" not in hlo


def test_sharded_online_step_compiles_for_four_chips(topo, smoke_engine):
    """The key-sharded serving step (``jax.shard_map`` over a 1-D mesh
    of the host's four chips) compiles with one store shard per chip,
    each 2**19 rows, and needs no collective: a request's rows all live
    on its key's shard."""
    import types

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices), ("shard",))
    per_shard = NamedSharding(mesh, P("shard"))
    b_pad = 16                      # 64 requests over 4 shards
    store = types.SimpleNamespace(mesh=mesh, axis="shard")
    step = drivers._sharded_store_fn(smoke_engine.cs, store, False, b_pad)

    def stacked(x):
        shape = (4, SMOKE_CAPACITY // 2) + x.shape[1:] if x.ndim else (4,)
        return _sds(shape, x.dtype, per_shard)

    base = smoke_engine.cs.script.base_table
    vals = {c: _sds((4, b_pad), jnp.float32, per_shard)
            for c in smoke_engine._need[base]}
    compiled = step.lower(
        jax.tree_util.tree_map(stacked, smoke_engine.store.tables),
        _sds((4, b_pad), jnp.int32, per_shard),
        _sds((4, b_pad), jnp.int32, per_shard), vals, {}).compile()
    text = compiled.as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES, mem
