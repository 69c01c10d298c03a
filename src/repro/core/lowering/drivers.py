"""Execution drivers — thin executors over the shared lowering.

OFFLINE (batch over whole tables).  One host-side plan (merge + sort +
§6.2 partition units per window GROUP, ``lower_group_offline``) feeds
the schedules:

* ``offline_fused``   — every window group in ONE jitted program; XLA
                        overlaps the independent subgraphs (§6.1
                        window-parallel, the default);
* ``offline_serial``  — one jitted program per group with a host
                        barrier in between;
* ``offline_sharded`` — units LPT-assigned to shards, folded under
                        ``shard_map`` on a 1-D device mesh (or a stacked
                        vmap when ``mesh`` is None).  Because the unit
                        plan is data-derived and each unit's padded
                        program is identical under every schedule, the
                        sharded result is BIT-EXACT vs the single-device
                        drivers — consistency by construction, not by
                        tolerance (tests/test_offline_sharded.py);
* ``offline_reference_serial`` — the SEED algorithm (per-branch in-trace
                        lexsort + global folds), kept as the measured
                        baseline for benchmarks/bench_offline.py.

ONLINE (request mode).  ``online_fn`` is the per-request trace the
scalar, batched (vmap), and key-sharded (shard_map) drivers all share.
Each window group gathers the request key's history into the SAME
padded unit layout the offline plan builds (``windows.gather_unit``)
and queries the SAME unit fold core (``windows.fold_unit``) at the
request position — offline and online are two gather strategies over
one fold engine, so raw request results are bitwise equal to
``offline()``, floats included.  ``online_fast_fn`` is the fused
megakernel path: a scatter-merge gather (``gather_unit_fused``) and ONE
``kernels.unit_fold`` dispatch per window group for the whole batch —
bitwise equal to the vmapped ``online_batch``.  LAST JOINs and scalar
items resolve through the same ``lowering`` modules the offline
schedules use — no fold or join is defined twice.

Every driver honors the context's fold-implementation selector
(``windows.fold_impl``): with ``fused_unit_fold`` set, the staged
per-leaf build/query inside ``fold_unit``/``fold_units`` is swapped for
the fused op — same bits, one dispatch — and the selector is part of
every compilation-cache key.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...spans import span
from ...storage import timestore
from .. import skew

from . import joins, scalars, windows
from .cache import cached
from .windows import (GroupLowering, LoweredWindow, fold_impl, fold_unit,
                      fold_units, gather_edges, gather_unit,
                      gather_unit_fused, group_windows,
                      lower_group_offline, unique_leaves)

__all__ = [
    "plan_offline", "offline_fused", "offline_serial", "offline_sharded",
    "offline_branch", "offline_reference_serial", "online_fn",
    "online_window_unit", "online_fast_fn", "pad_batch", "store_fn",
    "online", "online_batch", "online_sharded_batch", "online_batch_fast",
    "online_batch_fast_hlo",
]


# ===========================================================================
# OFFLINE
# ===========================================================================


def _np_arrays(tables) -> Dict[str, Dict[str, np.ndarray]]:
    return {name: {c: np.asarray(v)
                   for c, v in t.device_columns().items()}
            for name, t in tables.items()}


def _tables_sig(tables) -> Tuple:
    """Cache key for a table set: schema/length signature PLUS a content
    fingerprint — in-place column mutation or a recycled dict id must
    miss the plan cache, never serve stale features."""
    import hashlib

    sig = []
    for name, t in sorted(tables.items()):
        h = hashlib.blake2b(digest_size=8)
        for c in sorted(t.schema.column_names):
            h.update(np.ascontiguousarray(t.columns[c]).tobytes())
        sig.append((name, len(t), tuple(sorted(t.schema.column_names)),
                    h.hexdigest()))
    return tuple(sig)


def plan_offline(cs, tables) -> Tuple[List[GroupLowering],
                                      Dict[str, Dict[str, np.ndarray]], int]:
    """Host-side offline plan: merged + sorted + §6.2-partitioned window
    inputs for every branch.  Derived from the data and the compile
    context only — the same plan backs every schedule.

    Cached per table-set content fingerprint on the CompiledScript —
    repeated offline calls over the same tables (the common
    materialize-then-iterate loop) skip the re-plan and keep the plan's
    device buffers resident, the offline counterpart of the
    per-store-identity cache on the online path.
    """
    cache = getattr(cs, "_offline_plan_cache", None)
    if cache is None:
        cache = cs._offline_plan_cache = {}
    # content fingerprint only: a fresh dict with identical tables must
    # hit, an in-place mutation must miss
    with span("offline.fingerprint"):
        key = _tables_sig(tables)
        hit = cache.get(key)
    if hit is not None:
        return hit
    with span("offline.plan"):
        arrays = _np_arrays(tables)
        n_base = len(tables[cs.script.base_table])
        lws = []
        for members in group_windows(cs.windows):
            with span("offline.plan.group") as sp:
                gl = lower_group_offline(
                    members, arrays, cs.script.base_table, n_base,
                    target_rows=cs.ctx.offline_slice_rows,
                    max_slices=cs.ctx.offline_max_slices)
                if sp.is_enabled():
                    sp.set_metadata(**gl.fill())
            lws.append(gl)
    cache.clear()          # keep at most one resident plan per script
    cache[key] = (lws, arrays, n_base)
    return lws, arrays, n_base


def _join_scalar_fn(cs):
    """Traced LAST JOIN + scalar tail, closed over STATIC metadata only
    (script/plan/join_cols) — never over ``cs`` itself, which would pin
    its tables and resident offline plan in the global compilation
    cache."""
    script, plan, join_cols = cs.script, cs.plan, cs.join_cols

    def fn(arrays_dev):
        env = dict(arrays_dev[script.base_table])
        for js in script.last_joins:
            env.update(joins.offline_last_join(arrays_dev, js, script,
                                               join_cols))
        return scalars.eval_scalar_items(plan, env)
    return fn


def _group_feats(members: List[LoweredWindow], dev, impl=None
                 ) -> List[Dict[str, jnp.ndarray]]:
    """Finalized features per unit block of one group (leaf folds shared
    across member windows inside ``fold_units``; under a fused impl the
    flat lane lifts are built once here and shared by every block)."""
    prelift = (windows.fused_prelift(members, dev)
               if impl is not None else None)
    out = []
    for blk in dev["blocks"]:
        per_member = fold_units(members, dict(dev, **blk), impl=impl,
                                prelift=prelift)
        feats: Dict[str, jnp.ndarray] = {}
        for m, folded in zip(members, per_member):
            for name, agg in zip(m.feature_names, m.aggs):
                feats[name] = agg.finalize(folded)
        out.append(feats)
    return out


def _scatter_group(gl: GroupLowering, feats: List[Dict[str, Any]],
                   n_base: int, out: Dict[str, np.ndarray]):
    """Host-side ConcatJoin: place emitted unit rows back in base-row
    order (each base row is emitted by exactly one unit)."""
    for blk, bf in zip(gl.blocks, feats):
        rows = gl.orig[blk.idx][blk.emit]
        for name, feat in bf.items():
            feat = np.asarray(feat)
            buf = out.get(name)
            if buf is None:
                buf = np.zeros((n_base,) + feat.shape[2:], feat.dtype)
                out[name] = buf
            buf[rows] = feat[blk.emit]


def _plan_sig(cs, lws: Sequence[GroupLowering], arrays) -> Tuple:
    shapes = tuple(sorted(
        (name, tuple((c, v.shape) for c, v in sorted(cols.items())))
        for name, cols in arrays.items()))
    # the fold-implementation selector is part of the signature: the
    # same script compiled with/without the fused unit fold must never
    # share a traced program
    return (cs.fingerprint, fold_impl(cs.ctx),
            tuple(lw.signature for lw in lws), shapes)


def offline_fused(cs, tables) -> Dict[str, np.ndarray]:
    """Default offline schedule: all groups + joins + scalars, one jit."""
    with span("offline.call"):
        return _offline_fused(cs, tables)


def _offline_fused(cs, tables) -> Dict[str, np.ndarray]:
    lws, arrays, n_base = plan_offline(cs, tables)
    key = ("offline_fused", _plan_sig(cs, lws, arrays))
    # the cached closure must capture only static metadata — closing
    # over the GroupLowerings (or cs itself) would pin host columns and
    # resident device buffers in the never-evicted compilation cache
    members_per_group = [gl.members for gl in lws]
    js_fn = _join_scalar_fn(cs)
    impl = fold_impl(cs.ctx)

    def build():
        def fn(devs, arrays_dev):
            branch = [_group_feats(members, dev, impl)
                      for members, dev in zip(members_per_group, devs)]
            return branch, js_fn(arrays_dev)
        return jax.jit(fn)

    fn = cached(key, build)
    arrays_dev = {t: {c: jnp.asarray(v) for c, v in cols.items()}
                  for t, cols in arrays.items()}
    branch, flat = fn([gl.device_args() for gl in lws], arrays_dev)
    # the copies below would wait for the outputs anyway; waiting here
    # keeps the device's time out of the scatter span
    jax.block_until_ready((branch, flat))
    with span("offline.scatter"):
        out: Dict[str, np.ndarray] = {}
        for gl, feats in zip(lws, branch):
            _scatter_group(gl, feats, n_base, out)
        for name, v in flat.items():
            out[name] = np.asarray(v)
        return scalars.select_outputs(cs.script, out)


def offline_branch(cs, tables, wi: int) -> Dict[str, np.ndarray]:
    """One window branch alone (ConcatJoin alignment checks)."""
    lws, arrays, n_base = plan_offline(cs, tables)
    target = cs.windows[wi]
    gi, gl = next((i, g) for i, g in enumerate(lws)
                  if target in g.members)
    key = ("offline_group", gi, _plan_sig(cs, lws, arrays))
    members = gl.members          # capture metadata only (see above)
    impl = fold_impl(cs.ctx)
    fn = cached(key, lambda: jax.jit(
        lambda dev: _group_feats(members, dev, impl)))
    feats = fn(gl.device_args())
    out: Dict[str, np.ndarray] = {}
    _scatter_group(gl, feats, n_base, out)
    return {name: out[name] for name in target.feature_names}


def offline_serial(cs, tables) -> Dict[str, np.ndarray]:
    """Serialized schedule: window groups one-by-one with a host barrier
    between them.  Group programs are jit-cached — the gap vs
    ``offline_fused``/``offline_sharded`` is scheduling, not re-tracing.
    (The *seed-algorithm* baseline is ``offline_reference_serial``.)"""
    lws, arrays, n_base = plan_offline(cs, tables)
    out: Dict[str, np.ndarray] = {}
    impl = fold_impl(cs.ctx)
    for gi, gl in enumerate(lws):
        key = ("offline_group", gi, _plan_sig(cs, lws, arrays))
        members = gl.members      # capture metadata only (see above)
        fn = cached(key, lambda members=members: jax.jit(
            lambda dev: _group_feats(members, dev, impl)))
        feats = fn(gl.device_args())
        jax.block_until_ready(feats)           # hard barrier
        _scatter_group(gl, feats, n_base, out)
    key = ("offline_scalars", _plan_sig(cs, lws, arrays))
    fn = cached(key, lambda: jax.jit(_join_scalar_fn(cs)))
    arrays_dev = {t: {c: jnp.asarray(v) for c, v in cols.items()}
                  for t, cols in arrays.items()}
    for name, v in fn(arrays_dev).items():
        out[name] = np.asarray(v)
    return scalars.select_outputs(cs.script, out)


def _stack_window(lw: GroupLowering, n_shards: int):
    """LPT-assign one branch's units to shards and re-block every unit
    class into per-shard stacks (S, U_pad, R).  Padding units are
    all-invalid; the flat row arrays are replicated (they are the
    un-expanded inputs — each shard gathers only its units' halo context
    from them).  Host arrays are cached on the lowering per shard count.
    """
    cache = getattr(lw, "_stacked", None)
    if cache is None:
        cache = lw._stacked = {}
    hit = cache.get(n_shards)
    if hit is not None:
        return hit
    n_units = sum(b.unit_ids.size for b in lw.blocks)
    sizes = np.zeros(max(1, n_units), np.int64)
    for b in lw.blocks:
        sizes[b.unit_ids] = b.sizes
    owner = skew.assign_units_lpt(sizes, n_shards)
    n_flat = lw.ts.shape[0] - 1
    stacked = []
    for b in lw.blocks:
        b_owner = owner[b.unit_ids] if b.unit_ids.size else \
            np.zeros((0,), np.int32)
        u, r = b.idx.shape
        counts = np.bincount(b_owner, minlength=n_shards)
        u_pad = max(1, int(counts.max()))
        idx = np.full((n_shards, u_pad, r), n_flat, b.idx.dtype)
        valid = np.zeros((n_shards, u_pad, r), bool)
        emit = np.zeros((n_shards, u_pad, r), bool)
        for s in range(n_shards):
            sel = np.flatnonzero(b_owner == s)
            idx[s, :sel.size] = b.idx[sel]
            valid[s, :sel.size] = b.valid[sel]
            emit[s, :sel.size] = b.emit[sel]
        stacked.append({"idx": idx, "valid": valid, "emit": emit})
    cache[n_shards] = stacked
    return stacked


def _mesh_key(mesh) -> Optional[Tuple]:
    """Stable mesh identity: the device ids + axis names (two same-size
    meshes over different devices must never share cached programs or
    placements; ``id(mesh)`` can alias after gc)."""
    if mesh is None:
        return None
    return (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)


def _sharded_device_args(lws, n_shards: int, mesh, axis: str):
    """Per-shard stacked blocks + replicated flats, placed on the mesh
    ONCE and cached — repeated sharded offline calls reuse resident
    device buffers instead of re-transferring the plan."""
    key = (n_shards, _mesh_key(mesh))
    lw0 = lws[0] if lws else None
    cache = getattr(lw0, "_sharded_dev", None) if lw0 else {}
    if lw0 is not None and cache is None:
        cache = lw0._sharded_dev = {}
    hit = cache.get(key) if lw0 is not None else None
    if hit is not None:
        return hit
    stacked = [[{k: jnp.asarray(v) for k, v in blk.items()}
                for blk in _stack_window(lw, n_shards)] for lw in lws]
    flats = [{"cols": {c: jnp.asarray(v) for c, v in lw.cols.items()},
              "ts": jnp.asarray(lw.ts), "orig": jnp.asarray(lw.orig)}
             for lw in lws]
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh, P(axis))
        rep = NamedSharding(mesh, P())
        stacked = jax.device_put(stacked, sh)
        flats = jax.device_put(flats, rep)
    if lw0 is not None:
        cache[key] = (stacked, flats)
    return stacked, flats


def offline_sharded(cs, tables, mesh=None, n_shards: Optional[int] = None,
                    axis: str = "shard") -> Dict[str, np.ndarray]:
    """Key-partitioned offline execution across a device mesh (§6).

    Every branch's partition units (whole cold keys; hot keys
    time-sliced with halo rows — ``core.skew``) are LPT-assigned to
    shards and folded under ``shard_map`` on the mesh (or a stacked vmap
    on one device when ``mesh`` is None).  The units, their padded
    shapes, and their fold programs are identical to the single-device
    schedules', so results are bit-exact vs ``offline()`` for any shard
    count.  LAST JOINs and scalar items are per-base-row lookups with no
    window state; they run once on the default device.
    """
    if mesh is not None:
        n_shards = int(mesh.devices.size)
    n_shards = int(n_shards or 1)
    lws, arrays, n_base = plan_offline(cs, tables)
    sig = _plan_sig(cs, lws, arrays)
    if not lws:
        # scalar/LAST-JOIN-only script: nothing to shard (per-base-row
        # lookups carry no window state) — same one-device tail as the
        # fused schedule instead of an empty shard fan-out
        return offline_fused(cs, tables)
    stacked, flats = _sharded_device_args(lws, n_shards, mesh, axis)

    key = ("offline_sharded", n_shards, _mesh_key(mesh), axis, sig)
    members_per_group = [gl.members for gl in lws]   # metadata only
    impl = fold_impl(cs.ctx)

    def build():
        def per_shard(devs):
            return [_group_feats(members, dev, impl)
                    for members, dev in zip(members_per_group, devs)]

        if mesh is None:
            def fn(stacked, flats):
                def one(stk):
                    devs = [dict(flat, blocks=stk_w)
                            for flat, stk_w in zip(flats, stk)]
                    return per_shard(devs)
                return jax.vmap(one)(stacked)
            return jax.jit(fn)

        from jax.sharding import PartitionSpec as P

        tm = jax.tree_util.tree_map

        def mapped(stacked, flats):
            stk = tm(lambda x: x[0], stacked)
            devs = [dict(flat, blocks=stk_w)
                    for flat, stk_w in zip(flats, stk)]
            return tm(lambda x: x[None], per_shard(devs))

        def fn(stacked, flats):
            return jax.shard_map(
                mapped, mesh=mesh, in_specs=(P(axis), P()),
                out_specs=P(axis))(stacked, flats)
        return jax.jit(fn)

    fn = cached(key, build)
    branch = fn(stacked, flats)

    out: Dict[str, np.ndarray] = {}
    for gl, feats in zip(lws, branch):
        host_blocks = _stack_window(gl, n_shards)
        for blk, bf in zip(host_blocks, feats):
            rows = gl.orig[blk["idx"]][blk["emit"]]
            for name, feat in bf.items():
                feat = np.asarray(feat)           # (S, U_pad, R, *extra)
                buf = out.get(name)
                if buf is None:
                    buf = np.zeros((n_base,) + feat.shape[3:], feat.dtype)
                    out[name] = buf
                buf[rows] = feat[blk["emit"]]

    key2 = ("offline_scalars", sig)
    fn2 = cached(key2, lambda: jax.jit(_join_scalar_fn(cs)))
    arrays_dev = {t: {c: jnp.asarray(v) for c, v in cols.items()}
                  for t, cols in arrays.items()}
    for name, v in fn2(arrays_dev).items():
        out[name] = np.asarray(v)
    return scalars.select_outputs(cs.script, out)


def offline_reference_serial(cs, tables) -> Dict[str, np.ndarray]:
    """The SEED offline path, kept as the measured baseline: per-branch
    in-trace source merge + device lexsort + global segmented-scan /
    global segment-tree fold (``core.window.fold_windows``) with a host
    barrier between branches — no shared layout, no §6.2 units, no
    window-parallel fusion; a skewed hot key rides one partition and
    every branch re-sorts the whole input.
    ``benchmarks/bench_offline.py`` reports the unified engine's
    schedules against this.  Float results agree with the unit engine to
    reduction-order tolerance (integer features bitwise), same as the
    offline/online consistency contract."""
    from ..window import fold_windows, segment_starts, window_bounds

    lws, arrays, n_base = plan_offline(cs, tables)
    out: Dict[str, np.ndarray] = {}

    def branch_fn(w):
        spec = w.node.spec
        cols_needed = set(w.needed_cols) | {spec.partition_by,
                                            spec.order_by}

        def fn(arrays_dev):
            parts = []
            for rank, tname in enumerate(w.sources):
                cols = arrays_dev[tname]
                n_t = next(iter(cols.values())).shape[0]
                is_base = tname == cs.script.base_table and \
                    rank == len(w.sources) - 1
                part = {c: cols[c] for c in cols_needed}
                part["__rank__"] = jnp.full((n_t,), rank, jnp.int32)
                part["__arrival__"] = jnp.arange(n_t, dtype=jnp.int32)
                part["__orig__"] = (jnp.arange(n_t, dtype=jnp.int32)
                                    if is_base
                                    else jnp.full((n_t,), n_base,
                                                  jnp.int32))
                parts.append(part)
            merged = {k: jnp.concatenate([p[k] for p in parts])
                      for k in parts[0]}
            key_col = merged[spec.partition_by].astype(jnp.int32)
            ts_col = merged[spec.order_by].astype(jnp.int32)
            perm = jnp.lexsort((merged["__arrival__"], merged["__rank__"],
                                ts_col, key_col))
            env = {k: jnp.take(v, perm, axis=0) for k, v in merged.items()}
            key_s = jnp.take(key_col, perm)
            ts_s = jnp.take(ts_col, perm)
            n = key_s.shape[0]
            seg_start = segment_starts(key_s)
            seg_flag = jnp.arange(n, dtype=jnp.int32) == seg_start
            start, end = window_bounds(spec, key_s, ts_s, seg_start)
            feats = fold_windows(w.aggs, env, start, end, seg_start,
                                 seg_flag)
            outs = []
            for f in feats:
                buf = jnp.zeros((n_base,) + f.shape[1:], f.dtype)
                outs.append(buf.at[env["__orig__"]].set(f, mode="drop"))
            return outs
        return fn

    arrays_dev = {t: {c: jnp.asarray(v) for c, v in cols.items()}
                  for t, cols in arrays.items()}
    for wi, w in enumerate(cs.windows):       # one full pass PER WINDOW
        key = ("offline_reference", wi, _plan_sig(cs, lws, arrays))
        fn = cached(key, lambda w=w: jax.jit(branch_fn(w)))
        feats = fn(arrays_dev)
        jax.block_until_ready(feats)          # hard barrier
        for name, v in zip(w.feature_names, feats):
            out[name] = np.asarray(v)
    key2 = ("offline_scalars", _plan_sig(cs, lws, arrays))
    fn2 = cached(key2, lambda: jax.jit(_join_scalar_fn(cs)))
    arrays_dev = {t: {c: jnp.asarray(v) for c, v in cols.items()}
                  for t, cols in arrays.items()}
    for name, v in fn2(arrays_dev).items():
        out[name] = np.asarray(v)
    return scalars.select_outputs(cs.script, out)


# ===========================================================================
# ONLINE
# ===========================================================================


def pad_batch(keys, ts, values):
    """Pad a request batch to the next power of two by replicating the
    last request (per-request computations are independent, so padding
    never changes real rows' results and recompiles stay logarithmic in
    batch size).  Returns (keys, ts, values, b_real)."""
    keys = np.asarray(keys, np.int32)
    tsa = np.asarray(ts, np.int32)
    b = keys.shape[0]
    if b == 0:
        raise ValueError("empty request batch")
    b_pad = timestore.next_pow2(b)
    vals = {k: np.asarray(v, np.float32) for k, v in values.items()}
    if b_pad > b:
        pad = [(0, b_pad - b)]
        keys = np.pad(keys, pad, mode="edge")
        tsa = np.pad(tsa, pad, mode="edge")
        vals = {k: np.pad(v, pad, mode="edge") for k, v in vals.items()}
    return keys, tsa, vals, b


def store_fn(cs, store, kind: str, extra: Tuple, builder):
    """Two-level jitted-fn cache: a per-store-identity hot path over the
    global compilation cache (§4.2) keyed by plan fingerprint + store
    shape signature."""
    local_key = (id(store), store.capacity, kind) + extra
    fn = cs._online_fns.get(local_key)
    if fn is None:
        sig = tuple(sorted((t, s["keys"].shape[0]) for t, s in
                           store.tables.items()))
        cache_key = (kind, cs.fingerprint, sig) + extra
        fn = cached(cache_key, builder)
        cs._online_fns[local_key] = fn
    return fn


def online(cs, store, key: int, ts: int, values: Dict[str, float],
           preagg_states=None) -> Dict[str, np.ndarray]:
    """Features for one request tuple (virtually inserted)."""
    use_pre = preagg_states is not None
    fn = store_fn(
        cs, store, "online", (use_pre, fold_impl(cs.ctx)),
        lambda: jax.jit(functools.partial(
            cs._online_fn, use_preagg=use_pre)))
    vals = {k: jnp.asarray(v, jnp.float32) for k, v in values.items()}
    out = fn(store.tables, jnp.int32(key), jnp.int32(ts), vals,
             preagg_states if use_pre else {})
    if use_pre:
        cs._observe_queries([int(ts)])
    return {k: np.asarray(v) for k, v in out.items()}


def online_batch(cs, store, keys, ts, values, preagg_states=None
                 ) -> Dict[str, np.ndarray]:
    """Features for B requests in ONE jitted call (vmapped online
    driver); bit-identical to B scalar ``online`` calls."""
    with span("serve.encode"):
        keys, tsa, vals_np, b = pad_batch(keys, ts, values)
    use_pre = preagg_states is not None
    fn = store_fn(
        cs, store, "online_batch",
        (use_pre, keys.shape[0], fold_impl(cs.ctx)),
        lambda: jax.jit(jax.vmap(
            functools.partial(cs._online_fn, use_preagg=use_pre),
            in_axes=(None, 0, 0, 0, None))))
    vals = {k: jnp.asarray(v) for k, v in vals_np.items()}
    out = fn(store.tables, jnp.asarray(keys), jnp.asarray(tsa), vals,
             preagg_states if use_pre else {})
    if use_pre:
        cs._observe_queries(tsa[:b].tolist())
    with span("serve.fetch"):
        return {k: np.asarray(v)[:b] for k, v in out.items()}


def online_sharded_batch(cs, store, keys, ts, values, preagg_states=None
                         ) -> Dict[str, np.ndarray]:
    """Features for B requests against a ``ShardedOnlineStore``: host
    key-routing into (n_shards, b_pad) blocks, one jitted ``shard_map``
    fan-out running the same vmapped ``_online_fn`` per shard (bit-exact
    vs the unsharded path — window folds never gather across shards),
    request-order reassembly.  With ``store.mesh is None`` the identical
    computation runs as a vmap over the stacked shard dim."""
    ok, why = cs.sharded_eligible()
    if not ok:
        raise ValueError(f"script not shardable by key: {why}")
    keys = np.asarray(keys, np.int32)
    tsa = np.asarray(ts, np.int32)
    b = keys.shape[0]
    if b == 0:
        raise ValueError("empty request batch")
    use_pre = preagg_states is not None
    if use_pre:
        # same bounded-universe contract as the sharded pre-agg update:
        # a request routed by a raw key >= n_keys would read another
        # shard's alias plane (see PreAgg.update_many_sharded)
        nks = [w.preagg.n_keys for w in cs.windows
               if w.preagg is not None]
        if nks and (int(keys.max()) >= min(nks) or int(keys.min()) < 0):
            raise ValueError(
                f"request key outside the pre-agg key universe "
                f"[0, {min(nks)}) — not servable bit-exactly from "
                f"key-sharded bucket planes")
    vals_np = {k: np.asarray(v, np.float32) for k, v in values.items()}
    n_shards = store.n_shards
    owner = store.owner_of_keys(keys)
    counts = np.bincount(owner, minlength=n_shards)
    # pad the per-shard sub-batch: pow2 while small, then multiples of
    # 32 — near-balanced routing (max count ~ B/S) would waste up to 2x
    # work under pure pow2 padding, and recompile count stays bounded
    # (one fn per bucket)
    c_max = int(max(1, counts.max()))
    b_pad = (timestore.next_pow2(c_max) if c_max <= 32
             else ((c_max + 31) // 32) * 32)
    # req_idx[s, j] = which request shard s computes in slot j; padding
    # replicates the shard's last real request (empty shards recompute
    # request 0 — discarded either way)
    req_idx = np.zeros((n_shards, b_pad), np.int64)
    slot = np.empty(b, np.int64)
    for s in range(n_shards):
        sel = np.flatnonzero(owner == s)
        slot[sel] = np.arange(sel.size)
        req_idx[s, :sel.size] = sel
        if sel.size:
            req_idx[s, sel.size:] = sel[-1]
    fn = _sharded_store_fn(cs, store, use_pre, b_pad)
    vals = {c: jnp.asarray(v[req_idx]) for c, v in vals_np.items()}
    out = fn(store.tables, jnp.asarray(keys[req_idx]),
             jnp.asarray(tsa[req_idx]), vals,
             preagg_states if use_pre else {})
    if use_pre:
        cs._observe_queries(tsa.tolist())
    with span("serve.fetch"):
        return {k: np.asarray(v)[owner, slot] for k, v in out.items()}


def _sharded_store_fn(cs, store, use_pre: bool, b_pad: int):
    """Jitted (shard_map or stacked-vmap) online driver, cached per
    (store identity, preagg mode, padded sub-batch size, fold impl)."""
    local_key = (id(store), "sharded", use_pre, b_pad, fold_impl(cs.ctx))
    fn = cs._online_fns.get(local_key)
    if fn is not None:
        return fn
    one = functools.partial(cs._online_fn, use_preagg=use_pre)
    per_shard = jax.vmap(one, in_axes=(None, 0, 0, 0, None))
    if store.mesh is None:
        fn = jax.jit(jax.vmap(per_shard, in_axes=(0, 0, 0, 0, 0)))
    else:
        from jax.sharding import PartitionSpec as P

        tm = jax.tree_util.tree_map

        def mapped(states, kb, tb, vb, pre):
            local = tm(lambda x: x[0], states)
            out = per_shard(local, kb[0], tb[0],
                            tm(lambda x: x[0], vb),
                            tm(lambda x: x[0], pre))
            return tm(lambda x: x[None], out)

        spec = P(store.axis)
        fn = jax.jit(jax.shard_map(
            mapped, mesh=store.mesh, in_specs=(spec,) * 5,
            out_specs=spec))
    cs._online_fns[local_key] = fn
    return fn


def online_batch_fast(cs, store, keys, ts, values, use_pallas=None,
                      interpret=None) -> Dict[str, np.ndarray]:
    """Fused megakernel fast path entry (see ``online_fast_fn``) —
    bitwise equal to ``online_batch``."""
    fn, args, b = _fast_step(cs, store, keys, ts, values, use_pallas,
                             interpret)
    out = fn(*args)
    with span("serve.fetch"):
        return {k: np.asarray(v)[:b] for k, v in out.items()}


def online_batch_fast_hlo(cs, store, keys, ts, values, use_pallas=None,
                          interpret=None) -> str:
    """Compiled HLO text of the executable ``online_batch_fast`` runs
    for this batch (e.g. to check that it holds the Pallas kernel)."""
    fn, args, _ = _fast_step(cs, store, keys, ts, values, use_pallas,
                             interpret)
    return fn.lower(*args).compile().as_text()


def _fast_step(cs, store, keys, ts, values, use_pallas, interpret):
    """(cached jitted step, its arguments, real batch size)."""
    ok, why = cs.fast_batch_eligible()
    if not ok:
        raise ValueError(f"script not eligible for fused path: {why}")
    from ...kernels import dispatch
    use_pallas, interpret = dispatch.resolve(use_pallas, interpret,
                                             flag="unit_fold_pallas")
    with span("serve.encode"):
        keys, tsa, vals_np, b = pad_batch(keys, ts, values)
    # keys/ts/values are fresh per-call device buffers the caller never
    # reads back — donating them lets XLA alias the (B, R) gather
    # scratch onto them instead of allocating per request batch.  The
    # store tables (arg 0) stay undonated: they live across calls.
    # (The CPU runtime can't alias these buffers and would warn, so
    # donation turns on only where the runtime honors it.)
    donate = () if dispatch._platform() == "cpu" else (1, 2, 3)
    fn = store_fn(
        cs, store, "online_fast", (keys.shape[0], use_pallas, interpret),
        lambda: jax.jit(functools.partial(
            online_fast_fn, cs, use_pallas=use_pallas,
            interpret=interpret), donate_argnums=donate))
    vals = {k: jnp.asarray(v) for k, v in vals_np.items()}
    return fn, (store.tables, jnp.asarray(keys), jnp.asarray(tsa), vals), b


def online_window_unit(states, members: Sequence[LoweredWindow], key, ts,
                       values, impl=None) -> List[Dict[str, jnp.ndarray]]:
    """Serve one window GROUP for one request through the unit core:
    gather the key's history into the offline unit layout
    (``gather_unit``, or the scatter-merge ``gather_unit_fused`` under a
    fused impl) and query ``fold_unit`` at the request position.
    There is no online-only fold algebra — the scan / sparse-table /
    tree programs are the offline ones, which is what makes request
    results bitwise equal to ``offline()``, floats included."""
    if impl is not None:
        env, p = gather_unit_fused(states, members, key, ts, values)
    else:
        env, p = gather_unit(states, members, key, ts, values)
    folded = fold_unit(members, env, queries=p[None], impl=impl)
    return [{k: v[0] for k, v in f.items()} for f in folded]


def online_fn(cs, states, key, ts, values, preagg_states,
              use_preagg=False):
    """The per-request trace shared by the scalar, vmapped-batch, and
    key-sharded drivers.  Raw-served windows are grouped exactly like
    the offline plan (``group_windows``): one history gather and one
    structure build per group, member windows pay only bounds +
    queries."""
    impl = fold_impl(cs.ctx)
    out: Dict[str, jnp.ndarray] = {}
    raw_served: List[LoweredWindow] = []
    for wi, w in enumerate(cs.windows):
        if use_preagg and w.preagg is not None:
            folded = w.preagg.fold_online(
                states, w, key, ts, values, preagg_states[wi],
                gather=gather_edges)
            for name, agg in zip(w.feature_names, w.aggs):
                out[name] = agg.finalize(folded)
        else:
            raw_served.append(w)
    for members in group_windows(raw_served):
        per_member = online_window_unit(states, members, key, ts, values,
                                        impl=impl)
        for m, folded in zip(members, per_member):
            for name, agg in zip(m.feature_names, m.aggs):
                out[name] = agg.finalize(folded)

    env: Dict[str, jnp.ndarray] = dict(values)
    env[cs.script.order_column] = jnp.asarray(ts, jnp.int32)
    for js in cs.script.last_joins:
        env.update(joins.online_last_join(states, js, cs.join_cols, env,
                                          key, ts))
    out.update(scalars.eval_scalar_items(cs.plan, env))
    return scalars.select_outputs(cs.script, out)


def online_fast_fn(cs, states, keys, ts, values, use_pallas=False,
                   interpret=True):
    """Fused megakernel fast path: serve a whole request batch with ONE
    ``kernels.unit_fold`` dispatch per window group.

    The per-request gather is the scatter-merge ``gather_unit_fused``
    (vmapped over the batch); the stacked (B, R) unit envs then fold in
    one fused op — every member window, every deduplicated leaf, bounds
    + build + query — instead of B vmapped per-leaf folds.  Bitwise
    equal to ``online_batch`` on every leaf family and frame type
    (tests/test_online_batch.py): the gather produces the same unit
    rows and the fused op is ``array_equal`` to the staged fold it
    replaces."""
    out: Dict[str, jnp.ndarray] = {}
    for members in group_windows(cs.windows):
        spec0 = members[0].node.spec
        env, p = jax.vmap(
            lambda k, t, v: gather_unit_fused(states, members, k, t, v)
        )(keys, ts, values)
        group_leaves: Dict[str, Any] = {}
        for m in members:
            for k, leaf in unique_leaves(m.aggs).items():
                group_leaves.setdefault(k, leaf)
        from ...kernels.unit_fold import ops as unit_fold_ops
        fused = unit_fold_ops.unit_fold(
            [m.node.spec for m in members], group_leaves, env,
            p[:, None], order_by=spec0.order_by,
            member_keys=[tuple(unique_leaves(m.aggs)) for m in members],
            use_pallas=use_pallas, interpret=interpret)
        for m, f in zip(members, fused):
            folded = {k: f[k][:, 0] for k in unique_leaves(m.aggs)}
            for name, agg in zip(m.feature_names, m.aggs):
                out[name] = agg.finalize(folded)

    env = dict(values)
    env[cs.script.order_column] = ts
    for js in cs.script.last_joins:
        env.update(jax.vmap(
            lambda k, t, e: joins.online_last_join(
                states, js, cs.join_cols, e, k, t)
        )(keys, ts, env))
    out.update(scalars.eval_scalar_items(cs.plan, env))
    return scalars.select_outputs(cs.script, out)
