"""Window-fold lowering — the one place fold semantics are defined.

ONE FOLD ENGINE.  Every window fold in the system — offline batch,
online request, batched, key-sharded — runs through the *unit fold
core* (``fold_unit``): one padded unit of (key, ts, rank,
arrival)-sorted rows, one shared structure per deduplicated leaf
(§4.2 cycle binding), one bounds computation, one query program:

* invertible leaves   — inclusive combine-scan + prefix difference
                        (§5.2 subtract-and-evict), anchored at the key
                        segment's first row;
* idempotent leaves   — sparse-table min/max: any window in two lookups;
* order-sensitive     — per-unit ordered segment trees (§5.1's
  non-invertible leaves  structure).

The two executors differ only in how they GATHER rows into that layout:

* **offline unit engine** (``lower_group_offline`` → ``GroupLowering``,
  ``fold_units``) — the offline input is merged ONCE per window group,
  (key, ts, rank, arrival)-sorted, cut into partition units by
  ``core.skew`` (whole cold keys; hot keys time-sliced with halo rows),
  bucketed into power-of-two width classes, and folded as dense
  (units, rows) blocks — ``fold_unit`` vmapped over the units.  Because
  the unit plan is derived from the data alone, every schedule — fused,
  serial, shard_map — folds bit-identical blocks; *where* a unit runs
  never changes *what* it computes;
* **online unit gather** (``gather_unit``) — each request's key history
  is pulled from the live store into the same layout (same merge order,
  same sentinel padding, request row appended after its peers) and
  ``fold_unit`` is queried at the single request position.  Because the
  combine trees of the scan / sparse table / segment tree depend only
  on row values and unit positions — never on the padded width — the
  online result is **bitwise identical to the offline fold, floats
  included**, whenever the gather buffer covers the key's history and
  the offline plan did not time-slice the key (§6.2 slicing shifts the
  scan anchor; history overflowing the buffer truncates it — both
  degrade float equality to reduction-order tolerance, never change
  window semantics).

``gather_edges`` (bounded raw-edge gathers for §5.1 pre-aggregation)
is the only other store-read path; its bucket-decomposed combines are
inherently re-bracketed, so pre-agg serving is bitwise against offline
exactly when the leaf combines are order-insensitive in floats
(min/max, integer-valued sums/counts/histograms).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...spans import span
from ...storage import timestore
from ..expr import ColumnRef, collect_columns
from ..functions import Aggregator, Leaf, build_aggregator
from ..plan import FeaturePlan, FeatureScript, WindowAgg
from ..preagg import PreAgg
from .. import skew
from ..window import (first_geq, prefix_window_fold, sparse_levels,
                      sparse_query, tree_levels, tree_query)

__all__ = [
    "LoweredWindow", "lower_windows", "unique_leaves",
    "GroupLowering", "UnitBlock", "group_windows",
    "lower_group_offline", "unit_leaf_build", "unit_leaf_query",
    "unit_bounds", "fold_unit", "fold_units", "fold_impl",
    "gather_unit", "gather_unit_fused", "gather_edges", "INT_MIN",
]

INT_MIN = -(2**31) + 2


def fold_impl(ctx) -> Optional[Tuple[bool, Optional[bool], Optional[bool]]]:
    """The context's fold-implementation selector as a hashable cache-key
    component: ``None`` = staged per-leaf fold; ``(True, use_pallas,
    interpret)`` = the fused unit-fold op (``kernels.unit_fold``),
    whose results are bitwise-equal to the staged path."""
    if not getattr(ctx, "fused_unit_fold", False):
        return None
    return (True, ctx.unit_fold_pallas, ctx.unit_fold_interpret)


# ---------------------------------------------------------------------------
# Leaf plumbing (shared by every driver)
# ---------------------------------------------------------------------------


def unique_leaves(aggs: Sequence[Aggregator]) -> Dict[str, Leaf]:
    """Leaf-level CSE (§4.2 cycle binding): aggregators over the same
    column share one accumulator state."""
    uniq: Dict[str, Leaf] = {}
    for a in aggs:
        for leaf in a.leaves:
            uniq.setdefault(leaf.key, leaf)
    return uniq


# ---------------------------------------------------------------------------
# Static per-window lowering (shared by offline + online)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LoweredWindow:
    """Everything the drivers need for one physical window."""

    node: WindowAgg
    aggs: List[Aggregator]
    feature_names: List[str]
    sources: Tuple[str, ...]        # union tables first, base LAST
    needed_cols: Tuple[str, ...]    # agg-arg columns (value columns)
    online_buffer: int
    preagg: Optional[PreAgg]


def lower_windows(plan: FeaturePlan, script: FeatureScript, ctx
                  ) -> List[LoweredWindow]:
    """Static analysis of every physical window node."""
    out: List[LoweredWindow] = []
    for node in plan.physical_windows:
        spec = node.spec
        aggs, names = [], []
        for fname, call in node.agg_items:
            aggs.append(build_aggregator(call, ctx))
            names.append(fname)
        needed = set()
        for _, call in node.agg_items:
            for a in call.args:
                needed |= collect_columns(a)
        needed.discard(spec.partition_by)
        needed.discard(spec.order_by)
        # the online gather is anchored at the key segment's FIRST row
        # (not the window start) so the request-mode prefix scans see
        # the same rows at the same positions as the offline unit fold
        # — the buffer therefore sizes for the key's history, never
        # below ctx.online_buffer, and only grows for wide ROWS frames
        # or MAXSIZE caps
        buf = ctx.online_buffer
        if spec.frame_rows:
            buf = max(buf, min(4096, spec.preceding + 1))
        elif spec.maxsize:
            buf = max(buf, spec.maxsize)
        preagg = None
        if node.long_window_bucket_ms > 0 and not spec.frame_rows:
            preagg = PreAgg(
                spec=spec,
                leaves=unique_leaves(aggs),
                bucket_ms=node.long_window_bucket_ms,
                n_keys=ctx.cardinality(ColumnRef(spec.partition_by)),
                window_ms=spec.preceding,
                value_cols=tuple(sorted(needed)),
            )
        out.append(LoweredWindow(
            node=node, aggs=aggs, feature_names=names,
            sources=tuple(spec.union_tables) + (script.base_table,),
            needed_cols=tuple(sorted(needed)),
            online_buffer=buf, preagg=preagg))
    return out


# ---------------------------------------------------------------------------
# OFFLINE unit engine: host plan (merge, sort, units) + device fold
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class UnitBlock:
    """One padded (units, rows) class of a window's partition units.

    Units are bucketed by row count into power-of-two width classes so
    block padding stays bounded (< 2x) even when unit sizes are skewed —
    without the bucketing, one big unit would widen every unit's padded
    row.  The class boundaries depend only on unit sizes (data-derived),
    so every schedule buckets identically.
    """

    unit_ids: np.ndarray            # (U,) indices into the window's units
    idx: np.ndarray                 # (U, R) flat-row index (n_flat = pad)
    valid: np.ndarray               # (U, R) row present
    emit: np.ndarray                # (U, R) row emits output
    sizes: np.ndarray               # (U,) real rows per unit


@dataclasses.dataclass
class GroupLowering:
    """One window GROUP lowered against concrete tables.

    Windows sharing (partition column, order column, sources) — the
    common shape of multi-window feature scripts — share ONE merged
    sort, ONE §6.2 unit plan (halos cover the widest member window), ONE
    gathered dense layout, and one lift/scan/tree-build per deduplicated
    leaf; only the per-row frame bounds and the final prefix-difference /
    tree queries are member-specific.  This is §6.1 window-parallelism
    realized as data-pass sharing plus §4.2 cycle binding ACROSS windows.

    ``signature`` keys the compilation cache: two table sets with equal
    signatures re-use one traced program.
    """

    members: List[LoweredWindow]
    cols: Dict[str, np.ndarray]     # flat sorted value columns
    key: np.ndarray                 # flat sorted partition column (int32)
    ts: np.ndarray                  # flat sorted order column (int32)
    orig: np.ndarray                # flat sorted base-row index (n_base=none)
    blocks: List[UnitBlock]
    n_sliced_units: int
    _dev: Optional[Dict[str, Any]] = None

    @property
    def signature(self) -> Tuple:
        return (tuple(m.node.spec.canonical() for m in self.members),
                tuple(b.idx.shape for b in self.blocks),
                self.ts.shape[0], tuple(sorted(self.cols)))

    def fill(self) -> Dict[str, int]:
        """``rows``, the real rows the units fold, and ``slots``, the
        padded unit slots the device folds."""
        return {"rows": int(sum(b.sizes.sum() for b in self.blocks)),
                "slots": sum(b.idx.size for b in self.blocks)}

    def device_args(self) -> Dict[str, Any]:
        """Device copies of the plan arrays (cached: repeated offline
        calls over the same tables re-use resident buffers, mirroring
        the per-store-identity cache on the online path)."""
        if self._dev is None:
            self._dev = {
                "cols": {c: jnp.asarray(v) for c, v in self.cols.items()},
                "ts": jnp.asarray(self.ts),
                "orig": jnp.asarray(self.orig),
                "blocks": [{"idx": jnp.asarray(b.idx),
                            "valid": jnp.asarray(b.valid),
                            "emit": jnp.asarray(b.emit)}
                           for b in self.blocks],
            }
        return self._dev


def group_windows(windows: Sequence[LoweredWindow]
                  ) -> List[List[LoweredWindow]]:
    """Group physical windows that can share one offline layout."""
    groups: Dict[Tuple, List[LoweredWindow]] = {}
    for w in windows:
        spec = w.node.spec
        k = (spec.partition_by, spec.order_by, w.sources)
        groups.setdefault(k, []).append(w)
    return list(groups.values())


def lower_group_offline(members: Sequence[LoweredWindow],
                        arrays: Dict[str, Dict[str, Any]],
                        base_table: str, n_base: int,
                        target_rows: int = 1024, max_slices: int = 8
                        ) -> GroupLowering:
    """Merge the group's sources, sort, and cut into partition units.

    The sort key is (key, ts, rank, arrival) with the base table ranking
    LAST among equal timestamps — the same tie-break the online store's
    insert-after-peers policy reconstructs, which is what keeps replay
    consistent (core.consistency).
    """
    w = members[0]
    spec = w.node.spec
    cols_needed = sorted(
        set().union(*(m.needed_cols for m in members)) -
        {spec.partition_by, spec.order_by})

    with span("offline.plan.sort"):
        key_p, ts_p, rank_p, arr_p, orig_p = [], [], [], [], []
        col_p: Dict[str, List[np.ndarray]] = {c: [] for c in cols_needed}
        for rank, tname in enumerate(w.sources):
            cols = arrays[tname]
            n_t = next(iter(cols.values())).shape[0]
            is_base = tname == base_table and rank == len(w.sources) - 1
            key_p.append(np.asarray(cols[spec.partition_by], np.int64))
            ts_p.append(np.asarray(cols[spec.order_by], np.int64))
            rank_p.append(np.full((n_t,), rank, np.int64))
            arr_p.append(np.arange(n_t, dtype=np.int64))
            orig_p.append(np.arange(n_t, dtype=np.int32) if is_base
                          else np.full((n_t,), n_base, np.int32))
            for c in cols_needed:
                col_p[c].append(np.asarray(cols[c]))

        key = np.concatenate(key_p)
        ts = np.concatenate(ts_p)
        rank = np.concatenate(rank_p)
        arrival = np.concatenate(arr_p)
        orig = np.concatenate(orig_p)
        perm = np.lexsort((arrival, rank, ts, key))

        key_s = key[perm]
        ts_s = ts[perm].astype(np.int32)
        orig_s = orig[perm]
        cols_s = {c: np.concatenate(col_p[c])[perm] for c in cols_needed}

    units = skew.plan_window_units(
        key_s, ts_s,
        constraints=[(m.node.spec.frame_rows,
                      min(m.node.spec.preceding, 2**30))
                     for m in members],
        target_rows=target_rows, max_slices=max_slices)

    n_flat = key_s.shape[0]
    # bucket units into power-of-two width classes (bounded <2x padding)
    classes: Dict[int, List[int]] = {}
    for ui, u in enumerate(units):
        r = 16
        while r < u.n_rows:
            r *= 2
        classes.setdefault(r, []).append(ui)
    if not classes:
        classes = {16: []}

    blocks: List[UnitBlock] = []
    for r_pad in sorted(classes):
        uids = classes[r_pad]
        u_count = max(1, len(uids))
        idx = np.full((u_count, r_pad), n_flat, np.int64)
        valid = np.zeros((u_count, r_pad), bool)
        emit = np.zeros((u_count, r_pad), bool)
        sizes = np.zeros((len(uids),), np.int64)
        for bi, ui in enumerate(uids):
            u = units[ui]
            n_u = u.n_rows
            idx[bi, :n_u] = np.arange(u.lo, u.hi)
            valid[bi, :n_u] = True
            emit[bi, u.emit_lo - u.lo:n_u] = True
            # emit only base-table rows (union rows are fold context)
            emit[bi, :n_u] &= orig_s[u.lo:u.hi] < n_base
            sizes[bi] = n_u
        blocks.append(UnitBlock(
            unit_ids=np.asarray(uids, np.int64), idx=idx, valid=valid,
            emit=emit, sizes=sizes))

    # one sentinel pad row keeps the device gather branch-free
    ts_pad = np.concatenate([ts_s, [np.int32(2**31 - 1)]])
    orig_pad = np.concatenate([orig_s, [np.int32(n_base)]])
    cols_pad = {c: np.concatenate([v, np.zeros((1,), v.dtype)])
                for c, v in cols_s.items()}
    key_pad = np.concatenate([key_s.astype(np.int32), [np.int32(-1)]])
    return GroupLowering(
        members=list(members), cols=cols_pad, key=key_pad, ts=ts_pad,
        orig=orig_pad, blocks=blocks,
        n_sliced_units=sum(1 for u in units if u.sliced))


# ---------------------------------------------------------------------------
# The unit fold core — the ONE implementation of every leaf program
# ---------------------------------------------------------------------------


def unit_leaf_build(leaf: Leaf, lifted: jnp.ndarray):
    """Build one leaf's shared fold structure over a padded unit (R, *S).

    Built ONCE per (unit, deduplicated leaf) and queried by every
    member window / request row — §4.2 cycle binding at the structure
    level.  Each structure's combine tree depends only on row values
    and unit positions, never on the padded width, which is what lets
    the offline block fold and the online request gather produce
    bitwise-identical floats from the same rows.
    """
    if leaf.invertible:
        # §5.2 subtract-and-evict: inclusive combine-scan; prefixes are
        # left folds of position-aligned pow2 blocks, so prefix[i]
        # depends on rows [0, i] only
        return jax.lax.associative_scan(leaf.combine, lifted, axis=0)
    if leaf.idempotent:
        # min/max: sparse table — any window in two lookups
        return sparse_levels(leaf, lifted)
    return tuple(tree_levels(leaf, lifted))


def unit_leaf_query(leaf: Leaf, built, start, end) -> jnp.ndarray:
    """Fold [start, end) (unit coordinates, (Q,) each) from the built
    structure: prefix difference / sparse lookup / ordered tree walk."""
    if leaf.invertible:
        return prefix_window_fold(leaf, built, start, end,
                                  jnp.zeros_like(start))
    if leaf.idempotent:
        return sparse_query(leaf, built, start, end)
    return tree_query(leaf, list(built), start, end)


def unit_bounds(spec, ts_unit: jnp.ndarray, pos: jnp.ndarray, r: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[start, end) frame bounds for query rows at unit positions
    ``pos`` — the one bounds computation both executors share."""
    end = pos + 1
    if spec.frame_rows:
        start = jnp.maximum(0, pos - jnp.int32(min(spec.preceding, r)))
    else:
        pre = min(spec.preceding, 2**30)
        target = jnp.take(ts_unit, pos) - jnp.int32(pre)
        start = first_geq(ts_unit, target, jnp.zeros_like(pos), end)
    if spec.maxsize:
        start = jnp.maximum(start, end - jnp.int32(spec.maxsize))
    if spec.instance_not_in_window:
        end = jnp.minimum(end, pos)
        start = jnp.minimum(start, end)
    return start, end


def _group_leaf_set(members: Sequence[LoweredWindow]) -> Dict[str, Leaf]:
    group_leaves: Dict[str, Leaf] = {}
    for m in members:
        for k, leaf in unique_leaves(m.aggs).items():
            group_leaves.setdefault(k, leaf)
    return group_leaves


def _fold_unit_fused(members: Sequence[LoweredWindow],
                     env: Dict[str, Any],
                     queries: Optional[jnp.ndarray], impl,
                     batched: bool) -> List[Dict[str, jnp.ndarray]]:
    """Route one fold through the fused megakernel op; results are
    bitwise the staged path's (tests/test_kernels.py).  The single-unit
    route pins the XLA reference (vmap-safe under the online drivers);
    batched blocks honor the context's pallas/interpret selection."""
    from ...kernels.unit_fold import ops as unit_fold_ops
    spec0 = members[0].node.spec
    use_pallas, interpret = (impl[1], impl[2]) if batched else (False, True)
    fused = unit_fold_ops.unit_fold(
        [m.node.spec for m in members], _group_leaf_set(members), env,
        queries, order_by=spec0.order_by,
        member_keys=[tuple(unique_leaves(m.aggs)) for m in members],
        use_pallas=use_pallas, interpret=interpret)
    return [{k: fused[mi][k] for k in unique_leaves(m.aggs)}
            for mi, m in enumerate(members)]


def fused_prelift(members: Sequence[LoweredWindow], dev: Dict[str, Any]
                  ) -> Tuple:
    """Lift a group lowering's FLAT pad-appended columns into the fused
    op's lane layout, once for ALL of the group's unit blocks: the plan
    (cached), the per-group identity vectors, and each leaf group's
    (n_flat, F) lane data.  The flat ``__valid__`` is derived from the
    sentinel invariant (valid == idx < n_flat; the one pad row is
    last).  Feed the result to every ``fold_units`` call of the group
    (``drivers._group_feats``) so multi-block groups lift once."""
    from ...kernels.unit_fold import ops as unit_fold_ops
    spec0 = members[0].node.spec
    n = dev["ts"].shape[0]
    flat_env: Dict[str, Any] = dict(dev["cols"])
    flat_env[spec0.order_by] = dev["ts"]
    flat_env["__valid__"] = jnp.arange(n, dtype=jnp.int32) < n - 1
    return unit_fold_ops.prelift_blocks(
        [m.node.spec for m in members], _group_leaf_set(members),
        flat_env, order_by=spec0.order_by,
        member_keys=[tuple(unique_leaves(m.aggs)) for m in members])


def _fold_units_fused(members: Sequence[LoweredWindow],
                      dev: Dict[str, Any], impl, prelift=None
                      ) -> List[Dict[str, jnp.ndarray]]:
    """Offline block fold through the relayout-free fused entry: the
    flat pad-appended columns and the (U, R) gather index go straight to
    ``kernels.unit_fold.unit_fold_blocks`` — lane blocks are built by
    one lift over the flat rows (shared across blocks via ``prelift``)
    + one gather per leaf group, in the layout the kernel consumes (no
    per-call reshape/concat)."""
    from ...kernels.unit_fold import ops as unit_fold_ops
    spec0 = members[0].node.spec
    if prelift is None:
        prelift = fused_prelift(members, dev)
    fused = unit_fold_ops.unit_fold_blocks(
        [m.node.spec for m in members], _group_leaf_set(members),
        {}, dev["idx"], order_by=spec0.order_by,
        use_pallas=impl[1], interpret=impl[2], prelift=prelift)
    return [{k: fused[mi][k] for k in unique_leaves(m.aggs)}
            for mi, m in enumerate(members)]


def fold_unit(members: Sequence[LoweredWindow], env: Dict[str, Any],
              queries: Optional[jnp.ndarray] = None, impl=None
              ) -> List[Dict[str, jnp.ndarray]]:
    """THE unit fold core: fold one padded unit for every member window.

    ``env`` holds the unit's (key, ts, rank, arrival)-sorted columns —
    the order column, every needed value column, and ``__valid__``
    (padding rows lift to identity).  ``queries`` are the unit positions
    to emit (default: every row — the offline case; the online drivers
    pass the single request position).  Lifts and structure builds
    happen once per deduplicated leaf ACROSS the member windows; each
    member pays only its own bounds + queries.  Returns one
    ``{leaf key: (Q, *S)}`` dict per member; finalization happens in
    the driver.

    ``impl`` (from ``fold_impl``) selects the executor: ``None`` runs
    the staged per-leaf build/query below; a fused impl dispatches the
    whole group to ``kernels.unit_fold`` — one op, same bits.
    """
    if impl is not None:
        return _fold_unit_fused(members, env, queries, impl,
                                batched=False)
    spec0 = members[0].node.spec
    ts_unit = env[spec0.order_by]
    r = ts_unit.shape[0]
    if queries is None:
        queries = jnp.arange(r, dtype=jnp.int32)

    group_leaves = _group_leaf_set(members)
    built = {k: unit_leaf_build(leaf, leaf.lift(env))
             for k, leaf in group_leaves.items()}

    out: List[Dict[str, jnp.ndarray]] = []
    for m in members:
        start, end = unit_bounds(m.node.spec, ts_unit, queries, r)
        out.append({k: unit_leaf_query(leaf, built[k], start, end)
                    for k, leaf in unique_leaves(m.aggs).items()})
    return out


def fold_units(members: Sequence[LoweredWindow], dev: Dict[str, Any],
               impl=None, prelift=None) -> List[Dict[str, jnp.ndarray]]:
    """Offline execution of the unit core over one (U, R) block.

    The gather through ``idx`` IS the §6.2 halo expansion: a hot key's
    later time slices pull their window context rows into the unit
    in-trace.  The fold itself is ``fold_unit`` vmapped over the units
    — no offline-only fold algebra exists.  With a fused ``impl`` the
    block takes the relayout-free route: flat columns + gather index go
    to ``kernels.unit_fold.unit_fold_blocks`` in one batched dispatch
    (the Pallas grid folds lane tiles of units when enabled).
    """
    if impl is not None:
        return _fold_units_fused(members, dev, impl, prelift=prelift)
    spec0 = members[0].node.spec
    idx = dev["idx"]
    env = {c: jnp.take(v, idx, axis=0) for c, v in dev["cols"].items()}
    env["__valid__"] = dev["valid"]
    env[spec0.order_by] = jnp.take(dev["ts"], idx)       # (U, R)
    return jax.vmap(lambda e: fold_unit(members, e))(env)


# ---------------------------------------------------------------------------
# ONLINE unit gather (request mode against the live store)
# ---------------------------------------------------------------------------


def gather_unit(states, members: Sequence[LoweredWindow], key, ts, values
                ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Gather one request's rows into the padded unit layout.

    The online counterpart of ``lower_group_offline``'s merge: every
    source's rows for ``key`` up to the request's insert-after-peers
    position — the key's WHOLE history, not just the window span,
    because the unit core's prefix scans are anchored at the key
    segment's first row — merged in the same (ts, rank, arrival) order
    with the same INT_MAX sentinel padding, and the virtually-inserted
    request row appended after its peers (rank = n_sources).  Returns
    ``(env, p)`` where ``p`` is the request row's unit position; feed
    both to ``fold_unit(members, env, queries=p[None])``.
    """
    w0 = members[0]
    spec = w0.node.spec
    n_src = len(w0.sources)
    buf = max(m.online_buffer for m in members)
    needed = sorted(set().union(*(m.needed_cols for m in members)))

    cols_p, ts_p, valid_p, rank_p = [], [], [], []
    for rank, tname in enumerate(w0.sources):
        cols, ts_arr, valid = timestore.gather_key_unit(
            states[tname], key, ts, buf, needed)
        cols_p.append(cols)
        ts_p.append(ts_arr)
        valid_p.append(valid)
        rank_p.append(jnp.full_like(ts_arr, rank))

    cols = {c: jnp.concatenate(
        [p[c] for p in cols_p]
        + [jnp.asarray(values.get(c, 0.0), cols_p[0][c].dtype)[None]])
        for c in needed}
    ts_all = jnp.concatenate(ts_p + [jnp.asarray(ts, jnp.int32)[None]])
    valid = jnp.concatenate(valid_p + [jnp.ones((1,), bool)])
    rank = jnp.concatenate(rank_p + [jnp.full((1,), n_src, jnp.int32)])

    # same sort key as the offline lexsort: invalid rows carry the
    # offline pad sentinel and fall to the dead tail
    sort_ts = jnp.where(valid, ts_all, jnp.int32(2**31 - 1))
    pos0 = jnp.arange(ts_all.shape[0], dtype=jnp.int32)
    perm = jnp.lexsort((pos0, rank, sort_ts))
    env = {c: jnp.take(v, perm) for c, v in cols.items()}
    env["__valid__"] = jnp.take(valid, perm)
    env[spec.order_by] = jnp.take(sort_ts, perm)
    p = jnp.sum(valid.astype(jnp.int32)) - 1     # request row position
    return env, p


def gather_unit_fused(states, members: Sequence[LoweredWindow], key, ts,
                      values) -> Tuple[Dict[str, jnp.ndarray],
                                       jnp.ndarray]:
    """``gather_unit`` without the lexsort: rank-merge by scatter.

    The staged gather materializes a (ts, rank, arrival) ``lexsort`` —
    an O(n log n) permutation — to merge the per-source buffers.  But
    each source buffer is ALREADY time-sorted with its valid rows as a
    prefix, so every valid row's merged position is computable directly:
    its within-source index plus, per other source, a binary-search row
    count (``searchsorted`` right for lower ranks — equal timestamps
    sort before — left for higher).  ONE int32 scatter (invalid rows
    dropped onto the out-of-range index) builds the source-row index per
    unit slot; every column then fills by gather — scatters serialize on
    CPU XLA, so scattering once and gathering K columns beats K column
    scatters ~3x.  Unhit slots keep the pad index: timestamps read the
    INT_MAX sentinel and values zero, exactly the dead-tail contents the
    staged permutation produces on every position a fold can read
    (invalid columns differ only where ``__valid__`` masks the lift to
    identity) — so the folds downstream are bitwise the staged
    gather's.  Integer math end to end; the request row lands after its
    peers at rank ``n_sources``.
    """
    w0 = members[0]
    spec = w0.node.spec
    n_src = len(w0.sources)
    buf = max(m.online_buffer for m in members)
    needed = sorted(set().union(*(m.needed_cols for m in members)))
    total = n_src * buf + 1

    cols_p, ts_eff_p, valid_p = [], [], []
    for rank, tname in enumerate(w0.sources):
        cols, ts_arr, valid = timestore.gather_key_unit(
            states[tname], key, ts, buf, needed)
        cols_p.append(cols)
        ts_eff_p.append(jnp.where(valid, ts_arr, jnp.int32(2**31 - 1)))
        valid_p.append(valid)

    pos_p = []
    for r in range(n_src):
        pos = jnp.arange(buf, dtype=jnp.int32)   # within-source index
        for q in range(n_src):
            if q == r:
                continue
            side = "right" if q < r else "left"
            pos = pos + jnp.searchsorted(
                ts_eff_p[q], ts_eff_p[r], side=side).astype(jnp.int32)
        # invalid rows fall off the end of the scatter (mode='drop')
        pos_p.append(jnp.where(valid_p[r], pos, jnp.int32(total)))

    pos_all = jnp.concatenate(pos_p)
    p = sum(jnp.sum(v.astype(jnp.int32)) for v in valid_p)

    n_rows = n_src * buf
    pad_idx, req_idx = jnp.int32(n_rows), jnp.int32(n_rows + 1)
    src_idx = (jnp.full((total,), pad_idx)
               .at[pos_all].set(jnp.arange(n_rows, dtype=jnp.int32),
                                mode="drop")
               .at[p].set(req_idx))

    env: Dict[str, jnp.ndarray] = {}
    for c in needed:
        dt = cols_p[0][c].dtype
        vals = jnp.concatenate(
            [cp[c] for cp in cols_p]
            + [jnp.zeros((1,), dt),
               jnp.asarray(values.get(c, 0.0), dt)[None]])
        env[c] = jnp.take(vals, src_idx)
    env[spec.order_by] = jnp.take(
        jnp.concatenate(ts_eff_p
                        + [jnp.full((1,), 2**31 - 1, jnp.int32),
                           jnp.asarray(ts, jnp.int32)[None]]), src_idx)
    env["__valid__"] = jnp.take(
        jnp.concatenate(valid_p + [jnp.zeros((1,), bool),
                                   jnp.ones((1,), bool)]), src_idx)
    return env, p


def gather_edges(states, w: LoweredWindow, key, t0, t1):
    """Raw rows with ts in [t0, t1) across sources (pre-agg edge
    buckets, §5.1)."""
    bufs = []
    for rank, tname in enumerate(w.sources):
        st = states[tname]
        lo, hi = timestore.range_bounds(st, key, t0, t1 - 1)
        cols, ts_arr, valid = timestore.gather_window(
            st, lo, hi, w.preagg.max_bucket_rows, list(w.needed_cols))
        bufs.append((cols, ts_arr, valid, jnp.full_like(ts_arr, rank)))
    cols = {c: jnp.concatenate([b[0][c] for b in bufs])
            for c in w.needed_cols}
    ts_all = jnp.concatenate([b[1] for b in bufs])
    valid = jnp.concatenate([b[2] for b in bufs])
    rank = jnp.concatenate([b[3] for b in bufs])
    sort_ts = jnp.where(valid, ts_all, jnp.int32(2**31 - 1))
    pos0 = jnp.arange(ts_all.shape[0], dtype=jnp.int32)
    perm = jnp.lexsort((pos0, rank, sort_ts))
    env = {c: jnp.take(v, perm) for c, v in cols.items()}
    env["__valid__"] = jnp.take(valid, perm)
    return env
