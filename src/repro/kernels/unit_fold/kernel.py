"""Pallas TPU kernel: one window group's ENTIRE unit fold per dispatch.

Lane-tiled grid ``(unit tiles, leaf groups)``: each step folds a tile of
up to ``LANES`` units for one leaf group.  For every unit of the tile the
kernel

  1. builds the group's fold structure into VMEM scratch — balanced-tree
     levels for tree groups, the same levels swept down into a prefix
     table for scan groups, sparse-table levels for idempotent groups;
  2. per query position, computes the frame bounds of each member window
     that uses the group (ROWS arithmetic + the ``first_geq`` binary
     search for RANGE members) and answers the (member, query) fold.

Every form here is one the TPU's Mosaic compiler lowers: 2-D values,
sublane rolls, and reads of a data-dependent row as a one-hot select.
Mosaic has no general gather, so ``x[idx]`` is ``_take_row``: select the
row on its int32 bits and sum over rows.  Exactly one term is nonzero,
so the sum is exact and every float bit pattern (-0.0, inf, NaN
payloads) comes through unchanged.  A read costs O(rows) per query
instead of O(1); see ``CHANGES.md`` for the per-family query cost.

Levels are kept FULL LENGTH (rp rows each): node j of tree level k sits
at row ``j << k``, so a level is ``combine(prev, roll(prev, -2^(k-1)))``
— no strided slices, which Mosaic refuses.  Rows between nodes hold
values that are never read.

The scan stage canNOT use a Hillis–Steele recurrence: bitwise parity
with the staged engine requires reproducing
``jax.lax.associative_scan``'s exact bracketing.  Scan prefix ``[0, e)``
equals the MSB-first left fold of the position-aligned power-of-two
block decomposition of ``[0, e)`` over balanced-tree levels (verified in
tests/test_kernels.py).  A top-down sweep over the levels computes that
fold for every e at once — at level k, each e whose lowest set bit is k
takes ``combine(prefix[e - 2^k], T_k[e - 2^k])``, or the bare node when
``e == 2^k`` — so a query reads its two prefixes with one select each.

Inputs are padded to a power-of-two row count (at least 8) with identity
rows (values) and INT_MAX sentinels (timestamps), and the unit axis to a
multiple of the tile with all-sentinel units (sliced off on return);
every padded structure provably yields the staged values on real
queries:

* scan: decomposition blocks of ``[0, e)``, e <= R, never touch pads;
* sparse: identity rows are absorbed lane-wise (min/max/HLL combines);
* tree: levels above the staged tree's root only see inactive walks,
  and the levels below are the staged ``tree_levels``, pads included;
* bounds: the extra binary-search steps on converged rows are no-ops;
* unit pads: a whole-unit pad computes garbage bounds over INT_MAX
  timestamps, folds identity data, and is dropped before returning.

Query math (clamps, identity-seeded walk order, empty-range masking)
replicates ``core.window`` line for line — see each helper's note.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import UnitFoldPlan

INT_MAX = 2**31 - 1

# most units folded per grid step; edge shapes (U = 1, LANES +/- 1, ...)
# are padded up and gated bitwise in tests/test_kernels.py
LANES = 8

# a step's VMEM (double-buffered blocks + level scratch) is kept under
# this by shrinking the tile; the compiler limit is set from the estimate
_STEP_BUDGET = 24 << 20
_VMEM_CAP = 100 << 20          # v5e has 128 MiB of VMEM per core


def _levels(rp: int) -> int:
    return rp.bit_length()      # log2(rp) + 1 structure levels


# ---------------------------------------------------------------------------
# Mosaic-lowerable reads (one unit; indices are (1, 1) int32 values)
# ---------------------------------------------------------------------------


def _take_row(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``x[idx]`` of an (N, F) float value as a (1, F) row: a select on
    the int32 bits plus a sum over rows with one nonzero term — bitwise
    the gathered row, at O(N * F) per read."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    rows = lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    hit = jnp.where(rows == idx, bits, 0)
    return lax.bitcast_convert_type(jnp.sum(hit, axis=0, keepdims=True),
                                    x.dtype)


def _take_lane(row: jnp.ndarray, idx) -> jnp.ndarray:
    """``row[0, idx]`` of a (1, N) int32 value as (1, 1), by the same
    one-hot select (``idx`` may be a loop scalar or a (1, 1) value)."""
    lanes = lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lanes == idx, row, 0), axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# In-kernel stages
# ---------------------------------------------------------------------------


def _bounds(specs: Sequence[Any], ts_row: jnp.ndarray, q: jnp.ndarray,
            r_real: int, rp: int):
    """(1, 1) frame bounds of each member at query ``q`` —
    ``ref.unit_bounds_each`` with the ``first_geq`` binary search
    unrolled.  The search runs ceil(log2(rp))+1 steps over the padded
    row; rows converge within the staged step count and extra
    iterations leave (lo, hi) fixed, so the result is bitwise the staged
    one.  ``>> 1`` is the staged ``// 2``: both operands are >= 0."""
    end0 = q + 1
    steps = max(1, int(math.ceil(math.log2(max(rp, 2)))) + 1)
    tsq = None
    starts, ends = [], []
    for s in specs:
        end = end0
        if s.frame_rows:
            start = jnp.maximum(0, q - jnp.int32(min(s.preceding, r_real)))
        else:
            if tsq is None:
                tsq = _take_lane(ts_row, q)
            target = tsq - jnp.int32(min(s.preceding, 2**30))

            def step(_, lo_hi, target=target):
                lo, hi = lo_hi
                mid = (lo + hi) >> 1
                v = _take_lane(ts_row, jnp.clip(mid, 0, rp - 1))
                go_right = (v < target) & (lo < hi)
                lo = jnp.where(go_right, mid + 1, lo)
                hi = jnp.where(go_right | (lo >= hi), hi, mid)
                return lo, hi

            start, _ = lax.fori_loop(0, steps, step,
                                     (jnp.zeros_like(q), end0))
        if s.maxsize:
            start = jnp.maximum(start, end - jnp.int32(s.maxsize))
        if s.instance_not_in_window:
            end = jnp.minimum(end, q)
            start = jnp.minimum(start, end)
        starts.append(start)
        ends.append(end)
    return starts, ends


def _tree_build(proxy, data, ident, lvl, rp: int):
    """Balanced-tree levels, full length: ``lvl[k]`` row ``j << k`` is
    node j — the pair combine ``tree_levels`` makes over the
    identity-padded rows."""
    del ident
    lvl[0] = data

    def level(k, c):
        prev = lvl[k - 1]
        lvl[k] = proxy.combine(
            prev, pltpu.roll(prev, rp - jnp.left_shift(1, k - 1), 0))
        return c

    lax.fori_loop(1, _levels(rp), level, 0)


def _scan_build(proxy, data, ident, lvl, rp: int):
    """Tree levels swept top-down into the prefix table: afterwards
    ``lvl[0]`` row e (1 <= e < rp) holds prefix ``[0, e)`` and row 0 the
    whole-buffer prefix ``[0, rp)`` (the root), each bitwise the
    ``associative_scan`` value (see the module note)."""
    _tree_build(proxy, data, ident, lvl, rp)
    n_lev = _levels(rp)
    rows = lax.broadcasted_iota(jnp.int32, (rp, 1), 0)

    def sweep(i, pre):
        k = n_lev - 2 - i                        # top level down to 0
        d = jnp.left_shift(1, k)
        node = pltpu.roll(lvl[k], d, 0)          # row e <- T_k[e - d]
        before = pltpu.roll(pre, d, 0)           # row e <- prefix[e - d]
        cand = jnp.where(rows == d, node, proxy.combine(before, node))
        return jnp.where((rows & (2 * d - 1)) == d, cand, pre)

    # the initial rows are all overwritten before any is read
    pre = lax.fori_loop(0, n_lev - 1, sweep, data)
    lvl[0] = jnp.where(rows == 0, lvl[n_lev - 1], pre)


def _sparse_build(proxy, data, ident, lvl, rp: int):
    """``sparse_levels``: level j row i = combine(level j-1 rows i and
    i + 2^(j-1)), identity past the end — the staged concat-shift."""
    rows = lax.broadcasted_iota(jnp.int32, (rp, 1), 0)
    lvl[0] = data

    def level(j, c):
        off = jnp.left_shift(1, j - 1)
        cur = lvl[j - 1]
        nxt = jnp.where(rows >= rp - off, ident,
                        pltpu.roll(cur, rp - off, 0))
        lvl[j] = proxy.combine(cur, nxt)
        return c

    lax.fori_loop(1, _levels(rp), level, 0)


def _scan_query(proxy, lvl, ident, start, end, rp: int):
    """Invertible stage: two prefix reads + prefix diff — the in-kernel
    ``prefix_window_fold`` (same identity substitution at segment start,
    same empty-range masking)."""
    pre = lvl[0]
    last = _take_row(pre, jnp.maximum(end, 1) & (rp - 1))
    prev = _take_row(pre, jnp.maximum(start, 1) & (rp - 1))
    prev = jnp.where(start <= 0, ident, prev)
    folded = proxy.invert_prefix(last, prev)
    return jnp.where(end <= start, ident, folded)


def _sparse_query(proxy, lvl, ident, start, end, rp: int):
    """Idempotent stage: ``sparse_query``'s 2-lookup math, the level
    chosen per query by a select over the levels."""
    span = jnp.maximum(end - start, 1)
    jlev = 31 - lax.clz(span)
    lo = jnp.clip(start, 0, rp - 1)
    hi = jnp.clip(end - jnp.left_shift(1, jlev), 0, rp - 1)

    def level(j, ab):
        lv = lvl[j]
        hit = jlev == j
        return (jnp.where(hit, _take_row(lv, lo), ab[0]),
                jnp.where(hit, _take_row(lv, hi), ab[1]))

    lv0 = lvl[0]
    a, b = lax.fori_loop(1, _levels(rp), level,
                         (_take_row(lv0, lo), _take_row(lv0, hi)))
    out = proxy.combine(a, b)
    return jnp.where(end <= start, ident, out)


def _tree_query(proxy, lvl, ident, start, end, rp: int):
    """Order-sensitive stage: the bidirectional ``tree_query`` walk
    (left accumulator grows rightward, right leftward, root included),
    replicated clamp-for-clamp over the full-length levels."""

    def level(k, carry):
        res_l, res_r, l, r = carry
        last = jnp.right_shift(rp, k) - 1        # last node of level k
        active = l < r
        take_l = active & ((l & 1) == 1)
        take_r = active & ((r & 1) == 1)
        lv = lvl[k]
        node_l = _take_row(lv, jnp.left_shift(jnp.clip(l, 0, last), k))
        node_r = _take_row(lv, jnp.left_shift(jnp.clip(r - 1, 0, last), k))
        res_l = jnp.where(take_l, proxy.combine(res_l, node_l), res_l)
        res_r = jnp.where(take_r, proxy.combine(node_r, res_r), res_r)
        l = (l + take_l.astype(jnp.int32)) >> 1
        r = (r - take_r.astype(jnp.int32)) >> 1
        return res_l, res_r, l, r

    res_l, res_r, _, _ = lax.fori_loop(0, _levels(rp), level,
                                       (ident, ident, start, end))
    return proxy.combine(res_l, res_r)


_STAGES = {"scan": (_scan_build, _scan_query),
           "sparse": (_sparse_build, _sparse_query),
           "tree": (_tree_build, _tree_query)}


# ---------------------------------------------------------------------------
# Kernel body + pallas_call wrapper
# ---------------------------------------------------------------------------


def _unit_fold_kernel(ts_ref, q_ref, *refs, plan: UnitFoldPlan,
                      r_real: int, rp: int, lanes: int, nq: int):
    g = pl.program_id(1)
    n_groups = len(plan.groups)
    data_refs = refs[:n_groups]
    ident_refs = refs[n_groups:2 * n_groups]
    out_refs = refs[2 * n_groups:3 * n_groups]
    all_members = tuple(range(len(plan.specs)))

    for gi, grp in enumerate(plan.groups):
        @pl.when(g == gi)
        def _do_group(gi=gi, grp=grp):
            specs = [plan.specs[i] for i in grp.members_ix or all_members]
            build, query = _STAGES[grp.kind]
            ident = ident_refs[gi][...]                  # (1, F)
            out = out_refs[gi]

            def one_unit(u, carry, lvl):
                build(grp.proxy, data_refs[gi][u], ident, lvl, rp)
                ts_row = ts_ref[u]                       # (1, rp)
                q_row = q_ref[u]                         # (1, nq)

                def one_query(j, c):
                    q = _take_lane(q_row, j)
                    starts, ends = _bounds(specs, ts_row, q, r_real, rp)
                    for row, (st, en) in enumerate(zip(starts, ends)):
                        out[u, row, pl.ds(j, 1), :] = query(
                            grp.proxy, lvl, ident, st, en, rp)
                    return c

                return lax.fori_loop(0, nq, one_query, carry)

            # the level scratch lives only while its group runs, so a
            # step holds one group's levels, not every group's
            pl.run_scoped(
                lambda lvl: lax.fori_loop(
                    0, lanes, functools.partial(one_unit, lvl=lvl), 0),
                pltpu.VMEM((_levels(rp), rp, ident.shape[1]), jnp.float32))


def _tile_bytes(shape: Tuple[int, ...]) -> int:
    """VMEM bytes of a 32-bit block: the two minor dims pad to the
    (8, 128) tile."""
    *lead, a, b = shape
    return math.prod(lead) * -(-a // 8) * 8 * -(-b // 128) * 128 * 4


def _step_bytes(lanes: int, rp: int, nq: int, widths, mg_list) -> int:
    """VMEM one grid step holds: every block double-buffered, the level
    scratch of the widest group (a step folds one group, and its levels
    are scoped to it), and a few (rp, F) temporaries of that group."""
    blocks = (_tile_bytes((lanes, 1, rp)) + _tile_bytes((lanes, 1, nq))
              + sum(_tile_bytes((lanes, rp, w)) + _tile_bytes((1, w))
                    for w in widths)
              + sum(_tile_bytes((lanes, mg, nq, w))
                    for mg, w in zip(mg_list, widths)))
    scratch = max(_tile_bytes((_levels(rp), rp, w)) for w in widths)
    return 2 * blocks + scratch + 8 * _tile_bytes((rp, max(widths)))


def unit_fold_pallas(plan: UnitFoldPlan, data_list, ident_list,
                     ts: jnp.ndarray, queries: jnp.ndarray, r_real: int,
                     *, interpret: bool):
    """Run the fused fold: ``data_list[g]`` is group g's identity-padded
    (U, rp, F_g) block, ``ident_list[g]`` its (1, F_g) identity lane
    vector (a kernel input — Pallas kernels cannot capture array
    constants), ``ts`` the (U, rp) sentinel-padded order column, rp a
    power of two >= 8, ``queries`` the (U, Q) unit positions.  Returns
    one (U, Mg, Q, F_g) fold block per group, rows in ``members_ix``
    order.  ``interpret=True`` runs the body in the Pallas interpreter
    (CPU tests); ``False`` lowers it through Mosaic.

    The tile is the largest power of two <= ``LANES`` whose step fits
    ``_STEP_BUDGET`` of VMEM (see ``_step_bytes``).
    """
    u, rp = ts.shape
    if rp < 8 or rp & (rp - 1):
        raise ValueError(f"row count {rp} is not a power of two >= 8")
    nq = queries.shape[1]
    m = len(plan.specs)
    widths = [int(d.shape[-1]) for d in data_list]
    mg_list = [len(grp.members_ix or range(m)) for grp in plan.groups]
    lanes = 1 << (min(LANES, max(1, u)).bit_length() - 1)
    while lanes > 1 and _step_bytes(lanes, rp, nq, widths,
                                    mg_list) > _STEP_BUDGET:
        lanes //= 2
    need = _step_bytes(lanes, rp, nq, widths, mg_list)
    if need > _VMEM_CAP:
        raise ValueError(
            f"unit fold step needs {need} bytes of VMEM at rp={rp}, "
            f"Q={nq}, widths={widths}: above the {_VMEM_CAP}-byte cap")
    u_pad = -(-u // lanes) * lanes
    if u_pad > u:
        extra = u_pad - u
        ts = jnp.concatenate(
            [ts, jnp.full((extra, rp), INT_MAX, ts.dtype)], axis=0)
        queries = jnp.concatenate(
            [queries, jnp.zeros((extra, nq), queries.dtype)], axis=0)
        data_list = [
            jnp.concatenate(
                [d, jnp.broadcast_to(iv[0], (extra,) + d.shape[1:])],
                axis=0)
            for d, iv in zip(data_list, ident_list)]
    grid = (u_pad // lanes, len(plan.groups))

    in_specs = [pl.BlockSpec((lanes, 1, rp), lambda i, g: (i, 0, 0)),
                pl.BlockSpec((lanes, 1, nq), lambda i, g: (i, 0, 0))]
    for w in widths:
        in_specs.append(
            pl.BlockSpec((lanes, rp, w), lambda i, g: (i, 0, 0)))
    for w in widths:
        in_specs.append(pl.BlockSpec((1, w), lambda i, g: (0, 0)))
    out_specs = [
        pl.BlockSpec((lanes, mg, nq, w), lambda i, g: (i, 0, 0, 0))
        for mg, w in zip(mg_list, widths)]
    out_shape = [jax.ShapeDtypeStruct((u_pad, mg, nq, w), jnp.float32)
                 for mg, w in zip(mg_list, widths)]
    outs = pl.pallas_call(
        functools.partial(_unit_fold_kernel, plan=plan, r_real=r_real,
                          rp=rp, lanes=lanes, nq=nq),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(_VMEM_CAP, max(32 << 20, 2 * need))),
        interpret=interpret,
    )(ts.reshape(u_pad, 1, rp), queries.reshape(u_pad, 1, nq),
      *data_list, *ident_list)
    outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
    if u_pad > u:
        outs = [o[:u] for o in outs]
    return outs
