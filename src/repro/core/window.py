"""Window specifications and the vectorized window-execution machinery.

Frame semantics (matching OpenMLDB SQL):

  * ``ROWS BETWEEN k PRECEDING AND CURRENT ROW``       (count frame)
  * ``ROWS_RANGE BETWEEN <interval> PRECEDING AND CURRENT ROW`` (time frame;
    peers — rows with equal timestamp — are included, standard SQL RANGE)
  * optional ``MAXSIZE n`` row cap, optional ``UNION table, ...``.

Execution is fully vectorized jnp (jit-able, static shapes):

  * per-segment binary search (``first_geq``) for time-frame bounds,
  * segmented inclusive scans + prefix differencing for invertible leaves —
    this *is* the paper's subtract-and-evict incremental computation (§5.2),
  * ordered segment trees for non-invertible leaves (min/max/drawdown) —
    this *is* the paper's §5.1 structure, reused by pre-aggregation.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import vary_like
from .functions import Aggregator, Leaf

__all__ = [
    "WindowSpec", "parse_interval_ms", "first_geq", "segment_starts",
    "window_bounds", "segmented_inclusive_scan", "SegmentTree",
    "fold_windows", "sorted_perm", "tree_fold", "tree_levels",
    "tree_query", "sparse_levels", "sparse_query",
]


# --------------------------------------------------------------------------
# Spec
# --------------------------------------------------------------------------

_UNITS_MS = {"ms": 1, "s": 1000, "m": 60_000, "h": 3_600_000,
             "d": 86_400_000}


def parse_interval_ms(text: str) -> int:
    """``"3s" -> 3000``; bare integers are milliseconds."""
    t = text.strip().lower()
    for suffix in ("ms", "s", "m", "h", "d"):
        if t.endswith(suffix):
            head = t[: -len(suffix)]
            if head and head.replace(".", "", 1).isdigit():
                return int(float(head) * _UNITS_MS[suffix])
    return int(t)


@dataclasses.dataclass(frozen=True)
class WindowSpec:
    name: str
    partition_by: str
    order_by: str
    preceding: int                 # rows (ROWS) or milliseconds (ROWS_RANGE)
    frame_rows: bool = False       # True = ROWS, False = ROWS_RANGE
    union_tables: Tuple[str, ...] = ()
    maxsize: int = 0               # 0 = unlimited
    instance_not_in_window: bool = False

    def canonical(self) -> str:
        """Fingerprint used for window merging (§4.2 parsing optimization):
        windows with identical canonical forms share one physical window."""
        return (
            f"p={self.partition_by}|o={self.order_by}|"
            f"f={'rows' if self.frame_rows else 'range'}:{self.preceding}|"
            f"u={','.join(sorted(self.union_tables))}|m={self.maxsize}|"
            f"x={int(self.instance_not_in_window)}"
        )


# --------------------------------------------------------------------------
# Vector machinery
# --------------------------------------------------------------------------


def sorted_perm(key: jnp.ndarray, ts: jnp.ndarray) -> jnp.ndarray:
    """Permutation sorting rows by (key, ts) — the timestore pre-ranking."""
    return jnp.lexsort((ts, key))


def segment_starts(key_sorted: jnp.ndarray) -> jnp.ndarray:
    """For each sorted row, the index of its key-segment's first row."""
    n = key_sorted.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), key_sorted[1:] != key_sorted[:-1]])
    # running maximum of start indices
    return jax.lax.associative_scan(jnp.maximum,
                                    jnp.where(is_start, idx, 0))


def first_geq(ts_sorted: jnp.ndarray, targets: jnp.ndarray,
              lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """Vectorized per-row binary search: smallest i in [lo, hi) with
    ts_sorted[i] >= target (returns hi if none).  Each row gets its own
    [lo, hi) — this is the per-segment search jnp.searchsorted can't do.
    """
    n = ts_sorted.shape[0]
    steps = max(1, int(math.ceil(math.log2(max(n, 2)))) + 1)

    def body(_, carry):
        lo_, hi_ = carry
        mid = (lo_ + hi_) // 2
        v = ts_sorted[jnp.clip(mid, 0, n - 1)]
        go_right = (v < targets) & (lo_ < hi_)
        lo_ = jnp.where(go_right, mid + 1, lo_)
        hi_ = jnp.where(go_right | (lo_ >= hi_), hi_, mid)
        return lo_, hi_

    ops = (ts_sorted, targets, lo, hi)
    lo_f, _ = jax.lax.fori_loop(
        0, steps, body, (vary_like(jnp.asarray(lo, jnp.int32), *ops),
                         vary_like(jnp.asarray(hi, jnp.int32), *ops)))
    return lo_f


def window_bounds(spec: WindowSpec, key_sorted, ts_sorted,
                  seg_start: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row half-open [start, end) window bounds in sorted coordinates."""
    n = key_sorted.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    if seg_start is None:
        seg_start = segment_starts(key_sorted)
    # ``end`` is always position-based (current row inclusive): this makes
    # the offline batch semantics *identical* to online request replay —
    # a row's window sees exactly the rows that arrived before it (stable
    # sort keeps arrival order among equal timestamps).  Consistency by
    # construction (§4 / DESIGN.md §7).
    end = idx + 1
    if spec.frame_rows:
        start = jnp.maximum(seg_start,
                            idx - jnp.int32(min(spec.preceding, n)))
    else:
        # windows wider than the representable span saturate to
        # "all history" (long horizons should use time_unit='s')
        pre = min(spec.preceding, 2**30)
        target = ts_sorted - jnp.int32(pre)
        start = first_geq(ts_sorted, target, seg_start, idx + 1)
    if spec.maxsize:
        start = jnp.maximum(start, end - jnp.int32(spec.maxsize))
    if spec.instance_not_in_window:
        end = jnp.minimum(end, idx)
        start = jnp.minimum(start, end)
    return start, end


def _segment_end(key_sorted):
    """Exclusive end of each row's key segment."""
    n = key_sorted.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_end = jnp.concatenate(
        [key_sorted[1:] != key_sorted[:-1], jnp.ones((1,), bool)])
    ends = jnp.where(is_end, idx + 1, n)
    return jax.lax.associative_scan(jnp.minimum, ends, reverse=True)


# --------------------------------------------------------------------------
# Invertible path: segmented scan + prefix difference (subtract-and-evict)
# --------------------------------------------------------------------------


def segmented_inclusive_scan(leaf: Leaf, lifted: jnp.ndarray,
                             seg_flag: jnp.ndarray) -> jnp.ndarray:
    """Inclusive combine-scan that resets at segment starts.

    Classic segmented-monoid construction: carry (flag, state); when the
    right element starts a new segment its state wins outright.
    """

    def comb(a, b):
        fa, sa = a
        fb, sb = b
        state = jnp.where(_bshape(fb, sb), sb, leaf.combine(sa, sb))
        return fa | fb, state

    flags = seg_flag.astype(bool)
    _, states = jax.lax.associative_scan(comb, (flags, lifted))
    return states


def _bshape(flag, state):
    """Broadcast a (rows,) flag against (rows, *state_shape)."""
    extra = state.ndim - flag.ndim
    return flag.reshape(flag.shape + (1,) * extra)


def prefix_window_fold(leaf: Leaf, inclusive: jnp.ndarray,
                       start: jnp.ndarray, end: jnp.ndarray,
                       seg_start: jnp.ndarray) -> jnp.ndarray:
    """fold(rows[start:end]) via prefix difference (invertible leaves)."""
    last = jnp.take(inclusive, jnp.maximum(end - 1, 0), axis=0)
    prev_idx = jnp.maximum(start - 1, 0)
    prev = jnp.take(inclusive, prev_idx, axis=0)
    at_seg_start = start <= seg_start
    ident = leaf.identity()
    prev = jnp.where(_bshape(at_seg_start, prev),
                     jnp.broadcast_to(ident, prev.shape), prev)
    folded = leaf.invert_prefix(last, prev)
    empty = end <= start
    return jnp.where(_bshape(empty, folded),
                     jnp.broadcast_to(ident, folded.shape), folded)


def tree_fold(leaf: Leaf, lifted: jnp.ndarray) -> jnp.ndarray:
    """Ordered log-depth tree reduction (cheaper than a full prefix scan
    when only the total fold is needed — the online request case and the
    pre-aggregation raw edges)."""
    n = lifted.shape[0]
    n_pad = 1 << max(1, (n - 1).bit_length())
    if n_pad > n:
        ident = jnp.broadcast_to(leaf.identity(),
                                 (n_pad - n,) + lifted.shape[1:])
        lifted = jnp.concatenate([lifted, ident], axis=0)
    while lifted.shape[0] > 1:
        lifted = leaf.combine(lifted[0::2], lifted[1::2])
    return lifted[0]


# --------------------------------------------------------------------------
# Non-invertible path: ordered segment tree (§5.1's structure)
# --------------------------------------------------------------------------


def tree_levels(leaf: Leaf, lifted: jnp.ndarray) -> List[jnp.ndarray]:
    """Bottom-up segment-tree levels over lifted leaf states (built once
    per (window-group, leaf); shared by every query)."""
    n = lifted.shape[0]
    n_pad = 1 << max(1, (n - 1).bit_length())
    ident = jnp.broadcast_to(leaf.identity(),
                             (n_pad - n,) + lifted.shape[1:])
    level = jnp.concatenate([lifted, ident], axis=0) if n_pad > n else lifted
    levels: List[jnp.ndarray] = [level]
    while level.shape[0] > 1:
        level = leaf.combine(level[0::2], level[1::2])
        levels.append(level)
    return levels


def tree_query(leaf: Leaf, levels: Sequence[jnp.ndarray],
               start: jnp.ndarray, end: jnp.ndarray) -> jnp.ndarray:
    """Vectorized ordered fold over [start, end) for a batch of ranges
    (left accumulator grows rightward, right accumulator leftward, so
    order-sensitive combines stay exact)."""
    q = start.shape[0] if start.ndim else 1
    ident = jnp.broadcast_to(leaf.identity(),
                             (q,) + levels[0].shape[1:])
    res_l = ident
    res_r = ident
    l = start.astype(jnp.int32)
    r = end.astype(jnp.int32)
    # the walk must include the root level: a query spanning the whole
    # tree ([0, n_pad)) only resolves at the root (take_r on m == 1) —
    # skipping it silently returned identity for exactly-full ranges
    for level in levels:
        m = level.shape[0]
        active = l < r
        take_l = active & ((l & 1) == 1)
        take_r = active & ((r & 1) == 1)
        node_l = jnp.take(level, jnp.clip(l, 0, m - 1), axis=0)
        node_r = jnp.take(level, jnp.clip(r - 1, 0, m - 1), axis=0)
        res_l = jnp.where(_bshape(take_l, res_l),
                          leaf.combine(res_l, node_l), res_l)
        res_r = jnp.where(_bshape(take_r, res_r),
                          leaf.combine(node_r, res_r), res_r)
        l = (l + take_l.astype(jnp.int32)) >> 1
        r = (r - take_r.astype(jnp.int32)) >> 1
    return leaf.combine(res_l, res_r)


def sparse_levels(leaf: Leaf, lifted: jnp.ndarray) -> jnp.ndarray:
    """Sparse-table levels for IDEMPOTENT leaves (min/max): stacked
    (L, n, *S) with ``T[j, i] = fold(rows[i : i + 2^j))`` (clamped at the
    right edge).  Built once; any [start, end) fold is then TWO
    overlapping lookups — exact because idempotent+commutative combines
    are insensitive to the overlap and the bracketing."""
    n = lifted.shape[0]
    levels = [lifted]
    j = 1
    while (1 << j) <= max(n, 1):
        prev = levels[-1]
        off = 1 << (j - 1)
        pad = jnp.broadcast_to(leaf.identity(),
                               (min(off, n),) + lifted.shape[1:])
        shifted = jnp.concatenate([prev[off:], pad], axis=0)[:n]
        levels.append(leaf.combine(prev, shifted))
        j += 1
    return jnp.stack(levels, axis=0)


def sparse_query(leaf: Leaf, table: jnp.ndarray, start: jnp.ndarray,
                 end: jnp.ndarray) -> jnp.ndarray:
    """Fold [start, end) from a sparse table: combine the 2^j-row folds
    anchored at ``start`` and ``end - 2^j`` (j = floor(log2(span)))."""
    n = table.shape[1]
    span = jnp.maximum(end - start, 1).astype(jnp.int32)
    j = 31 - jax.lax.clz(span)
    lo = jnp.clip(start, 0, n - 1)
    hi = jnp.clip(end - (1 << j).astype(jnp.int32), 0, n - 1)
    a = table[j, lo]
    b = table[j, hi]
    out = leaf.combine(a, b)
    empty = end <= start
    ident = jnp.broadcast_to(leaf.identity(), out.shape)
    extra = out.ndim - empty.ndim
    empty = empty.reshape(empty.shape + (1,) * extra)
    return jnp.where(empty, ident, out)


class SegmentTree:
    """Ordered (non-commutative-safe) segment tree over lifted leaf states.

    Built once per (window, leaf); answers any [start, end) fold in
    O(log n) combines.  Thin wrapper over ``tree_levels``/``tree_query``
    (which the lowering uses directly to share one build across many
    query sets).
    """

    def __init__(self, leaf: Leaf, lifted: jnp.ndarray):
        self.leaf = leaf
        self.n = lifted.shape[0]
        self.levels = tree_levels(leaf, lifted)

    def query(self, start: jnp.ndarray, end: jnp.ndarray) -> jnp.ndarray:
        """Vectorized fold over [start, end) for a batch of ranges."""
        return tree_query(self.leaf, self.levels, start, end)


# --------------------------------------------------------------------------
# Full window fold for a set of aggregators (one physical window)
# --------------------------------------------------------------------------


def fold_windows(aggs: Sequence[Aggregator], env: Dict[str, jnp.ndarray],
                 start: jnp.ndarray, end: jnp.ndarray,
                 seg_start: jnp.ndarray, seg_flag: jnp.ndarray,
                 ) -> List[jnp.ndarray]:
    """Compute every aggregator's finalized output for each row's window.

    ``env`` holds the *sorted* columns.  Leaves are deduplicated by key —
    the cycle-binding optimization (§4.2): e.g. ``avg`` and ``sum`` over the
    same column share one additive leaf and one scan.
    """
    unique: Dict[str, Leaf] = {}
    for agg in aggs:
        for leaf in agg.leaves:
            unique.setdefault(leaf.key, leaf)

    folded: Dict[str, jnp.ndarray] = {}
    for key, leaf in unique.items():
        lifted = leaf.lift(env)
        if leaf.invertible:
            inclusive = segmented_inclusive_scan(leaf, lifted, seg_flag)
            folded[key] = prefix_window_fold(leaf, inclusive, start, end,
                                             seg_start)
        else:
            tree = SegmentTree(leaf, lifted)
            folded[key] = tree.query(start, end)

    return [agg.finalize(folded) for agg in aggs]
