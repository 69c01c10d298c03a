"""Unified query plan compiler (§4) — one plan, one lowering, thin drivers.

The consistency mechanism: a FeaturePlan lowers ONCE (``core.lowering``)
to per-window folds, LAST JOIN resolution, and scalar evaluation; the
offline schedules (fused / serial / key-sharded, ``lowering.drivers``)
and the online request drivers (scalar / vmapped batch / fused kernel /
key-sharded) are thin executors over that shared lowering.  Same
lowering => the paper's months-long online/offline verification
collapses to a unit test (tests/test_consistency.py), and the sharded
offline engine is bit-exact against the single-device one by
construction (tests/test_offline_sharded.py).

Compilation-level optimizations reproduced from §4.2:

  * window merging      — done in plan.build_plan (canonical WindowSpec);
  * cycle binding       — leaf-level CSE (lowering.windows.unique_leaves);
  * compilation cache   — lowering.cache, keyed by (plan fingerprint,
                          driver, shape/plan signature); cache hits skip
                          tracing and XLA compilation entirely
                          (bench_glq_compile).

This module is the stable facade: ``CompiledScript``'s API is unchanged
from the pre-lowering compiler.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..storage import timestore
from .expr import ColumnRef, Expr
from .lowering import drivers as _drv
from .lowering import windows as _lw
from .lowering.cache import cache_stats, cached, clear_cache  # noqa: F401
from .lowering.joins import join_columns
from .plan import FeaturePlan, FeatureScript, build_plan
from .types import Table

__all__ = ["CompileContext", "CompiledScript", "compile_script",
           "cache_stats", "clear_cache"]

INT_MIN = _lw.INT_MIN


# ---------------------------------------------------------------------------
# Context
# ---------------------------------------------------------------------------


class CompileContext:
    """Static compile-time info: category cardinalities, buffer sizes,
    offline partition-unit parameters (§6.2)."""

    def __init__(self, tables: Optional[Dict[str, Table]] = None,
                 default_cardinality: int = 32,
                 max_cardinality: int = 256,
                 online_buffer: int = 256,
                 cardinality_overrides: Optional[Dict[str, int]] = None,
                 offline_slice_rows: int = 1024,
                 offline_max_slices: int = 8,
                 distinct_hll_p: Optional[int] = None,
                 distinct_hll_min_card: int = 64,
                 fused_unit_fold: bool = False,
                 unit_fold_pallas: Optional[bool] = None,
                 unit_fold_interpret: Optional[bool] = None):
        self.tables = tables or {}
        self.default_cardinality = default_cardinality
        self.max_cardinality = max_cardinality
        self.online_buffer = online_buffer
        self.overrides = dict(cardinality_overrides or {})
        # §6.2 unit planning: hot keys with more than offline_slice_rows
        # rows are cut into at most offline_max_slices time slices.  The
        # parameters are part of the *plan*, so every offline schedule
        # (single-device or sharded) folds identical units.
        self.offline_slice_rows = offline_slice_rows
        self.offline_max_slices = offline_max_slices
        # optional mergeable-sketch leaf for distinct_count over wide
        # key universes (functions.HLLLeaf): columns with cardinality >=
        # distinct_hll_min_card fold a 2^p-register HyperLogLog instead
        # of an exact (cardinality,)-histogram — O(2^p) pre-agg bucket
        # state at ~1.04/sqrt(2^p) relative error
        self.distinct_hll_p = distinct_hll_p
        self.distinct_hll_min_card = distinct_hll_min_card
        # fused unit-fold megakernel (kernels/unit_fold): route every
        # driver's fold through one gather+bounds+build+query dispatch.
        # Results are bitwise the staged path's (tests/test_kernels.py).
        # The pallas/interpret selectors follow kernels.dispatch.resolve
        # semantics: None autodetects TPU, explicit booleans win.
        self.fused_unit_fold = fused_unit_fold
        self.unit_fold_pallas = unit_fold_pallas
        self.unit_fold_interpret = unit_fold_interpret

    def cardinality(self, expr: Expr) -> int:
        if isinstance(expr, ColumnRef):
            if expr.name in self.overrides:
                return self.overrides[expr.name]
            for t in self.tables.values():
                d = t.dicts.get(expr.name)
                if d is not None:
                    c = max(8, len(d))
                    return min(self.max_cardinality, _round8(c))
        return self.default_cardinality


def _round8(x: int) -> int:
    return (x + 7) // 8 * 8


# ---------------------------------------------------------------------------
# Compiled script — the stable facade over core.lowering
# ---------------------------------------------------------------------------


class CompiledScript:
    """A deployed feature script: offline + online drivers sharing one
    lowering."""

    def __init__(self, script: FeatureScript, ctx: CompileContext):
        self.script = script
        self.ctx = ctx
        self.plan: FeaturePlan = build_plan(script)
        self._fingerprint = script.fingerprint()   # hashed once
        self._online_fns: Dict[Tuple, Any] = {}
        self.windows: List[_lw.LoweredWindow] = _lw.lower_windows(
            self.plan, script, ctx)
        self.join_cols: Dict[str, List[str]] = join_columns(self.plan,
                                                            script)

    @property
    def fingerprint(self) -> str:
        return self._fingerprint

    @property
    def feature_names(self) -> List[str]:
        return [it.name for it in self.script.select]

    def describe_plan(self) -> str:
        return self.plan.describe()

    # ======================================================================
    # OFFLINE driver (batch over whole tables)
    # ======================================================================

    def offline(self, tables: Dict[str, Table]) -> Dict[str, np.ndarray]:
        """Default offline schedule: fused window-parallel branches."""
        return _drv.offline_fused(self, tables)

    def offline_serial(self, tables: Dict[str, Table]
                       ) -> Dict[str, np.ndarray]:
        """Serialized-branch baseline schedule (bench_offline)."""
        return _drv.offline_serial(self, tables)

    def offline_sharded(self, tables: Dict[str, Table], mesh=None,
                        n_shards: Optional[int] = None,
                        axis: str = "shard") -> Dict[str, np.ndarray]:
        """Key-partitioned, skew-aware offline execution on a device mesh
        (bit-exact vs ``offline``; see lowering.drivers.offline_sharded)."""
        return _drv.offline_sharded(self, tables, mesh=mesh,
                                    n_shards=n_shards, axis=axis)

    # ======================================================================
    # ONLINE driver (request mode against the live store)
    # ======================================================================

    def required_store_columns(self) -> Dict[str, List[str]]:
        """Which columns each table's online store must retain."""
        need: Dict[str, set] = {}
        for w in self.windows:
            spec = w.node.spec
            for t in w.sources:
                s = need.setdefault(t, set())
                s |= set(w.needed_cols)
                s.add(spec.partition_by)
        for js in self.script.last_joins:
            s = need.setdefault(js.right_table, set())
            s |= set(self.join_cols.get(js.right_table, []))
            s.add(js.right_key)
        need.setdefault(self.script.base_table, set())
        return {t: sorted(cs - {"ts"}) for t, cs in need.items()}

    def _online_fn(self, states, key, ts, values, preagg_states,
                   use_preagg=False):
        return _drv.online_fn(self, states, key, ts, values,
                              preagg_states, use_preagg=use_preagg)

    def online(self, store: "timestore.OnlineStore", key: int, ts: int,
               values: Dict[str, float],
               preagg_states: Optional[Dict[int, Any]] = None
               ) -> Dict[str, np.ndarray]:
        """Compute features for one request tuple (virtually inserted)."""
        return _drv.online(self, store, key, ts, values,
                           preagg_states=preagg_states)

    # kept as API for callers that pre-pad request batches themselves
    _pad_batch = staticmethod(_drv.pad_batch)

    def _store_fn(self, store, kind: str, extra: Tuple, builder):
        return _drv.store_fn(self, store, kind, extra, builder)

    def online_batch(self, store: "timestore.OnlineStore",
                     keys: Sequence[int], ts: Sequence[int],
                     values: Dict[str, Sequence[float]],
                     preagg_states: Optional[Dict[int, Any]] = None
                     ) -> Dict[str, np.ndarray]:
        """Features for B requests in ONE jitted call (vmapped online
        driver); bit-identical to B scalar ``online`` calls."""
        return _drv.online_batch(self, store, keys, ts, values,
                                 preagg_states=preagg_states)

    # -- key-sharded batch driver (mesh-distributed serving) ---------------
    def sharded_eligible(self) -> Tuple[bool, str]:
        """Whether the script can serve from a key-sharded store: every
        row a request touches must live on the request key's shard, i.e.
        all windows partition by one column (engine-enforced already) and
        every LAST JOIN routes by that same column."""
        part = {w.node.spec.partition_by for w in self.windows}
        if not part:
            return False, "no window partition column to shard by"
        if len(part) > 1:
            return (False,
                    f"windows partition by multiple columns "
                    f"{sorted(part)}: requests can only be routed by "
                    f"one key")
        for js in self.script.last_joins:
            if js.left_key not in part:
                return (False,
                        f"LAST JOIN keys on {js.left_key!r}, not the "
                        f"window partition column {sorted(part)[0]!r}: "
                        f"the joined row may live on another shard")
        return True, ""

    def online_sharded_batch(self, store, keys: Sequence[int],
                             ts: Sequence[int],
                             values: Dict[str, Sequence[float]],
                             preagg_states: Optional[Dict[int, Any]] = None
                             ) -> Dict[str, np.ndarray]:
        """Features for B requests against a ``ShardedOnlineStore``:
        host key-routing into (n_shards, b_pad) blocks, one jitted
        ``shard_map`` fan-out running the same vmapped ``_online_fn``
        per shard (bit-exact vs the unsharded path), request-order
        reassembly (see lowering.drivers.online_sharded_batch)."""
        return _drv.online_sharded_batch(self, store, keys, ts, values,
                                         preagg_states=preagg_states)

    def _sharded_fn(self, store, use_pre: bool, b_pad: int):
        return _drv._sharded_store_fn(self, store, use_pre, b_pad)

    def _observe_queries(self, ts_list: Sequence[int]):
        """§5.1 adaptive hierarchy: host-side per-query level stats."""
        for w in self.windows:
            if w.preagg is None:
                continue
            for t in ts_list:
                w.preagg.observe_query(int(t))

    # -- fused megakernel fast path (kernels/unit_fold) --------------------
    def fast_batch_eligible(self) -> Tuple[bool, str]:
        """Whether the fused batch path can serve this script.  The unit
        fold megakernel covers every leaf family and frame type (and the
        LAST JOIN tail runs vmapped alongside it), so every script is
        eligible; the method remains for callers that gate on it."""
        return True, ""

    def online_batch_fast(self, store: "timestore.OnlineStore",
                          keys: Sequence[int], ts: Sequence[int],
                          values: Dict[str, Sequence[float]],
                          use_pallas: Optional[bool] = None,
                          interpret: Optional[bool] = None
                          ) -> Dict[str, np.ndarray]:
        """Fused megakernel fast path (see drivers.online_fast_fn): one
        ``kernels.unit_fold`` dispatch per window group serves the whole
        batch, BITWISE equal to ``online_batch``.  ``use_pallas`` /
        ``interpret`` default to TPU autodetection
        (kernels.dispatch.resolve)."""
        return _drv.online_batch_fast(self, store, keys, ts, values,
                                      use_pallas=use_pallas,
                                      interpret=interpret)

    def online_batch_fast_hlo(self, store: "timestore.OnlineStore",
                              keys: Sequence[int], ts: Sequence[int],
                              values: Dict[str, Sequence[float]]) -> str:
        """Compiled HLO text of the executable ``online_batch_fast``
        runs for this batch."""
        return _drv.online_batch_fast_hlo(self, store, keys, ts, values)

    # -- pre-aggregation plumbing -------------------------------------------
    def init_preagg_states(self) -> Dict[int, Any]:
        return {wi: w.preagg.init_state()
                for wi, w in enumerate(self.windows) if w.preagg is not None}

    def init_preagg_states_sharded(self, n_shards: int) -> Dict[int, Any]:
        """Per-shard bucket states (leading shard dim on every leaf)."""
        return {wi: w.preagg.init_state_stacked(n_shards)
                for wi, w in enumerate(self.windows) if w.preagg is not None}

    def preagg_owned_masks(self, owner_fn, n_shards: int
                           ) -> Dict[int, np.ndarray]:
        """Per-window one-hot (n_shards, n_keys) ownership masks.

        ``owner_fn(key_indices) -> shard ids`` is the store's routing
        (``ShardedOnlineStore.owner_of_keys``), evaluated over each
        window's bounded key universe [0, n_keys).  Masks change only on
        rebalance — callers cache the result against the store's
        assignment version (see FeatureEngine._preagg_owned) instead of
        rebuilding on the hot write path.
        """
        masks = {}
        for wi, w in enumerate(self.windows):
            if w.preagg is None:
                continue
            nk = w.preagg.n_keys
            owners = np.asarray(owner_fn(np.arange(nk)))
            owned = np.zeros((n_shards, nk), bool)
            owned[owners, np.arange(nk)] = True
            masks[wi] = jnp.asarray(owned)
        return masks

    def preagg_update_many_sharded(self, pre_states: Dict[int, Any],
                                   table: str, keys, ts,
                                   values: Dict[str, Any],
                                   owned_masks: Dict[int, Any]):
        """Batched pre-agg maintenance on key-sharded states: each
        window's ownership mask restricts every shard's bucket scatter
        to the planes it owns (see PreAgg.update_many_sharded)."""
        for wi, w in enumerate(self.windows):
            if w.preagg is None or table not in w.sources:
                continue
            pre_states[wi] = w.preagg.update_many_sharded(
                pre_states[wi], keys, ts, values, owned_masks[wi])
        return pre_states

    def preagg_update(self, pre_states: Dict[int, Any], table: str,
                      key: int, ts: int, values: Dict[str, float]):
        """Fold one ingested row into every relevant window's buckets —
        driven from the store binlog (asynchronous, §5.1)."""
        for wi, w in enumerate(self.windows):
            if w.preagg is None or table not in w.sources:
                continue
            pre_states[wi] = w.preagg.update(
                pre_states[wi], key, ts, values)
        return pre_states

    def preagg_update_many(self, pre_states: Dict[int, Any], table: str,
                           keys, ts, values: Dict[str, Any]):
        """Batched pre-agg maintenance: fold N ingested rows per window
        with one segment-fold + scatter (see PreAgg.update_many)."""
        for wi, w in enumerate(self.windows):
            if w.preagg is None or table not in w.sources:
                continue
            pre_states[wi] = w.preagg.update_many(pre_states[wi], keys, ts,
                                                  values)
        return pre_states


def compile_script(script_or_sql, tables: Optional[Dict[str, Table]] = None,
                   **ctx_kwargs) -> CompiledScript:
    """Front door: SQL text or FeatureScript -> CompiledScript."""
    if isinstance(script_or_sql, str):
        from .sql import parse

        script = parse(script_or_sql)
    else:
        script = script_or_sql
    ctx = CompileContext(tables=tables, **ctx_kwargs)
    return CompiledScript(script, ctx)
