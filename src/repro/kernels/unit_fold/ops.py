"""Public entry for the fused unit-fold op.

``unit_fold(specs, leaves, env, queries)`` folds one window group's
padded unit(s) — every member window, every deduplicated leaf — in one
dispatch.  It accepts both unit layouts the engine produces:

* **single unit** (env arrays (R, ...)): the online request path;
  vmap-safe, always served by the hand-fused XLA reference;
* **batched units** (env arrays (U, R, ...)): the offline block fold
  and the batched fast serving path; served by the vmapped reference
  or, with ``use_pallas``, the Pallas kernel (rows padded to a power
  of two with identity values / INT_MAX timestamps — provably
  value-preserving, see kernel.py).

``unit_fold_blocks(specs, leaves, flat_env, idx)`` is the
relayout-free offline entry: it consumes the §6.2 unit-block layout
directly — flat pad-appended columns plus the (U, R) gather index the
offline planner already holds — lifting each leaf group's lanes ONCE
over the flat rows and gathering (U, R, F) lane blocks natively, with
no per-call reshape/concat relayout.  Bitwise-equal to gathering the
columns first (every ``Leaf.lift`` is row-local with fill == identity).

Both paths are bitwise (``array_equal``) against the staged
``lowering.windows.fold_unit`` — gated by tests/test_kernels.py.
Dispatch policy lives in ``kernels.dispatch``: explicit booleans win,
``None`` autodetects TPU (Pallas compiled) vs everything else (ref;
kernel bodies still run under ``interpret=True`` in tests).

``UnitFoldPlan`` construction (leaf stacking + per-lane identity
vectors) is hoisted into the shared lowering cache
(``core.lowering.cache``) keyed by the group's static signature —
repeated folds of the same script (snapshot swaps, B-pad classes,
offline iterations) reuse one plan and its resident identity vectors.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import dispatch
from . import ref as _ref
from . import kernel as _kernel

__all__ = ["unit_fold", "unit_fold_blocks", "prelift_blocks", "plan_for"]


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _leaf_sig(key: str, leaf) -> Tuple:
    # the key embeds the argument expression fingerprint (and HLL p), so
    # (key, type, shape, decay) pins the leaf's lift/combine semantics
    return (key, type(leaf).__name__, tuple(leaf.shape),
            float(getattr(leaf, "decay", 0.0) or 0.0))


def plan_for(specs: Sequence[Any], leaves: Dict[str, Any],
             order_by: str,
             member_keys: Optional[Sequence[Sequence[str]]] = None
             ) -> Tuple[Any, Tuple[jnp.ndarray, ...]]:
    """Cached ``(UnitFoldPlan, per-group identity vectors)`` for one
    window group — built once per static group signature and shared by
    every driver through ``core.lowering.cache`` (plan + ident arrays
    stay resident across snapshot swaps and repeated pad classes).

    ``member_keys`` (per-member leaf-key usage) masks each leaf group's
    query stage to the members that use it — see ``ref.build_plan``."""
    from ...core.lowering.cache import cached

    mk = (None if member_keys is None
          else tuple(tuple(ks) for ks in member_keys))
    key = ("unit_fold_plan", order_by,
           tuple(s.canonical() for s in specs),
           tuple(_leaf_sig(k, l) for k, l in leaves.items()), mk)

    def build():
        # the plan may first be demanded inside a jit trace; its ident
        # vectors are compile-time constants and must be materialized
        # eagerly, or cached tracers would escape the trace
        with jax.ensure_compile_time_eval():
            plan = _ref.build_plan(specs, leaves, order_by,
                                   member_keys=mk)
            ident = tuple(_ref.group_identity(g) for g in plan.groups)
        return plan, ident

    return cached(key, build)


def _unstack_batched(plan, folded_groups: Sequence[jnp.ndarray]
                     ) -> List[Dict[str, jnp.ndarray]]:
    """Scatter per-group (U, Mg, Q, F) fold blocks into per-member
    ``{leaf key: (U, Q, *S)}`` dicts (rows in ``members_ix`` order)."""
    out: List[Dict[str, jnp.ndarray]] = [{} for _ in plan.specs]
    for grp, folded in zip(plan.groups, folded_groups):
        members_ix = grp.members_ix or tuple(range(len(plan.specs)))
        for row, mi in enumerate(members_ix):
            fm = folded[:, row]                # (U, Q, F)
            off = 0
            for key, leaf, size in zip(grp.keys, grp.leaves, grp.sizes):
                out[mi][key] = fm[..., off:off + size].reshape(
                    fm.shape[:2] + leaf.shape)
                off += size
    return out


def _run_pallas(plan, ident_list, data_list: List[jnp.ndarray],
                ts: jnp.ndarray, queries: jnp.ndarray, r_real: int,
                interpret: bool) -> List[Dict[str, jnp.ndarray]]:
    """Pad (U, rp, F) lane blocks to a pow2 row count (at least one
    8-row sublane tile) if needed and run the lane-tiled Pallas kernel.
    ``data_list`` rows beyond ``r_real`` must already be identity/INT_MAX
    (the offline blocks satisfy this by construction; the batched online
    path pads here)."""
    u, r = ts.shape
    rp = max(8, _next_pow2(r))
    if rp > r:
        padded = []
        for grp, iv, data in zip(plan.groups, ident_list, data_list):
            pad = jnp.broadcast_to(iv, (u, rp - r, data.shape[-1]))
            padded.append(jnp.concatenate([data, pad], axis=1))
        data_list = padded
        ts = jnp.concatenate(
            [ts, jnp.full((u, rp - r), _ref.INT_MAX, ts.dtype)], axis=1)
    folded_groups = _kernel.unit_fold_pallas(
        plan, data_list, [iv[None] for iv in ident_list], ts,
        queries.astype(jnp.int32), r_real=r_real, interpret=interpret)
    return _unstack_batched(plan, folded_groups)


def _member_keys(specs: Sequence[Any],
                 member_keys: Optional[Sequence[Sequence[str]]]
                 ) -> Optional[Tuple[Tuple[str, ...], ...]]:
    if member_keys is None:
        return None
    if len(member_keys) != len(specs):
        raise ValueError(
            f"member_keys covers {len(member_keys)} members, "
            f"plan has {len(specs)}")
    return tuple(tuple(ks) for ks in member_keys)


def _pallas_batched(plan, ident_list, env: Dict[str, Any],
                    queries: jnp.ndarray, interpret: bool
                    ) -> List[Dict[str, jnp.ndarray]]:
    ts = env[plan.order_by]
    u, r = ts.shape
    data_list = []
    for grp in plan.groups:
        data = jax.vmap(lambda e, g=grp: _ref.lift_group(g, e))(env)
        data_list.append(data.reshape(u, r, -1))
    return _run_pallas(plan, ident_list, data_list, ts, queries,
                       r_real=r, interpret=interpret)


def unit_fold(specs: Sequence[Any], leaves: Dict[str, Any],
              env: Dict[str, Any],
              queries: Optional[jnp.ndarray] = None, *, order_by: str,
              member_keys: Optional[Sequence[Sequence[str]]] = None,
              use_pallas: Optional[bool] = None,
              interpret: Optional[bool] = None
              ) -> List[Dict[str, jnp.ndarray]]:
    """Fused fold of one window group over one unit or a (U, R) block.

    ``specs`` are the member WindowSpecs, ``leaves`` the group's
    deduplicated ``{key: Leaf}`` set, ``env`` the padded unit columns
    (incl. ``order_by`` and ``__valid__``), ``queries`` the unit
    positions to emit (default: every row).  Returns one
    ``{leaf key: (..., Q, *S)}`` dict per member; with ``member_keys``
    each member's dict covers (at least) its own leaf usage, without it
    the full group leaf set.
    """
    use_pallas, interpret = dispatch.resolve(use_pallas, interpret,
                                            flag="unit_fold_pallas")
    plan, ident_list = plan_for(specs, leaves, order_by,
                                _member_keys(specs, member_keys))
    ts = jnp.asarray(env[order_by])
    batched = ts.ndim == 2
    # default queries stay an UNBATCHED (R,) iota: under vmap they ride
    # along as a constant, so ROWS-frame bounds (and every query-index
    # expression) constant-fold once instead of recomputing per unit
    shared_q = queries is None
    if shared_q:
        queries = jnp.arange(ts.shape[-1], dtype=jnp.int32)
    queries = jnp.asarray(queries, jnp.int32)
    if not use_pallas:
        if batched:
            return jax.vmap(
                lambda e, qq: _ref.unit_fold_ref(plan, e, qq),
                in_axes=(0, None if shared_q else 0),
            )(dict(env), queries)
        return _ref.unit_fold_ref(plan, env, queries)
    if batched and shared_q:
        queries = jnp.broadcast_to(queries, ts.shape)
    if not batched:
        env_b = {k: jnp.asarray(v)[None] for k, v in env.items()}
        out = _pallas_batched(plan, ident_list, env_b, queries[None],
                              interpret)
        return [{k: v[0] for k, v in d.items()} for d in out]
    return _pallas_batched(plan, ident_list, dict(env), queries, interpret)


# lane width at which lifting the FLAT rows once (then gathering wide
# lane blocks) beats gathering the raw columns and lifting in-register:
# expansion-heavy lifts (HLL one-hot, histogram states) pay for their
# lane traffic, narrow groups (scalar sums, EW/drawdown states) don't
PRELIFT_MIN_WIDTH = 8


def _prelift_group(group) -> bool:
    return group.width >= PRELIFT_MIN_WIDTH


def prelift_blocks(specs: Sequence[Any], leaves: Dict[str, Any],
                   flat_env: Dict[str, Any], *, order_by: str,
                   member_keys: Optional[Sequence[Sequence[str]]] = None
                   ) -> Tuple:
    """Build the flat lane data every block of a group lowering shares:
    the cached plan + ident vectors, and — for expansion-heavy leaf
    groups (see ``PRELIFT_MIN_WIDTH``) — the group's lanes lifted ONCE
    over the flat pad-appended rows.  Narrow groups carry ``None`` and
    lift per unit from the gathered raw columns instead (one shared
    column gather, lifts fused in-register — the cheaper layout when the
    lift expands few lanes).  Pass the result to
    ``unit_fold_blocks(..., prelift=)`` for each block — multi-block
    groups then pay one flat lift total instead of one per block."""
    plan, ident_list = plan_for(specs, leaves, order_by,
                                _member_keys(specs, member_keys))
    flat_data = [_ref.lift_group(g, flat_env) if _prelift_group(g)
                 else None for g in plan.groups]
    cols = {c: jnp.asarray(v) for c, v in flat_env.items()
            if c not in (order_by, "__valid__")}
    return (plan, ident_list, flat_data, jnp.asarray(flat_env[order_by]),
            cols)


def unit_fold_blocks(specs: Sequence[Any], leaves: Dict[str, Any],
                     flat_env: Dict[str, Any], idx: jnp.ndarray,
                     queries: Optional[jnp.ndarray] = None, *,
                     order_by: str,
                     member_keys: Optional[Sequence[Sequence[str]]] = None,
                     use_pallas: Optional[bool] = None,
                     interpret: Optional[bool] = None,
                     prelift: Optional[Tuple] = None
                     ) -> List[Dict[str, jnp.ndarray]]:
    """Relayout-free fold of one window group over a §6.2 unit block.

    ``flat_env`` holds the group's FLAT pad-appended columns — the
    merged (key, ts, rank, arrival)-sorted rows plus one sentinel row
    (``order_by`` = INT_MAX, ``__valid__`` = False) — and ``idx`` the
    (U, R) flat-row gather index of the block (pad slots point at the
    sentinel).  Layout invariant (guaranteed by the §6.2 producer):
    every flat row except the trailing sentinel is valid, so row
    validity is exactly ``idx < n_flat - 1`` — the gather-then-lift
    path for narrow groups recomputes it from ``idx`` instead of
    gathering the ``__valid__`` column.  Each leaf group's lanes lift once over the flat rows;
    one ``take`` per group then builds its (U, R, F) lane block natively
    in the layout both the XLA ref and the Pallas kernel consume — no
    per-call reshape/concat.  Bitwise-equal to ``unit_fold`` over the
    gathered per-unit env (lifts are row-local, sentinel lifts to
    identity), gated in tests/test_kernels.py.
    """
    use_pallas, interpret = dispatch.resolve(use_pallas, interpret,
                                            flag="unit_fold_pallas")
    if prelift is None:
        prelift = prelift_blocks(specs, leaves, flat_env,
                                 order_by=order_by,
                                 member_keys=member_keys)
    plan, ident_list, flat_data, flat_ts, flat_cols = prelift
    idx = jnp.asarray(idx)
    ts = jnp.take(flat_ts, idx)                             # (U, R)
    shared_q = queries is None
    if shared_q:
        # unbatched (R,) iota — constant under vmap (see unit_fold)
        queries = jnp.arange(ts.shape[-1], dtype=jnp.int32)
    queries = jnp.asarray(queries, jnp.int32)
    env_unit = None
    data_list = []
    for grp, fd in zip(plan.groups, flat_data):
        if fd is not None:
            data_list.append(jnp.take(fd, idx, axis=0))
            continue
        if env_unit is None:
            # narrow groups gather the raw columns once (shared across
            # every such group) and lift in-register per unit; the
            # sentinel invariant makes validity a pure index test
            env_unit = {c: jnp.take(v, idx, axis=0)
                        for c, v in flat_cols.items()}
            env_unit[plan.order_by] = ts
            env_unit["__valid__"] = idx < flat_ts.shape[0] - 1
        data_list.append(jax.vmap(
            lambda e, g=grp: _ref.lift_group(g, e))(env_unit))
    if not use_pallas:
        return jax.vmap(
            lambda dl, t, qq: _ref.unit_fold_ref_data(plan, list(dl), t, qq),
            in_axes=(0, 0, None if shared_q else 0),
        )(tuple(data_list), ts, queries)
    u, r = ts.shape
    if shared_q:
        queries = jnp.broadcast_to(queries, ts.shape)
    data_flat = [d.reshape(u, r, -1) for d in data_list]
    return _run_pallas(plan, ident_list, data_flat, ts, queries,
                       r_real=r, interpret=interpret)
