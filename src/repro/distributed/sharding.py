"""Sharding rules for params, batches, decode caches, and the key-sharded
online store.

Strategy (DESIGN.md §5): TP over ``model`` (output-feature / vocab /
expert / KV-sequence dims), ZeRO-3-style weight sharding over ``data``
(a second tensor dim), DP over ``pod`` × ``data`` for the batch.  With
pjit, sharding choices are *performance* knobs — the SPMD partitioner
keeps the math exact for any assignment — so the rule engine is a
heuristic that the §Perf hillclimb overrides per-tensor.

Rule engine (``auto_pspec``): skip the stacked layer axis (scanned);
among remaining dims, assign ``model`` to the largest divisible dim
(preferring later dims — Megatron column-parallel style), then ``data``
to the largest remaining divisible dim of at least ``min_shard`` rows.
Overrides handle the cases where the heuristic is wrong (routers,
norms, per-head tables).

Feature-store sharding (paper §5 / §7.2 tablet partitioning): the online
store is *key*-partitioned — every row of a given partition key lives on
exactly one shard, so window folds never cross shards.  ``key_shard_mesh``
builds the 1-D mesh, ``stacked_store_sharding`` places a shard-stacked
store pytree (leading dim = shard) with one shard per device, and
``vary_like`` gives loop carries inside ``jax.shard_map`` bodies the
varying-axes type their loop bodies produce.
Routing itself (key -> shard) is host-side hash + rebalance, owned by
``storage.timestore.ShardedOnlineStore``.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["auto_pspec", "param_pspecs", "batch_pspec", "cache_pspecs",
           "named_shardings", "key_shard_mesh", "stacked_store_sharding",
           "vary_like"]


def vary_like(x, *operands):
    """``x`` cast to varying over every manual mesh axis that any of
    ``operands`` varies over.  Under ``jax.shard_map`` a loop carry that
    the body mixes with per-shard data leaves the body varying, and the
    carry's initial value must already have that type.  Outside
    ``shard_map`` nothing varies and ``x`` comes back unchanged."""
    x = jnp.asarray(x)
    axes = set().union(*(jax.typeof(o).vma for o in operands))
    axes -= jax.typeof(x).vma
    if not axes:  # lint: ok J001 — vma is static type data, not a value
        return x
    return jax.lax.pcast(x, tuple(sorted(axes)), to="varying")


def key_shard_mesh(n_shards: Optional[int] = None,
                   axis: str = "shard") -> Mesh:
    """1-D device mesh for the key-sharded online store.

    Defaults to one shard per visible device.  Raises if ``n_shards``
    exceeds the device count — callers wanting more *logical* shards than
    devices use ``ShardedOnlineStore(mesh=None)`` (stacked/vmap mode).
    """
    devs = jax.devices()
    n = n_shards or len(devs)
    if n > len(devs):
        raise ValueError(
            f"{n} shards > {len(devs)} devices; use mesh=None for "
            f"logical sharding on fewer devices")
    return Mesh(np.asarray(devs[:n]), (axis,))


def stacked_store_sharding(mesh: Mesh, axis: str = "shard"):
    """NamedSharding placing dim 0 (the shard dim) of every leaf of a
    shard-stacked pytree on the mesh axis — one store shard per device."""
    return NamedSharding(mesh, P(axis))

# tensors whose name matches are always replicated (small / per-layer
# scalars / norm scales / routing tables)
_REPLICATE_RE = re.compile(
    r"(norm|mix_a|mix_s|w0|u_bonus|mu|b_dt|d_skip|w_dt|b_up|b_down)")


def auto_pspec(path: str, shape: Tuple[int, ...], mesh_shape: Dict[str, int],
               stacked: bool, min_shard: int = 128) -> P:
    model_n = mesh_shape.get("model", 1)
    data_n = mesh_shape.get("data", 1)
    spec = [None] * len(shape)
    if _REPLICATE_RE.search(path) or len(shape) == 0:
        return P(*spec)

    start = 1 if stacked else 0
    dims = list(range(start, len(shape)))
    # model axis: largest divisible dim, ties broken toward later dims
    model_dim = None
    best = -1
    for i in dims:
        if shape[i] % model_n == 0 and shape[i] >= max(min_shard, model_n):
            if shape[i] >= best:
                best = shape[i]
                model_dim = i
    if model_dim is not None:
        spec[model_dim] = "model"
    # data (ZeRO) axis: largest remaining divisible dim
    data_dim = None
    best = -1
    for i in dims:
        if i == model_dim:
            continue
        if shape[i] % data_n == 0 and shape[i] >= max(min_shard, data_n):
            if shape[i] > best:
                best = shape[i]
                data_dim = i
    if data_dim is not None:
        spec[data_dim] = "data"
    return P(*spec)


def _divisible(shape, spec: P, mesh_shape) -> bool:
    for dim, ax in zip(shape, tuple(spec) + (None,) * len(shape)):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = int(np.prod([mesh_shape.get(a, 1) for a in axes]))
        if dim % n:
            return False
    return True


def megatron_overrides(zero: bool = False) -> Dict[str, P]:
    """Megatron-style 1D tensor parallelism: column-parallel up
    projections, row-parallel down projections, vocab-parallel embedding.
    ``zero=True`` adds a ``data`` dim on the *unsharded* weight axis
    (ZeRO-3 weight sharding) for archs whose optimizer state exceeds a
    16-way split (llava-34b, dbrx attention)."""
    d2 = "data" if zero else None
    return {
        r"embed$": P("model", None),
        r"lm_head$": P(None, "model"),
        r"attn/(wq|wk|wv)$": P(None, d2, "model"),
        r"attn/wo$": P(None, "model", d2),
        r"xattn/(wq|wk|wv)$": P(None, d2, "model"),
        r"xattn/wo$": P(None, "model", d2),
        r"mlp/(w_gate|w_up)$": P(None, d2, "model"),
        r"mlp/w_down$": P(None, "model", d2),
        r"moe/router$": P(None, None, None),
        r"moe/(w_gate|w_up)$": P(None, "model", "data", None),
        r"moe/w_down$": P(None, "model", None, "data"),
        r"(shared_gate|shared_up)$": P(None, d2, "model"),
        r"shared_down$": P(None, "model", d2),
        r"attn/q_down$": P(None, None, None),
        r"attn/kv_down$": P(None, None, None),
        r"attn/(q_up|k_up|v_up)$": P(None, None, "model"),
        r"rwkv/(wr|wk|wv|wg|ww|cm_k|cm_r)$": P(None, d2, "model"),
        r"rwkv/(wo|cm_v)$": P(None, "model", d2),
        r"ssm/in_proj$": P(None, d2, "model"),
        r"ssm/out_proj$": P(None, "model", d2),
    }


STRATEGIES = {
    "auto": lambda: {},
    "megatron": lambda: megatron_overrides(zero=False),
    "megatron_zero": lambda: megatron_overrides(zero=True),
    "embed_fix": lambda: {r"embed$": P("model", None),
                          r"lm_head$": P(None, "model")},
}


def param_pspecs(cfg, params_tree, mesh: Mesh,
                 overrides: Optional[Dict[str, P]] = None,
                 strategy: str = "auto"):
    """PartitionSpec pytree matching the params pytree.

    ``strategy`` selects a named override set (hillclimb knob);
    ``overrides`` takes precedence.  Overrides that violate divisibility
    fall back to the auto rule (small archs keep working)."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    merged = dict(STRATEGIES[strategy]())
    merged.update(overrides or {})

    def leaf_spec(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        stacked = pstr.startswith("layers") or pstr.startswith("enc_layers")
        for pat, spec in merged.items():
            if re.search(pat, pstr):
                if _divisible(leaf.shape, spec, mesh_shape):
                    return spec
                break
        return auto_pspec(pstr, leaf.shape, mesh_shape, stacked)

    return jax.tree_util.tree_map_with_path(leaf_spec, params_tree)


def batch_pspec(batch_tree, mesh: Mesh):
    """Batch dim over (pod, data) where divisible; rest replicated."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh_shape)
    dp = int(np.prod([mesh_shape[a] for a in dp_axes]))

    def leaf_spec(leaf):
        if leaf.ndim == 0:
            return P()
        if leaf.shape[0] % dp == 0 and leaf.shape[0] >= dp:
            return P(dp_axes, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map(leaf_spec, batch_tree)


def cache_pspecs(cfg, cache_tree, mesh: Mesh):
    """Decode cache sharding: batch over data (if divisible), the long
    KV-sequence axis over ``model`` (context parallelism — required to
    fit 32k x 128 caches, DESIGN.md §5), heads over model for SSM/RWKV
    states."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_n = mesh_shape.get("model", 1)
    data_n = mesh_shape.get("data", 1)

    def leaf_spec(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        nd = leaf.ndim
        spec = [None] * nd
        if nd == 0 or "len" in pstr:
            return P(*spec)
        # stacked layer caches: (L, B, S, ...) or (L, B, ...)
        if pstr.startswith("layers"):
            if nd >= 2 and leaf.shape[1] % data_n == 0 and \
                    leaf.shape[1] >= data_n:
                spec[1] = "data"
            # KV / latent caches: seq axis = 2 when deep (>= 4096)
            if nd >= 3 and leaf.shape[2] >= 4096 and \
                    leaf.shape[2] % model_n == 0:
                spec[2] = "model"
            elif nd >= 3:
                # state caches: shard the largest model-divisible dim
                best, dim = -1, None
                for i in range(2, nd):
                    if leaf.shape[i] % model_n == 0 and \
                            leaf.shape[i] >= max(128, model_n) and \
                            leaf.shape[i] > best:
                        best, dim = leaf.shape[i], i
                if dim is not None:
                    spec[dim] = "model"
            return P(*spec)
        if pstr.startswith("enc_out"):
            if leaf.shape[0] % data_n == 0 and leaf.shape[0] >= data_n:
                spec[0] = "data"
            return P(*spec)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_tree)


def named_shardings(spec_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
