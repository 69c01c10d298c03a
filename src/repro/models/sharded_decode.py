"""Sequence-sharded decode attention via shard_map partial-softmax merge.

This is the paper's pre-aggregation insight applied to the model layer
(DESIGN.md §2): each `model`-axis shard holds a contiguous KV-cache
chunk and produces the partial-softmax state (m, l, o) — the same monoid
as kernels/flash_decode — merged across shards with two tiny collectives
(a pmax and two psums of (B, H)-sized tensors) instead of all-gathering
the multi-GB cache every step.

Baseline (pjit auto-partitioning) all-gathers ~2 x cache bytes per layer
per step; this path moves O(B*H*D) bytes.  Before/after numbers in
EXPERIMENTS.md §Perf (llama3-8b x decode_32k hillclimb).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_NEG = -1e30


def _partials_gqa(q, k, v, lo, hi, scale):
    """Masked partial-softmax state over a local KV chunk.

    q: (B, Hq, D); k/v: (B, S_loc, Hkv, D); lo/hi: (B,) live range.
    Returns m, l: (B, Hq); o: (B, Hq, D).
    """
    b, hq, d = q.shape
    s_loc, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg,
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(s_loc, dtype=jnp.int32)
    live = (pos[None, :] >= lo[:, None]) & (pos[None, :] < hi[:, None])
    s = jnp.where(live[:, None, None, :], s, _NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(live[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return (m.reshape(b, hq), l.reshape(b, hq), o.reshape(b, hq, d))


def sharded_decode_attention(q, cache_k, cache_v, k_new, v_new, pos,
                             mesh, axis: str = "model",
                             window: int = 0,
                             batch_axis: Optional[str] = "data"):
    """One decode step against a sequence-sharded KV cache.

    q: (B, 1, Hq, D); cache_k/v: (B, S, Hkv, D) sharded P(data, model);
    k_new/v_new: (B, 1, Hkv, D); pos: (B,) current lengths.
    Returns (out (B, 1, Hq, D), new cache_k, new cache_v).
    """
    b, _, hq, d = q.shape
    scale = d ** -0.5
    n_shards = mesh.shape[axis]
    bspec = batch_axis if (batch_axis in mesh.shape and
                           b % mesh.shape[batch_axis] == 0 and
                           b >= mesh.shape[batch_axis]) else None

    def shard_fn(q, ck, cv, kn, vn, pos):
        ax = jax.lax.axis_index(axis)
        s_loc = ck.shape[1]
        start = ax * s_loc
        # ---- write the new token's KV into its owning shard -------------
        # in-place-friendly: one dynamic_update_slice per buffer; the
        # non-owner shards write back the value already at the slot (a
        # (B,1,Hkv,D) gather) instead of select-copying the whole cache
        local_pos = jnp.clip(pos - start, 0, s_loc - 1)
        own = ((pos - start) >= 0) & ((pos - start) < s_loc)

        def write(c, n):
            old = jax.vmap(lambda cc, ii: jax.lax.dynamic_slice(
                cc, (ii, 0, 0), (1,) + cc.shape[1:]))(c, local_pos)
            val = jnp.where(own[:, None, None, None], n, old)
            return jax.vmap(lambda cc, nn, ii: jax.lax.dynamic_update_slice(
                cc, nn, (ii, 0, 0)))(c, val, local_pos)

        ck = write(ck, kn)
        cv = write(cv, vn)
        # ---- local partials over the live (windowed) range -------------
        hi = jnp.clip(pos + 1 - start, 0, s_loc)
        lo = jnp.zeros_like(hi)
        if window is not None and (isinstance(window, jnp.ndarray)
                                   or window):
            w = jnp.asarray(window, jnp.int32)
            lo = jnp.clip(pos + 1 - w - start, 0, s_loc)
        m, l, o = _partials_gqa(q[:, 0], ck, cv, lo, hi, scale)
        # ---- aggregator merge across shards (pre-agg monoid, §5.1) -----
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axis)
        o_g = jax.lax.psum(o * corr[..., None], axis)
        out = (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q.dtype)
        return out[:, None], ck, cv

    cache_spec = P(bspec, axis, None, None)
    rep = P(bspec, None, None, None)
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(rep, cache_spec, cache_spec, rep, rep, P(bspec)),
        out_specs=(rep, cache_spec, cache_spec))
    return fn(q, cache_k, cache_v, k_new, v_new, pos)
