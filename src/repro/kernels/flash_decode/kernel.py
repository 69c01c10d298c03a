"""Pallas TPU kernel: flash-decode partial attention over a KV shard.

One decode step attends a single query against a long, possibly
sequence-sharded KV cache.  Each shard runs this kernel to produce the
partial-softmax state (m, l, o); shards merge with the same monoid the
feature layer uses for pre-aggregated buckets (``ref.merge_partials``) via
a tiny psum/gather — the paper's aggregator-merge insight applied to
attention (DESIGN.md §2).

Grid: (BH tiles, S tiles).  S is the sequential axis; the online-softmax
accumulators (m, l, o) live in VMEM scratch across S steps and are written
out after the last tile.

BlockSpecs:
    q    (BB, D)        one tile of flattened (batch*heads)
    k, v (BB, BS, D)    KV tile for those rows
    out m,l: (BB, 1); o: (BB, D)

VMEM per step ~ 2*BB*BS*D + 2*BB*D floats; defaults BB=8, BS=512, D=128
=> ~4 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BB = 8
DEFAULT_BS = 512
_NEG = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, m_ref, l_ref, o_ref,
                   acc_m, acc_l, acc_o, *, bs: int, scale: float):
    j = pl.program_id(1)
    n_s = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_m[...] = jnp.full_like(acc_m, _NEG)
        acc_l[...] = jnp.zeros_like(acc_l)
        acc_o[...] = jnp.zeros_like(acc_o)

    q = q_ref[...]                    # (BB, D)
    k = k_ref[...]                    # (BB, BS, D)
    v = v_ref[...]                    # (BB, BS, D)
    lens = len_ref[...]               # (BB, 1) valid KV length per row

    s = jax.lax.dot_general(q, k, (((1,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    # mask positions beyond each row's live length
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < lens, s, _NEG)

    m_prev = acc_m[...]               # (BB, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)            # (BB, BS)
    corr = jnp.exp(m_prev - m_new)    # (BB, 1)
    acc_l[...] = acc_l[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_o[...] = acc_o[...] * corr + pv
    acc_m[...] = m_new

    @pl.when(j == n_s - 1)
    def _emit():
        m_ref[...] = acc_m[...]
        l_ref[...] = acc_l[...]
        o_ref[...] = acc_o[...]


@functools.partial(jax.jit, static_argnames=("bb", "bs", "interpret"))
def decode_partials_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           lengths: jnp.ndarray, bb: int = DEFAULT_BB,
                           bs: int = DEFAULT_BS, *, interpret: bool):
    """q: (N, D) flattened batch*heads; k/v: (N, S, D);
    lengths: (N,) live KV length per row.  Returns (m (N,), l (N,),
    o (N, D)) partial-softmax states."""
    n, d = q.shape
    s = k.shape[1]
    bb = min(bb, n)
    bs = min(bs, s)
    n_pad = (n + bb - 1) // bb * bb
    s_pad = (s + bs - 1) // bs * bs

    qp = jnp.zeros((n_pad, d), jnp.float32).at[:n].set(
        q.astype(jnp.float32))
    kp = jnp.zeros((n_pad, s_pad, d), jnp.float32).at[:n, :s].set(
        k.astype(jnp.float32))
    vp = jnp.zeros((n_pad, s_pad, d), jnp.float32).at[:n, :s].set(
        v.astype(jnp.float32))
    lp = jnp.zeros((n_pad, 1), jnp.int32).at[:n, 0].set(
        lengths.astype(jnp.int32))

    grid = (n_pad // bb, s_pad // bs)
    m, l, o = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, scale=d ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, bs, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bb, bs, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, 1), jnp.float32),
            pltpu.VMEM((bb, 1), jnp.float32),
            pltpu.VMEM((bb, d), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp, lp)
    return m[:n, 0], l[:n, 0], o[:n]
