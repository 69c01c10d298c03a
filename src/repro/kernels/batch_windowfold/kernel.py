"""Pallas TPU kernel: fused batched request-window fold.

The scalar online path does, per request: two binary searches
(``range_bounds``), a bounded gather (``gather_window``), then a tree
fold.  For B requests against additive (invertible) leaves the whole
pipeline fuses into one masked matmul over the (key, ts)-ranked store:

    mask[b, i] = (keys[i] == qkey[b]) & (qt0[b] <= ts[i] <= qt1[b])
    out[b, f]  = sum_i mask[b, i] * vals[i, f]

The mask is built in-register from the store's key/ts columns (no search,
no gather — the time-frame predicate *is* the membership test) and the
reduction runs on the MXU at tile throughput, amortizing one kernel
launch over the whole request batch.

Grid: (B tiles, store tiles).  The store dimension is innermost, so each
request-tile output block stays resident in VMEM and accumulates across
consecutive store tiles (TPU grids execute sequentially).

BlockSpecs (VMEM tiles per step):
    qkey/qt0/qt1  (BB, 1)   request tile
    keys/ts       (BC, 1)   store tile
    vals          (BC, F)   lifted leaf values for the store tile
    out           (BB, F)   request tile's accumulator

VMEM working set: BB*BC mask + BC*F vals + BB*F out floats; defaults
(BB=128, BC=256, F<=512) stay far under 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BB = 128
DEFAULT_BC = 256


def _bwf_kernel(qkey_ref, qt0_ref, qt1_ref, keys_ref, ts_ref, vals_ref,
                out_ref):
    i = pl.program_id(1)   # store tile (innermost: in-place accumulation)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    qk = qkey_ref[...]                        # (BB, 1) int32
    t0 = qt0_ref[...]                         # (BB, 1) int32
    t1 = qt1_ref[...]                         # (BB, 1) int32
    ks = keys_ref[...]                        # (BC, 1) int32
    tss = ts_ref[...]                         # (BC, 1) int32
    # (BB, 1) x (1, BC) broadcast -> (BB, BC) membership mask
    ks_t = jnp.transpose(ks)
    ts_t = jnp.transpose(tss)
    mask = (qk == ks_t) & (ts_t >= t0) & (ts_t <= t1)
    # (BB, BC) @ (BC, F) on the MXU, accumulated into the output tile
    out_ref[...] += jax.lax.dot_general(
        mask.astype(jnp.float32), vals_ref[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bb", "bc", "interpret"))
def batch_windowfold_pallas(keys: jnp.ndarray, ts: jnp.ndarray,
                            vals: jnp.ndarray, qkey: jnp.ndarray,
                            qt0: jnp.ndarray, qt1: jnp.ndarray,
                            bb: int = DEFAULT_BB, bc: int = DEFAULT_BC,
                            *, interpret: bool) -> jnp.ndarray:
    c, f = vals.shape
    b = qkey.shape[0]
    bb = min(bb, _ceil_mult(b, 8))
    bc = min(bc, _ceil_mult(c, 8))
    b_pad = _ceil_mult(b, bb)
    c_pad = _ceil_mult(c, bc)

    # padding queries use key -1 (matches nothing: store keys are >= 0,
    # padding store rows carry INT32_MAX); padding store rows use ts
    # INT32_MIN with an empty frame so they contribute to no query
    qk = jnp.full((b_pad, 1), -1, jnp.int32).at[:b, 0].set(
        qkey.astype(jnp.int32))
    t0 = jnp.zeros((b_pad, 1), jnp.int32).at[:b, 0].set(
        qt0.astype(jnp.int32))
    t1 = jnp.full((b_pad, 1), -1, jnp.int32).at[:b, 0].set(
        qt1.astype(jnp.int32))
    ks = jnp.full((c_pad, 1), -2, jnp.int32).at[:c, 0].set(
        keys.astype(jnp.int32))
    tss = jnp.zeros((c_pad, 1), jnp.int32).at[:c, 0].set(
        ts.astype(jnp.int32))
    vs = jnp.zeros((c_pad, f), jnp.float32).at[:c].set(
        vals.astype(jnp.float32))

    grid = (b_pad // bb, c_pad // bc)
    out = pl.pallas_call(
        _bwf_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, 1), lambda j, i: (j, 0)),
            pl.BlockSpec((bb, 1), lambda j, i: (j, 0)),
            pl.BlockSpec((bb, 1), lambda j, i: (j, 0)),
            pl.BlockSpec((bc, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bc, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bc, f), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, f), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, f), jnp.float32),
        interpret=interpret,
    )(qk, t0, t1, ks, tss, vs)
    return out[:b]


def _ceil_mult(x: int, m: int) -> int:
    return max(m, (x + m - 1) // m * m)
