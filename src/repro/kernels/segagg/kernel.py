"""Pallas TPU kernel: segmented aggregation as one-hot MXU matmuls.

The pre-aggregation bucket build (paper §5.1) is a scatter-reduce:
``out[seg_ids[i]] += values[i]``.  Scatters serialize badly on TPU; the
TPU-native formulation is a *matmul against a one-hot membership matrix*:

    out[s, f] = sum_i  onehot[i, s] * values[i, f]
              = (onehot^T @ values)[s, f]

which the MXU executes at full tile throughput.  The grid tiles rows (i)
and segments (j); TPU grids iterate sequentially over the row dimension,
so each (j) output block accumulates across row tiles in place.

BlockSpecs (VMEM tiles):
    values  (BN, F)    rows x all features      (F padded to 128 lanes)
    segs    (BN, 1)    row tile's segment ids
    out     (BS, F)    one segment tile

VMEM working set per step: BN*F + BN*BS + BS*F floats; defaults
(BN=256, BS=256, F<=512) stay well under 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 256
DEFAULT_BS = 256


def _segagg_kernel(segs_ref, values_ref, out_ref, *, bs: int):
    i = pl.program_id(0)   # row tile (sequential, innermost accumulation)
    j = pl.program_id(1)   # segment tile

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    segs = segs_ref[...]                      # (BN, 1) int32
    vals = values_ref[...]                    # (BN, F) f32
    seg0 = j * bs
    local = segs - seg0                       # segment id within this tile
    lanes = jax.lax.broadcasted_iota(jnp.int32, (segs.shape[0], bs), 1)
    onehot = (local == lanes).astype(jnp.float32)      # (BN, BS)
    # (BS, BN) @ (BN, F) on the MXU, accumulate into the output tile
    out_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("n_segments", "bn", "bs", "interpret"))
def segagg_pallas(values: jnp.ndarray, seg_ids: jnp.ndarray,
                  n_segments: int, bn: int = DEFAULT_BN,
                  bs: int = DEFAULT_BS, *, interpret: bool
                  ) -> jnp.ndarray:
    n, f = values.shape
    bn = min(bn, _ceil_mult(n, 8))
    bs = min(bs, _ceil_mult(n_segments, 8))
    n_pad = _ceil_mult(n, bn)
    s_pad = _ceil_mult(n_segments, bs)

    vals = jnp.zeros((n_pad, f), jnp.float32).at[:n].set(
        values.astype(jnp.float32))
    # padding rows get an out-of-range id -> contribute to no tile
    segs = jnp.full((n_pad, 1), -1, jnp.int32).at[:n, 0].set(
        seg_ids.astype(jnp.int32))

    grid = (n_pad // bn, s_pad // bs)
    out = pl.pallas_call(
        functools.partial(_segagg_kernel, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, f), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bs, f), lambda i, j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((s_pad, f), jnp.float32),
        interpret=interpret,
    )(segs, vals)
    return out[:n_segments]


def _ceil_mult(x: int, m: int) -> int:
    return max(m, (x + m - 1) // m * m)
