"""Pallas TPU kernel: feature-signature hashing.

Maps dictionary codes of discrete columns to bounded hashed feature ids
(the paper's export-signature path that avoids materializing
million-dimensional one-hots).  Pure VPU integer ops — murmur3 fmix32 per
lane — tiled (BN, BC) over (rows, columns).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 512


def _hash_kernel(codes_ref, out_ref, *, dim: int, salt: int):
    x = codes_ref[...].astype(jnp.uint32) ^ jnp.uint32(salt)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    out_ref[...] = (x % jnp.uint32(dim)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("dim", "salt", "bn",
                                             "interpret"))
def feature_hash_pallas(codes: jnp.ndarray, dim: int,
                        salt: int = 0x9E3779B9, bn: int = DEFAULT_BN,
                        *, interpret: bool) -> jnp.ndarray:
    orig_shape = codes.shape
    flat = codes.reshape(-1)
    n = flat.shape[0]
    bn = min(bn, max(8, n))
    n_pad = (n + bn - 1) // bn * bn
    padded = jnp.zeros((n_pad, 1), jnp.int32).at[:n, 0].set(
        flat.astype(jnp.int32))
    out = pl.pallas_call(
        functools.partial(_hash_kernel, dim=dim, salt=salt),
        grid=(n_pad // bn,),
        in_specs=[pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        interpret=interpret,
    )(padded)
    return out[:n, 0].reshape(orig_shape)
