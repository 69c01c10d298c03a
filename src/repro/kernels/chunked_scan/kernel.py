"""Pallas TPU kernel: chunked first-order linear recurrence.

    y_t = a_t * y_{t-1} + b_t          (diagonal decay, elementwise over D)

This is pre-aggregation applied to the *model* layer (DESIGN.md §2): each
chunk's (prod a, fold b) pair is a bucket partial; the carry across chunks
is the bucket merge.  The same algebra backs the feature function
``ew_avg`` and the SSM/hybrid state updates.

Grid: (batch, T // C).  TPU grids execute sequentially, so the carry lives
in a VMEM scratch buffer persisted across grid steps; it resets when a new
batch row starts.  Within a chunk we run a log2(C)-depth Hillis-Steele
scan on (C, D) tiles — vector ops over the lane dimension, no serial
per-timestep loop.

BlockSpecs: a, b, y tiles are (1, C, D) in VMEM; scratch carry is (1, D).
VMEM: ~4 tiles of C*D floats; defaults C=128, D<=1024 => ~2 MB.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _scan_kernel(a_ref, b_ref, y_ref, carry_ref, *, chunk: int):
    j = pl.program_id(1)  # chunk index within the sequence

    @pl.when(j == 0)
    def _reset():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = a_ref[0]        # (C, D)
    b = b_ref[0]        # (C, D)

    # fold the carried state into the first element
    carry = carry_ref[...]                       # (1, D)
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    b = jnp.where(row == 0, a * carry + b, b)

    # Hillis–Steele inclusive scan of the (a, b) monoid along the chunk
    steps = int(math.log2(chunk))
    for s in range(steps):
        offset = 1 << s
        a_sh = _shift_down(a, offset)
        b_sh = _shift_down(b, offset)
        use = row >= offset
        b = jnp.where(use, a * b_sh + b, b)
        a = jnp.where(use, a * a_sh, a)

    y_ref[0] = b
    carry_ref[...] = b[-1:][...]


def _shift_down(x, k):
    """x shifted by +k along axis 0 (rows < k get zeros/ones upstream)."""
    return jnp.roll(x, k, axis=0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def linear_scan_pallas(a: jnp.ndarray, b: jnp.ndarray,
                       chunk: int = DEFAULT_CHUNK,
                       *, interpret: bool) -> jnp.ndarray:
    """a, b: (B, T, D) -> y: (B, T, D).  T must be a multiple of chunk
    (callers pad; padding steps should use a=1, b=0 to be no-ops)."""
    bsz, t, d = a.shape
    assert chunk & (chunk - 1) == 0, "chunk must be a power of two"
    assert t % chunk == 0, f"T={t} not a multiple of chunk={chunk}"
    grid = (bsz, t // chunk)
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, t, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))
