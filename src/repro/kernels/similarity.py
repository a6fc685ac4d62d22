"""Fused per-client similarity statistics kernel (DiverseFL Step 4).

Computes, for every client row j of the stacked update matrix Z and
guiding matrix G, the three reductions the C1/C2 criteria need —
(z·g, ‖z‖², ‖g‖²) — in a single pass over HBM.  The XLA baseline issues
three separate reductions (three reads of each operand); this kernel
reads each operand once.

Grid: (N / row tile, D / chunk); the chunk axis is the trailing
(sequential) TPU grid dimension, so the (row tile, 128) output block
persists in VMEM across chunk iterations and is written back to HBM once
per row tile.  The row tile is one native sublane tile of the input
dtype (8 rows for 32-bit, 16 for bf16), and the three statistics sit in
lanes 0..2 of a lane-dense output, so every block obeys the TPU's
(8, 128) tiling rule at any N.  Neither axis is padded in HBM: the
partial last column chunk is masked in-kernel, and rows past N only
reach output rows that are sliced away.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

STATS_LANES = 128       # lane-dense output row; lanes 0..2 used

DEFAULT_CHUNK = 16 * 1024


def _row_tile(*dtypes) -> int:
    """One native sublane tile of the narrowest dtype: 8 rows of 32-bit
    values, 16 of bf16, 32 of int8."""
    return 8 * max(4 // jnp.dtype(t).itemsize for t in dtypes)


def _kernel(z_ref, g_ref, out_ref, *, d: int, chunk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    z = z_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if d % chunk:
        # the last chunk overhangs D: its out-of-bounds columns hold
        # unspecified values, which must not reach the sums
        col = j * chunk + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
        z = jnp.where(col < d, z, 0.0)
        g = jnp.where(col < d, g, 0.0)
    dot = jnp.sum(z * g, axis=1, keepdims=True)
    zz = jnp.sum(z * z, axis=1, keepdims=True)
    gg = jnp.sum(g * g, axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] += jnp.where(lane == 0, dot,
                              jnp.where(lane == 1, zz,
                                        jnp.where(lane == 2, gg, 0.0)))


def similarity_kernel(z, g, *, chunk: int = DEFAULT_CHUNK,
                      interpret: bool = False):
    """z, g: (N, D) -> (N, 3) fp32 [dot, ||z||^2, ||g||^2] per client."""
    n, d = z.shape
    chunk = min(chunk, d)
    tn = min(_row_tile(z.dtype, g.dtype), n)
    grid = (pl.cdiv(n, tn), pl.cdiv(d, chunk))
    out = pl.pallas_call(
        functools.partial(_kernel, d=d, chunk=chunk),
        grid=grid,
        in_specs=[pl.BlockSpec((tn, chunk), lambda i, j: (i, j)),
                  pl.BlockSpec((tn, chunk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tn, STATS_LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, STATS_LANES), jnp.float32),
        name="similarity",
        interpret=interpret,
    )(z, g)
    return out[:, :3]
