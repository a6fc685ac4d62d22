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

``similarity_leaf_kernel`` is the same reduction on one parameter leaf
as the vmapped SGD wrote it, stacked ``(N, *s)`` and viewed as
``(N, R, L)`` with ``L = s[-1]``: a bitcast wherever ``s[-2]`` is a
multiple of the sublane tile, so no ``(N, D)`` row matrix is built.  The
client axis is an untiled leading grid axis; each client's (tr, lc)
blocks are summed into its own lane-dense output row, and the edge
blocks are masked in-kernel.  Summing the per-leaf statistics across
leaves gives the statistics of the raveled rows (kernels/ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

STATS_LANES = 128       # lane-dense output row; lanes 0..2 used

DEFAULT_CHUNK = 16 * 1024

# one f32 operand block of a leaf view in VMEM: two operands, double
# buffered, plus the block's f32 temporaries stay inside v5e's default
# scoped VMEM (16 MiB)
LEAF_BLOCK_BYTES = 1 << 20


def _row_tile(*dtypes) -> int:
    """One native sublane tile of the narrowest dtype: 8 rows of 32-bit
    values, 16 of bf16, 32 of int8."""
    return 8 * max(4 // jnp.dtype(t).itemsize for t in dtypes)


def leaf_tiles(r: int, l: int, dtypes, budget: int = LEAF_BLOCK_BYTES):
    """(row tile, column tile) of an (R, L) leaf view: whole rows of L
    (lane-padded to 128) in blocks of about ``budget`` f32 bytes, row
    tiles a multiple of the sublane tile of ``dtypes``; a row too wide
    for even one sublane tile of rows is cut into 128-lane multiples."""
    granule = _row_tile(*dtypes)
    lanes = -(-l // 128) * 128
    if granule * lanes * 4 <= budget:
        lc = l
        tr = budget // (lanes * 4) // granule * granule
    else:
        tr = granule
        lc = min(l, max(128, budget // (granule * 4) // 128 * 128))
    return min(tr, r), lc


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


def _leaf_kernel(z_ref, g_ref, out_ref, *, r: int, l: int, tr: int, lc: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    z = z_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    # edge blocks overhang the view: their outside elements hold
    # unspecified values, which must not reach the sums
    ok = None
    if r % tr:
        ok = i * tr + jax.lax.broadcasted_iota(jnp.int32, z.shape, 0) < r
    if l % lc:
        col = j * lc + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) < l
        ok = col if ok is None else ok & col
    if ok is not None:
        z = jnp.where(ok, z, 0.0)
        g = jnp.where(ok, g, 0.0)

    def total(x):                                     # (tr, lc) -> (1, 1)
        return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1,
                       keepdims=True)
    dot, zz, gg = total(z * g), total(z * z), total(g * g)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] += jnp.where(lane == 0, dot,
                              jnp.where(lane == 1, zz,
                                        jnp.where(lane == 2, gg, 0.0)))


def similarity_leaf_kernel(z, g, *, block_bytes: int = LEAF_BLOCK_BYTES,
                           interpret: bool = False):
    """z, g: (N, R, L) leaf views -> (N, 3) fp32 [dot, ||z||^2, ||g||^2]
    per client over the leaf."""
    n, r, l = z.shape
    tr, lc = leaf_tiles(r, l, (z.dtype, g.dtype), block_bytes)
    block = pl.BlockSpec((None, tr, lc), lambda c, i, j: (c, i, j))
    out = pl.pallas_call(
        functools.partial(_leaf_kernel, r=r, l=l, tr=tr, lc=lc),
        grid=(n, pl.cdiv(r, tr), pl.cdiv(l, lc)),
        in_specs=[block, block],
        out_specs=pl.BlockSpec((None, 1, STATS_LANES),
                               lambda c, i, j: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, STATS_LANES), jnp.float32),
        name="similarity",
        interpret=interpret,
    )(z, g)
    return out[:, 0, :3]
