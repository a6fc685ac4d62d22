"""Fused dequantize-and-fold kernel — the int8 streaming aggregation pass.

The streaming AggState fold (fl/streaming.py) accumulates
``acc + Σ_i w_i·u_i`` one client block at a time.  With int8-compressed
update streams (fl/compression.py) the block arrives as an int8 payload
``q`` (1 byte/param) plus per-block f32 scales — dequantizing it to a
dense f32 block before the masked-agg kernel would cost an extra HBM
round-trip of 4·n·D bytes, exactly the traffic compression exists to
remove.  This kernel fuses the dequantization into the weighted-mean
fold: each (n, chunk) int8 tile streams through VMEM **once**, is scaled
in-register by its (n, chunk/qblock) scale tile, weighted, reduced over
clients, and added to the carried (1, chunk) accumulator tile — so the
aggregation pass reads 1 byte per update element instead of 4, and
decompression costs zero extra HBM passes over U.

Grid: (D/chunk, n/CLIENT_TILE) with ``chunk`` a qblock multiple — the
client-axis fold of ``masked_agg.masked_agg_update_kernel``, whose grid
and row masking it shares.  Blocks: weights (CLIENT_TILE, 1); q
(CLIENT_TILE, chunk) int8; scales (CLIENT_TILE, chunk/qblock) f32; the
accumulator (1, chunk) tile stays in VMEM across the client tiles and
its buffer is donated via ``input_output_aliases``.  That kernel remains
the fold for dense-payload codecs (its in-kernel f32 cast is bf16's
whole dequantization).  Nothing is padded in HBM: columns past D read
unspecified payload and scales but only reach output columns that are
sliced away.

Numerics: the kernel computes ``(q·scale)·w`` with the identical
products and the identical axis-0 reduction as the reference
``kernels/ref.dequant_fold_ref``, so on exact-data cases (0/1 weights,
products representable) the two agree bitwise; in general the guarantee
is the usual block-fold fp tolerance (DESIGN.md §10).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .masked_agg import DEFAULT_CHUNK, fold_grid, row_mask


def _kernel(w_ref, q_ref, s_ref, acc_ref, out_ref, *, n: int, tn: int,
            qblock: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = acc_ref[...]

    wt = w_ref[...]                             # (tn, 1) weights
    qf = q_ref[...].astype(jnp.float32)         # (tn, chunk) int8 tile
    s = s_ref[...]                              # (tn, cb) block scales
    sc = jnp.broadcast_to(s[:, :, None],
                          s.shape + (qblock,)).reshape(qf.shape)
    x = (qf * sc) * wt
    ok = row_mask(n, tn)
    if ok is not None:
        x = jnp.where(ok, x, 0.0)
    out_ref[...] += jnp.sum(x, axis=0, keepdims=True)


def dequant_fold_update_kernel(q, scale, w, acc, *, qblock: int,
                               chunk: int = DEFAULT_CHUNK,
                               interpret: bool = False):
    """Streaming int8 accumulate: ``acc + Σ_i w_i · (q_i ⊙ scale_i)``.

    q: (n, D) int8 payload; scale: (n, nb) f32 per-block scales with
    nb = ceil(D / qblock); w: (n,) raw per-client weights (mask already
    folded in, NO 1/|kept| normalization — that happens once at
    ``finalize``); acc: (D,) the carried AggState partial sum.
    """
    n, d = q.shape
    nb = scale.shape[1]
    # chunk must tile in whole quantization blocks
    chunk = max(qblock, (min(chunk, nb * qblock) // qblock) * qblock)
    tn, chunk, grid = fold_grid(n, nb * qblock, chunk)
    cb = chunk // qblock
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, tn=tn, qblock=qblock),
        grid=grid,
        in_specs=[pl.BlockSpec((tn, 1), lambda i, k: (k, 0)),
                  pl.BlockSpec((tn, chunk), lambda i, k: (k, i)),
                  pl.BlockSpec((tn, cb), lambda i, k: (k, i)),
                  pl.BlockSpec((1, chunk), lambda i, k: (0, i))],
        out_specs=pl.BlockSpec((1, chunk), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        input_output_aliases={3: 0},
        name="dequant_fold",
        interpret=interpret,
    )(w.astype(jnp.float32).reshape(n, 1), q, scale.astype(jnp.float32),
      acc.astype(jnp.float32).reshape(1, d))
    return out[0]
