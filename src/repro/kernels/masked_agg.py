"""Fused masked-mean aggregation kernel (DiverseFL Step 5, Eq. 6).

Computes the mean of the surviving client updates — ``mean(U[mask])`` —
in a single pass over HBM.  The XLA baseline materializes the mask
broadcast (``U * mask[:, None]``) and reduces it in a separate pass from
the similarity statistics; this kernel folds the mask *and* the
1/|kept| normalization into a per-client weight vector that streams
through VMEM next to each (clients, chunk) tile of ``U``.

Composed with kernels/similarity.py (via ops.diversefl_step45), the
whole DiverseFL Step 4+5 is two HBM passes over U and one over G:

    pass 1: similarity kernel  reads U, G   -> (dot, ‖z‖², ‖g‖²)/client
    (VPU)   diversefl_mask     on (N,) scalars, no HBM traffic
    pass 2: this kernel        reads U      -> masked mean (D,)

versus the unfused baseline's five operand passes (three reductions
over U/G for the stats, then select + mean over U again).

Grid: (D/chunk, N/CLIENT_TILE).  The client axis is the trailing
(sequential) reduction dimension: the (1, chunk) output tile stays in
VMEM while successive (CLIENT_TILE, chunk) tiles of ``U`` and their
(CLIENT_TILE, 1) weights are folded into it, so the VMEM block is
bounded whatever N is (2 MB of fp32 ``U`` per buffer at the defaults).
Up to CLIENT_TILE clients the block is the whole client axis and the
result is the single ``sum(u * w, axis=0)`` of the untiled kernel.
Neither axis is padded in HBM: rows past N are zeroed in-kernel, and
columns past D only reach output columns that are sliced away.

``masked_agg_leaf_kernel`` folds one parameter leaf as the vmapped SGD
wrote it, viewed as ``(N, R, L)`` (kernels/similarity.py's leaf view):
grid (R/tr, L/lc, N), the client axis trailing and sequential, so the
(tr, lc) output block is the accumulator that stays in VMEM while each
client's block of the leaf is weighted into it.  The delta comes out in
the parameter's own layout, so no (N, D) row matrix is built and none is
unraveled.  Edge blocks only reach output elements that are dropped.

``masked_agg_fold_kernel`` is the streaming fold's own form: its
accumulator is 2-D for the whole sweep, in the layout ``fold_layout``
gives, with no reshape on either side of the call.  Where 128 divides D
that layout is the lane-dense (D/128, 128) view: its (8, 128) tiles hold
the elements in flat order, as the TPU tiles a flat ``f32[D]``
(``T(1024)``), so the round's flat delta is the same bytes.  The client
block is read where its producer wrote it, as (tn, tr * 128) tiles of
its (c, D) rows (tn up to ``CLIENT_TILE`` clients), and their weighted
sum, a (1, tr * 128) row, is added to a (tr, 128) tile of the view, of
about ``LEAF_BLOCK_BYTES`` (the leaf kernels' tiles): grid (R/tr, c/tn),
the client tiles trailing and sequential, the same association as
``masked_agg_update_kernel``'s.  (A wider view, (D/512, 512) say, makes
XLA copy the update into its tiles before every call.)  Elsewhere the
layout is the (1, D) row and the fold is ``masked_agg_update_kernel``'s
grid on it.  A (D,) accumulator would be relaid out into the kernel's
2-D block and back on every call: a full copy each way on the TPU, whose
tilings of ``f32[D]`` and ``f32[1, D]`` differ.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .similarity import LEAF_BLOCK_BYTES, leaf_tiles

DEFAULT_CHUNK = 16 * 1024
CLIENT_TILE = 32        # a whole sublane tile of f32, bf16 and int8 rows
FOLD_LANES = 128        # lanes of the streaming fold's accumulator view


def fold_layout(d: int):
    """(rows, lanes) of the streaming fold's accumulator for a flat
    length ``d``: the (d / 128, 128) view where 128 divides d, else the
    (1, d) row."""
    return (d // FOLD_LANES, FOLD_LANES) if d % FOLD_LANES == 0 else (1, d)


def fold_grid(n: int, d: int, chunk: int):
    """(rows per client tile, column chunk, grid) of a client-axis fold."""
    tn = min(CLIENT_TILE, n)
    chunk = min(chunk, d)
    return tn, chunk, (pl.cdiv(d, chunk), pl.cdiv(n, tn))


def row_mask(n: int, tn: int):
    """(tn, 1) bool: which rows of the current client tile exist, or None
    when every tile is full."""
    if n % tn == 0:
        return None
    k = pl.program_id(1)
    return k * tn + jax.lax.broadcasted_iota(jnp.int32, (tn, 1), 0) < n


def _update_kernel(w_ref, u_ref, acc_ref, out_ref, *, n: int, tn: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = acc_ref[...]

    w = w_ref[...]                            # (tn, 1) per-client weights
    x = u_ref[...].astype(jnp.float32) * w    # (tn, chunk)
    ok = row_mask(n, tn)
    if ok is not None:
        x = jnp.where(ok, x, 0.0)
    out_ref[...] += jnp.sum(x, axis=0, keepdims=True)


def masked_agg_update_kernel(u, w, acc, *, chunk: int = DEFAULT_CHUNK,
                             interpret: bool = False):
    """Streaming accumulate: ``acc + sum_i w_i * u_i`` over one client block.

    u: (n, D) update block; w: (n,) raw per-client weights (mask already
    folded in, NO 1/|kept| normalization — that happens once at
    ``finalize``); acc: (D,) the carried AggState partial sum.  One HBM
    pass over the block: each (CLIENT_TILE, chunk) tile of ``u`` streams
    through VMEM into the matching (1, chunk) tile of ``acc``.
    ``input_output_aliases`` donates the accumulator's buffer, so
    sweeping a federation chunk-by-chunk updates one (D,) state in place
    instead of allocating a fresh partial per block — the kernel twin of
    fl/streaming.py's ``update_block``.
    """
    return _row_fold(u, w, acc, chunk=chunk, interpret=interpret)[0]


def _row_fold(u, w, acc, *, chunk: int, interpret: bool):
    """(n, D) block, (n,) weights, accumulator of D elements -> (1, D)."""
    n, d = u.shape
    tn, chunk, grid = fold_grid(n, d, chunk)
    return pl.pallas_call(
        functools.partial(_update_kernel, n=n, tn=tn),
        grid=grid,
        in_specs=[pl.BlockSpec((tn, 1), lambda i, k: (k, 0)),
                  pl.BlockSpec((tn, chunk), lambda i, k: (k, i)),
                  pl.BlockSpec((1, chunk), lambda i, k: (0, i))],
        out_specs=pl.BlockSpec((1, chunk), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        input_output_aliases={2: 0},
        name="masked_agg",
        interpret=interpret,
    )(w.astype(jnp.float32).reshape(n, 1), u,
      acc.astype(jnp.float32).reshape(1, d))


def _view_kernel(w_ref, u_ref, acc_ref, out_ref, *, n: int, tn: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = acc_ref[...]

    x = u_ref[...].astype(jnp.float32) * w_ref[...]   # (tn, tr * 128)
    if tn > 1:
        ok = row_mask(n, tn)
        if ok is not None:
            x = jnp.where(ok, x, 0.0)
        x = jnp.sum(x, axis=0, keepdims=True)
    # (1, tr * 128) of the flat row -> its (tr, 128) tiles
    out_ref[...] += x.reshape(out_ref.shape)


def masked_agg_fold_kernel(u, w, acc, *, chunk: int = DEFAULT_CHUNK,
                           block_bytes: int = LEAF_BLOCK_BYTES,
                           interpret: bool = False):
    """The streaming fold in its accumulator's own layout:
    ``acc + sum_i w_i * u_i``.

    u: (c, D) client block; w: (c,) raw weights; acc: the (rows, lanes)
    f32 accumulator of ``fold_layout(D)`` -> the same shape.  The view's
    output has a buffer of its own, so the streaming sweep copies its
    loop-carried accumulator once a call: aliasing it in place
    (``input_output_aliases``) raised the round's peak HBM on the chip
    (PERF.md section 6).  The (D/128, 128) view takes (tn, tr * 128)
    tiles of the block, as many rows as ``masked_agg_update_kernel``'s
    client tiles, and adds their weighted sum to (tr, 128) tiles of the
    accumulator, grid (R/tr, c/tn) with the client tiles trailing and
    sequential; the (1, D) row takes ``masked_agg_update_kernel``'s grid
    with ``chunk``.  Edge blocks of the view are not masked: each output
    element reads only its own elements of ``u`` and ``acc``, so an
    overhanging block only reaches rows that are dropped."""
    c, d = u.shape
    if acc.shape != fold_layout(d):
        raise ValueError(f"accumulator {acc.shape} is not fold_layout({d})")
    r, l = acc.shape
    if l % FOLD_LANES:
        return _row_fold(u, w, acc, chunk=chunk, interpret=interpret)
    tn = min(CLIENT_TILE, c)
    # about block_bytes of the client block per grid step
    tr, _ = leaf_tiles(r, l, (u.dtype,), block_bytes // tn)
    return pl.pallas_call(
        functools.partial(_view_kernel, n=c, tn=tn),
        grid=(pl.cdiv(r, tr), pl.cdiv(c, tn)),
        in_specs=[pl.BlockSpec((tn, 1), lambda i, k: (k, 0)),
                  pl.BlockSpec((tn, tr * l), lambda i, k: (k, i)),
                  pl.BlockSpec((tr, l), lambda i, k: (i, 0))],
        out_specs=pl.BlockSpec((tr, l), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, l), jnp.float32),
        name="masked_agg",
        interpret=interpret,
    )(w.astype(jnp.float32).reshape(c, 1), u, acc)


def masked_agg_kernel(u, mask, *, chunk: int = DEFAULT_CHUNK,
                      interpret: bool = False):
    """u: (N, D); mask: (N,) bool/float -> (D,) fp32 masked mean (Eq. 6)."""
    m = mask.astype(jnp.float32)
    w = m / jnp.maximum(m.sum(), 1.0)
    return masked_agg_update_kernel(
        u, w, jnp.zeros((u.shape[1],), jnp.float32), chunk=chunk,
        interpret=interpret)


def _leaf_kernel(w_ref, u_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += u_ref[...].astype(jnp.float32) * w_ref[...]


def masked_agg_leaf_kernel(u, w, *, block_bytes: int = LEAF_BLOCK_BYTES,
                           interpret: bool = False):
    """u: (N, R, L) leaf view; w: (N,) per-client weights, the mask over
    max(|kept|, 1) that ``masked_agg_kernel`` folds -> (R, L) fp32
    ``sum_i w_i * u_i``."""
    n, r, l = u.shape
    tr, lc = leaf_tiles(r, l, (u.dtype,), block_bytes)
    return pl.pallas_call(
        _leaf_kernel,
        grid=(pl.cdiv(r, tr), pl.cdiv(l, lc), n),
        in_specs=[pl.BlockSpec((None, 1, 1), lambda i, j, k: (k, 0, 0)),
                  pl.BlockSpec((None, tr, lc), lambda i, j, k: (k, i, j))],
        out_specs=pl.BlockSpec((tr, lc), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, l), jnp.float32),
        name="masked_agg",
        interpret=interpret,
    )(w.astype(jnp.float32).reshape(n, 1, 1), u)
