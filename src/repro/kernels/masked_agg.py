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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_CHUNK = 16 * 1024
CLIENT_TILE = 32        # a whole sublane tile of f32, bf16 and int8 rows


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
    n, d = u.shape
    tn, chunk, grid = fold_grid(n, d, chunk)
    out = pl.pallas_call(
        functools.partial(_update_kernel, n=n, tn=tn),
        grid=grid,
        in_specs=[pl.BlockSpec((tn, 1), lambda i, k: (k, 0)),
                  pl.BlockSpec((tn, chunk), lambda i, k: (k, i)),
                  pl.BlockSpec((1, chunk), lambda i, k: (0, i))],
        out_specs=pl.BlockSpec((1, chunk), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(w.astype(jnp.float32).reshape(n, 1), u,
      acc.astype(jnp.float32).reshape(1, d))
    return out[0]


def masked_agg_kernel(u, mask, *, chunk: int = DEFAULT_CHUNK,
                      interpret: bool = False):
    """u: (N, D); mask: (N,) bool/float -> (D,) fp32 masked mean (Eq. 6)."""
    m = mask.astype(jnp.float32)
    w = m / jnp.maximum(m.sum(), 1.0)
    return masked_agg_update_kernel(
        u, w, jnp.zeros((u.shape[1],), jnp.float32), chunk=chunk,
        interpret=interpret)
