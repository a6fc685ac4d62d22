"""Coordinate-wise robust aggregation kernel (Median / trimmed-mean).

The server-side baselines (Median [9], Bulyan's trimmed mean [12]) reduce
a stacked update matrix U (N clients, D) per coordinate.  This kernel
tiles D into VMEM blocks and sorts along the (small, compile-time) client
axis with an odd-even transposition network — pure min/max vector ops on
whole (1, chunk) rows, MXU-free and scatter-free — emitting both the
median and the mean-of-(N-2f)-closest-to-median in one pass.

Grid: (D/chunk,).  Block: (N, chunk) in VMEM: for N<=64, chunk=2048 fp32
this is 512 KB — well inside the ~16 MB VMEM budget.  D is not padded in
HBM: columns past D only reach output columns that are sliced away.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_CHUNK = 2048


def _oddeven_sort(rows):
    """Sort a list of (1, chunk) rows elementwise with an odd-even
    transposition network; returns the sorted list."""
    rows = list(rows)
    n = len(rows)
    for it in range(n):
        for i in range(it % 2, n - 1, 2):
            a, b = rows[i], rows[i + 1]
            rows[i], rows[i + 1] = jnp.minimum(a, b), jnp.maximum(a, b)
    return rows


def _kernel(u_ref, med_ref, trim_ref, *, f: int):
    u = u_ref[...].astype(jnp.float32)
    n = u.shape[0]
    s = _oddeven_sort([u[i:i + 1] for i in range(n)])
    if n % 2:
        med = s[n // 2]
    else:
        med = 0.5 * (s[n // 2 - 1] + s[n // 2])
    med_ref[...] = med
    # Bulyan-style: mean of the N-2f values closest to the median.
    keep_n = max(n - 2 * f, 1)
    ds = _oddeven_sort([jnp.abs(r - med) for r in s])
    thresh = ds[keep_n - 1]          # keep distances <= this
    w = (jnp.abs(u - med) <= thresh).astype(jnp.float32)
    # ties can admit >keep_n entries; normalize by actual count
    trim_ref[...] = (jnp.sum(u * w, axis=0, keepdims=True)
                     / jnp.maximum(jnp.sum(w, axis=0, keepdims=True), 1.0))


def robust_agg_kernel(u, f: int = 0, *, chunk: int = DEFAULT_CHUNK,
                      interpret: bool = False):
    """u: (N, D) -> (median (D,), trimmed (D,)) fp32."""
    n, d = u.shape
    chunk = min(chunk, d)
    med, trim = pl.pallas_call(
        functools.partial(_kernel, f=f),
        grid=(pl.cdiv(d, chunk),),
        in_specs=[pl.BlockSpec((n, chunk), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, chunk), lambda i: (0, i)),
                   pl.BlockSpec((1, chunk), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((1, d), jnp.float32),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        interpret=interpret,
    )(u)
    return med[0], trim[0]
