"""Chunked selective-scan (Mamba-1 SSM) kernel.

TPU adaptation of the CUDA selective-scan: instead of a warp-level scan,
we exploit the *sequential* trailing grid dimension — the SSM state h
(d_state, d_inner-block) persists in VMEM scratch across sequence chunks,
and each chunk runs an in-register recurrence.  The channel dim is tiled
so each (chunk, d_state, d_block) working set fits VMEM.

Inside the kernel d_inner is the lane dimension and d_state the sublane
dimension, and every per-timestep access indexes a leading (untiled)
block dimension, so no load or store is unaligned.  The public entry
point keeps the model's (B, S, d_inner, d_state) layout; the transposes
to the kernel layout run in XLA around the call.

Grid: (B, d_inner/bd, S/bs) — trailing = sequence (carried).
    h_t = dA_t * h_{t-1} + dBx_t ;   y_t = <h_t, C_t> + handled outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(da_ref, dbx_ref, c_ref, y_ref, h_ref, *, bs: int):
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        h = (da_ref[0, t].astype(jnp.float32) * h
             + dbx_ref[0, t].astype(jnp.float32))              # (n, bd)
        c = c_ref[0, t].astype(jnp.float32)                    # (n, 1)
        y_ref[0, t] = jnp.sum(h * c, axis=0, keepdims=True)    # (1, bd)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bs, step, h_ref[...])


def mamba_scan_kernel(da, dbx, c, *, bs: int = 64, bd: int = 256,
                      interpret: bool = False):
    """da, dbx: (B, S, di, n); c: (B, S, n) -> y: (B, S, di) fp32."""
    B, S, di, n = da.shape
    bs = min(bs, S)
    bd = min(bd, di)
    assert S % bs == 0 and di % bd == 0, (S, bs, di, bd)
    grid = (B, di // bd, S // bs)
    y = pl.pallas_call(
        functools.partial(_kernel, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, n, bd), lambda b, j, s: (b, s, 0, j)),
            pl.BlockSpec((1, bs, n, bd), lambda b, j, s: (b, s, 0, j)),
            pl.BlockSpec((1, bs, n, 1), lambda b, j, s: (b, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, 1, bd), lambda b, j, s: (b, s, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, S, 1, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)],
        name="mamba_scan",
        interpret=interpret,
    )(da.swapaxes(2, 3), dbx.swapaxes(2, 3), c[..., None])
    return y[:, :, 0, :]
