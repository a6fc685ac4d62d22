"""Jit'd public wrappers for the Pallas kernels.

On the TPU the kernels compile through Mosaic.  Any other backend is the
CPU test path: the kernels execute in Pallas interpret mode — same
kernel body, evaluated as XLA — so every call site runs in the tests
too.  ``chip_smoke.py`` checks on the chip that the interpreter is not
taken.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dequant_fold as _dq
from . import flash_attention as _fa
from . import mamba_scan as _ms
from . import masked_agg as _ma
from . import robust_agg as _ra
from . import similarity as _sim
from .. import models
from ..core.diversefl import diversefl_mask


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("chunk",))
def similarity_stats(z, g, chunk: int = _sim.DEFAULT_CHUNK):
    """(N, D) x (N, D) -> (N, 3) fp32 [dot, ||z||^2, ||g||^2]."""
    return _sim.similarity_kernel(z, g, chunk=chunk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def masked_aggregate(u, mask, chunk: int = _ma.DEFAULT_CHUNK):
    """(N, D), (N,) -> (D,) masked mean (Eq. 6) in one HBM pass over u."""
    return _ma.masked_agg_kernel(u, mask, chunk=chunk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def masked_agg_update(u, w, acc, chunk: int = _ma.DEFAULT_CHUNK):
    """Streaming accumulate: (n, D) block + (n,) weights + (D,) carried
    partial -> (D,) ``acc + sum_i w_i * u_i`` in one HBM pass over u.
    The Pallas leg of the streaming AggState ``update_block`` — the
    1/|kept| normalization happens once at ``finalize``, not here."""
    return _ma.masked_agg_update_kernel(u, w, acc, chunk=chunk,
                                        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("qblock", "chunk"))
def dequant_fold_update(q, scale, w, acc, qblock: int,
                        chunk: int = _ma.DEFAULT_CHUNK):
    """Streaming int8 accumulate: (n, D) int8 payload + (n, ceil(D/qblock))
    f32 per-block scales + (n,) weights + (D,) carried partial ->
    ``acc + sum_i w_i * dequant(q_i)`` with the dequantization fused into
    the one HBM pass over q (1 byte/element instead of 4).  The int8 leg
    of the streaming AggState ``update_block`` (fl/streaming.py); dense-
    payload codecs keep using :func:`masked_agg_update`, whose in-kernel
    f32 cast is their whole dequantization."""
    return _dq.dequant_fold_update_kernel(q, scale, w, acc, qblock=qblock,
                                          chunk=chunk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("cfg", "chunk"))
def diversefl_step45(u, g, cfg, chunk: int = _sim.DEFAULT_CHUNK):
    """Fused DiverseFL Step 4+5: (N, D) updates + guides -> (delta (D,),
    keep mask (N,), (dot, ||z||^2, ||g||^2)).

    Two HBM passes over u (similarity stats, masked mean) and one over g
    — the criterion itself runs on (N,) scalars in registers.  ``cfg`` is
    a (hashable) DiverseFLConfig."""
    stats = _sim.similarity_kernel(u, g, chunk=chunk, interpret=_interpret())
    dot, zz, gg = stats[:, 0], stats[:, 1], stats[:, 2]
    mask = diversefl_mask(dot, zz, gg, cfg)
    delta = _ma.masked_agg_kernel(u, mask, chunk=chunk, interpret=_interpret())
    return delta, mask, (dot, zz, gg)


@functools.partial(jax.jit, static_argnames=("f", "chunk"))
def robust_aggregate(u, f: int = 0, chunk: int = _ra.DEFAULT_CHUNK):
    """(N, D) -> (median (D,), trimmed_mean (D,))."""
    return _ra.robust_agg_kernel(u, f, chunk=chunk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "softcap", "bq", "bk"))
def flash_attention_bhsd(q, k, v, window=None, softcap=None,
                         bq: int = 128, bk: int = 128):
    """q: (B,H,Sq,dh), k/v: (B,K,Sk,dh) -> (B,H,Sq,dh)."""
    return _fa.flash_attention_kernel(q, k, v, window=window, softcap=softcap,
                                      bq=bq, bk=bk, interpret=_interpret())


def flash_attention(q, k, v, window=None, softcap=None):
    """Model-layout adapter: q (B,S,H,dh), k/v (B,S,K,dh) -> (B,S,H,dh)."""
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    o = flash_attention_bhsd(qt, kt, vt, window=window, softcap=softcap)
    return o.swapaxes(1, 2)


@functools.partial(jax.jit, static_argnames=("bs", "bd"))
def mamba_scan_raw(da, dbx, c, bs: int = 64, bd: int = 256):
    return _ms.mamba_scan_kernel(da, dbx, c, bs=bs, bd=bd,
                                 interpret=_interpret())


def mamba_scan(xc, p, cfg):
    """Model adapter: post-conv activations -> scan output (B,S,di) fp32."""
    from ..models.mamba import _ssm_coeffs
    da, dbx, cm = _ssm_coeffs(xc, p, cfg)
    S, di = da.shape[1], da.shape[2]
    bs = 64 if S % 64 == 0 else S
    bd = 256 if di % 256 == 0 else di
    return mamba_scan_raw(da, dbx, cm, bs=bs, bd=bd)
