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
from jax.experimental.pallas.ops.tpu import splash_attention as _splash_lib

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
    with jax.named_scope("step4_filter"):
        return _sim.similarity_kernel(z, g, chunk=chunk,
                                      interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def masked_aggregate(u, mask, chunk: int = _ma.DEFAULT_CHUNK):
    """(N, D), (N,) -> (D,) masked mean (Eq. 6) in one HBM pass over u."""
    with jax.named_scope("step5_fold"):
        return _ma.masked_agg_kernel(u, mask, chunk=chunk,
                                     interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def masked_agg_update(u, w, acc, chunk: int = _ma.DEFAULT_CHUNK):
    """Streaming accumulate: (n, D) block + (n,) weights + carried
    partial -> ``acc + sum_i w_i * u_i`` in one HBM pass over u, in
    acc's shape: a (D,) vector, or the 2-D accumulator of
    ``masked_agg.fold_layout(D)``, which the kernel reads and writes
    with no relayout.  The Pallas leg of the streaming AggState
    ``update_block`` — the 1/|kept| normalization happens once at
    ``finalize``, not here."""
    with jax.named_scope("step5_fold"):
        if acc.ndim == 2:
            return _ma.masked_agg_fold_kernel(u, w, acc, chunk=chunk,
                                              interpret=_interpret())
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
    with jax.named_scope("step5_fold"):
        return _dq.dequant_fold_update_kernel(q, scale, w, acc,
                                              qblock=qblock, chunk=chunk,
                                              interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("cfg", "chunk"))
def diversefl_step45(u, g, cfg, chunk: int = _sim.DEFAULT_CHUNK):
    """Fused DiverseFL Step 4+5: (N, D) updates + guides -> (delta (D,),
    keep mask (N,), (dot, ||z||^2, ||g||^2)).

    Two HBM passes over u (similarity stats, masked mean) and one over g
    — the criterion itself runs on (N,) scalars in registers.  ``cfg`` is
    a (hashable) DiverseFLConfig."""
    with jax.named_scope("step4_filter"):
        stats = _sim.similarity_kernel(u, g, chunk=chunk,
                                       interpret=_interpret())
        dot, zz, gg = stats[:, 0], stats[:, 1], stats[:, 2]
        mask = diversefl_mask(dot, zz, gg, cfg)
    with jax.named_scope("step5_fold"):
        delta = _ma.masked_agg_kernel(u, mask, chunk=chunk,
                                      interpret=_interpret())
    return delta, mask, (dot, zz, gg)


# ----------------------------------------------------------------------
# Step 4+5 on the stacked per-client pytrees the vmapped SGD wrote: each
# leaf (N, *s) is read in place as its (N, R, L) view (a bitcast wherever
# s[-2] tiles the sublanes), so no (N, D) row matrix is built.  Leaves
# with at most one axis per client already are rows: they are joined into
# one small (N, D_rows) block for the (n, D) kernels.  Statistics and
# fold are float32, every leaf and every client included.
# ----------------------------------------------------------------------

def _leaf_view(u):
    """(N, *s) -> (N, prod(s[:-1]), s[-1])."""
    return u.reshape(u.shape[0], -1, u.shape[-1])


def _row_leaves(leaves):
    """Indices of the leaves that are rows already: at most one axis
    after the client axis."""
    return [i for i, u in enumerate(leaves) if u.ndim <= 2]


def _join_rows(leaves, rows):
    n = leaves[0].shape[0]
    with jax.named_scope("flatten"):
        return jnp.concatenate(
            [leaves[i].reshape(n, -1).astype(jnp.float32) for i in rows],
            axis=1)


def _leaf_stats(z_tree, g_tree):
    zs, gs = jax.tree.leaves(z_tree), jax.tree.leaves(g_tree)
    rows = _row_leaves(zs)
    parts = []
    if rows:
        parts.append(_sim.similarity_kernel(
            _join_rows(zs, rows), _join_rows(gs, rows),
            interpret=_interpret()))
    parts += [_sim.similarity_leaf_kernel(_leaf_view(z), _leaf_view(g),
                                          interpret=_interpret())
              for z, g in zip(zs, gs) if z.ndim > 2]
    s = functools.reduce(jnp.add, parts)
    return s[:, 0], s[:, 1], s[:, 2]


def _leaf_fold(u_tree, mask):
    leaves, treedef = jax.tree.flatten(u_tree)
    m = mask.astype(jnp.float32)
    w = m / jnp.maximum(m.sum(), 1.0)
    out = [None] * len(leaves)
    rows = _row_leaves(leaves)
    if rows:
        u = _join_rows(leaves, rows)
        flat = _ma.masked_agg_update_kernel(
            u, w, jnp.zeros((u.shape[1],), jnp.float32),
            interpret=_interpret())
        off = 0
        for i in rows:
            size = leaves[i][0].size
            out[i] = flat[off:off + size].reshape(leaves[i].shape[1:])
            off += size
    for i, u in enumerate(leaves):
        if u.ndim > 2:
            out[i] = _ma.masked_agg_leaf_kernel(
                _leaf_view(u), w, interpret=_interpret()
            ).reshape(u.shape[1:])
    return jax.tree.unflatten(treedef, out)


@jax.jit
def similarity_stats_leaves(z_tree, g_tree):
    """Stacked update and guide pytrees -> per-client (dot, ||z||^2,
    ||g||^2), each (N,) fp32, summed over the leaves."""
    with jax.named_scope("step4_filter"):
        return _leaf_stats(z_tree, g_tree)


@jax.jit
def masked_aggregate_leaves(u_tree, mask):
    """Stacked update pytree + (N,) mask -> the masked mean (Eq. 6) as a
    pytree of fp32 leaves in the parameters' shapes."""
    with jax.named_scope("step5_fold"):
        return _leaf_fold(u_tree, mask)


@functools.partial(jax.jit, static_argnames=("cfg",))
def diversefl_step45_leaves(u_tree, g_tree, cfg):
    """:func:`diversefl_step45` on the stacked pytrees: (delta pytree,
    keep mask (N,), (dot, ||z||^2, ||g||^2)), one pass over each update
    and guide leaf for the statistics and one over each update leaf for
    the fold."""
    with jax.named_scope("step4_filter"):
        dot, zz, gg = _leaf_stats(u_tree, g_tree)
        mask = diversefl_mask(dot, zz, gg, cfg)
    with jax.named_scope("step5_fold"):
        delta = _leaf_fold(u_tree, mask)
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


@functools.lru_cache(maxsize=32)
def _splash(heads: int, seq: int, softcap, interpret: bool):
    # the largest square tile that divides seq: fewer grid steps, each
    # of more MXU work, against finer skipping of the masked tiles
    b = next(b for b in (512, 256, 128) if seq % b == 0)
    sizes = _splash_lib.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
        block_kv_dkv=b, block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    mask = _splash_lib.MultiHeadMask(
        [_splash_lib.CausalMask((seq, seq))] * heads)
    # the kernel holds its block tables as arrays: make them concrete even
    # when the first call comes inside a trace, since they are cached
    with jax.ensure_compile_time_eval():
        return _splash_lib.make_splash_mha_single_device(
            mask, block_sizes=sizes, attn_logits_soft_cap=softcap,
            interpret=interpret)


def fused_causal_attention(q, k, v, scale: float, softcap=None):
    """Causal self-attention through JAX's splash kernel, forward and
    backward (its own ``custom_vjp``): scores and probabilities live in
    VMEM tiles only, and masked tiles are skipped.

    Model layout: q (B,S,H,dqk) in any float dtype, k (B,S,K,dqk), v
    (B,S,K,dv) with H a multiple of K -> (B,S,H,dv) in v's dtype.  q is
    scaled by ``scale`` in float32 and then cast once to k's dtype;
    ``softcap`` caps the scaled scores as ``_sdpa`` does.  The batch is
    folded into the heads: q head ``b H + h`` reads kv head ``b K +
    h // (H / K)``, the kernel's own grouping.  A sequence is padded at
    its end to a multiple of 128 and the output cut back: under the
    causal mask no real query sees a padded key, so that is exact."""
    B, S, H = q.shape[:3]
    dv = v.shape[-1]
    Sp = -(-S // 128) * 128
    q = (q.astype(jnp.float32) * scale).astype(k.dtype)

    def heads_first(x):
        x = jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3).reshape(-1, Sp, x.shape[-1])
    o = _splash(B * H, Sp, softcap, _interpret())(
        heads_first(q), heads_first(k), heads_first(v))
    o = o.reshape(B, H, Sp, dv)[:, :, :S].transpose(0, 2, 1, 3)
    return o.astype(v.dtype)


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
