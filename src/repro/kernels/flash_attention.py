"""Flash attention (causal, GQA, optional sliding window) for TPU.

Online-softmax tiling: grid (B, H, Sq/bq, Sk/bk) with the key axis as the
trailing (sequential) TPU grid dimension; running (m, l, acc) live in
VMEM scratch across key iterations.  Fully-masked key blocks — beyond the
causal frontier or outside the sliding window — are skipped with
``pl.when`` so compute is O(S·window) for SWA layers.

Block sizes default to MXU-aligned 128x128 q/k tiles with the full head
dim resident (head_dim <= 256 for all assigned archs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bq: int, bk: int, scale: float, window, softcap, n_k: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = kj * bk
    # causal: need k_start <= q_end;  window: need k_end > q_start - window
    run = (k_start <= q_start + bq - 1)
    if window is not None:
        run &= (k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = (q @ k.T) * scale                        # (bq, bk)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                          # (bq, 1)
        m_cur = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_cur

    @pl.when(kj == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, window=None, softcap=None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False):
    """q: (B, H, Sq, dh), k/v: (B, K, Sk, dh) — causal GQA flash attention.

    Returns (B, H, Sq, dh) in q.dtype."""
    B, H, Sq, dh = q.shape
    K, Sk = k.shape[1], k.shape[2]
    g = H // K
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded keys sit at positions >= Sk and are masked by causality
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q, n_k = q.shape[2] // bq, k.shape[2] // bk
    grid = (B, H, n_q, n_k)
    kern = functools.partial(
        _kernel, bq=bq, bk=bk, scale=1.0 / (dh ** 0.5),
        window=window, softcap=softcap, n_k=n_k)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, q.shape[2], dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq, :]
