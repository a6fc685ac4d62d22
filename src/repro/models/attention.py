"""Attention: GQA/MQA self-attention (full / sliding-window), multi-head
latent attention (DeepSeek-V2 MLA), cross-attention, blockwise
(flash-style) long-sequence path, and single-token decode with a KV cache
(ring buffer for sliding-window layers).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..sharding import model_shard_count, shard
from .config import ModelConfig, Yarn
from .layers import dense_init, rms_norm

NEG_INF = -2.0 ** 30


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------

def rope(x, positions, theta, freq=None):
    """x: (B, S, H, dh); positions: (B, S) int32; ``freq`` (dh/2,) the
    rotary frequencies (default ``theta ** (-2i/dh)``)."""
    dh = x.shape[-1]
    half = dh // 2
    if freq is None:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, y: Yarn):
    """YaRN frequencies of ``dim/2`` rotary pairs, as HF DeepSeek-V2's
    rotary embedding computes them: pairs below the correction range (the
    fast ones) keep their base frequency, pairs above it are divided by
    ``factor``, and those inside it blend the two linearly."""
    def corr(rot):
        return dim * math.log(y.original_max_position / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    base = theta ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return base / y.factor * ramp + base * (1.0 - ramp)


# ----------------------------------------------------------------------
# Params
# ----------------------------------------------------------------------

def make_attn_params(key, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    return {"wq": dense_init(ks[0], (d, h * hd), cfg.param_dtype),
            "wk": dense_init(ks[1], (d, kv * hd), cfg.param_dtype),
            "wv": dense_init(ks[2], (d, kv * hd), cfg.param_dtype),
            "wo": dense_init(ks[3], (h * hd, d), cfg.param_dtype, fan_in=h * hd)}


# ----------------------------------------------------------------------
# Core softmax attention on explicit q, k, v
# ----------------------------------------------------------------------

def _q_projection(x, w, path):
    """``x @ w``; on the fused path accumulated and kept in float32, so
    that q is rounded once, after rope and the score scale."""
    if path == "fused":
        return jnp.matmul(x, w, preferred_element_type=jnp.float32)
    return x @ w


def _sdpa(q, k, v, mask, softcap=None, scale=None):
    """q, k: (B,Sq|Sk,H|K,dh)  v: (B,Sk,K,dv)  mask: broadcastable
    (B,1,Sq,Sk) bool; scores scaled by ``scale`` (default dh**-0.5)."""
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    g = H // K
    qf = q.reshape(B, Sq, K, g, dh).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32))
    if scale is None:
        s = s / jnp.sqrt(dh).astype(jnp.float32)
    else:
        s = s * jnp.float32(scale)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask[:, :, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(v.dtype)


def _causal_mask(q_pos, k_pos, window):
    """q_pos: (B,Sq), k_pos: (B,Sk) -> (B,1,Sq,Sk) bool."""
    m = k_pos[:, None, None, :] <= q_pos[:, None, :, None]
    if window is not None:
        m &= k_pos[:, None, None, :] > (q_pos[:, None, :, None] - window)
    return m


def _blockwise(q, k, v, q_pos, k_pos, window, chunk, softcap=None,
               scale=None):
    """Memory-efficient attention: scan over q chunks (the XLA 'flash' path).

    For sliding-window layers each q chunk only loads a (chunk+window) slice
    of k/v, making compute O(S * window) instead of O(S^2).
    """
    B, S, H, dh = q.shape
    pad = (-S) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    n_chunks = q.shape[1] // chunk
    qc = q.reshape(B, n_chunks, chunk, H, dh).swapaxes(0, 1)
    pc = q_pos.reshape(B, n_chunks, chunk).swapaxes(0, 1)

    use_slice = window is not None and (chunk + window) < k.shape[1]
    span = chunk + window if use_slice else k.shape[1]

    def body(carry, inp):
        i, (qi, pi) = inp
        if use_slice:
            start = jnp.maximum(i * chunk - window, 0)
            start = jnp.minimum(start, k.shape[1] - span)
            ki = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            kpi = jax.lax.dynamic_slice_in_dim(k_pos, start, span, axis=1)
        else:
            ki, vi, kpi = k, v, k_pos
        mask = _causal_mask(pi, kpi, window) & (pi[:, None, :, None] >= 0)
        oi = _sdpa(qi, ki, vi, mask, softcap, scale)
        return carry, oi

    _, out = jax.lax.scan(body, None,
                          (jnp.arange(n_chunks), (qc, pc)))
    out = out.swapaxes(0, 1).reshape(B, n_chunks * chunk, H, v.shape[-1])
    return out[:, :S]


# ----------------------------------------------------------------------
# Which core computes causal self-attention over a whole sequence
# ----------------------------------------------------------------------

def attention_path(cfg: ModelConfig, seq: int, window=None,
                   flash: bool = False) -> str:
    """Which core computes causal self-attention over ``seq`` tokens with
    no cache, from what the call can observe, never the model: ``fused``
    (:func:`repro.kernels.ops.fused_causal_attention`) on a TPU with no
    model-sharded mesh (the kernel does not partition over heads) and no
    window narrower than the sequence; else ``flash`` (the forward-only
    kernel, where the mixer offers it and ``use_kernels`` asks for it past
    ``attn_direct_max``); else ``direct`` (:func:`_sdpa`, S x S scores in
    HBM) up to ``attn_direct_max`` and ``blockwise`` past it."""
    if (jax.default_backend() == "tpu" and model_shard_count() == 1
            and (window is None or window >= seq)):
        return "fused"
    if flash and cfg.use_kernels and seq > cfg.attn_direct_max:
        return "flash"
    return "direct" if seq <= cfg.attn_direct_max else "blockwise"


def _path_event(path: str, q, k, v) -> None:
    """One ``attention_path`` event on the flight recorder per trace."""
    from ..fl import telemetry
    telemetry.event("attention_path", path=path, heads=q.shape[2],
                    kv_heads=k.shape[2], seq=q.shape[1], dqk=q.shape[-1],
                    dv=v.shape[-1])


# ----------------------------------------------------------------------
# Self attention block (training / prefill / decode)
# ----------------------------------------------------------------------

def self_attention(x, p, cfg: ModelConfig, positions, window=None,
                   cache=None, cache_index=None):
    """Returns (out, new_cache).  cache: {"k": (B,C,K,dh), "v": ...} or None.

    - cache is None            -> training/forward; new_cache is (k, v) computed.
    - cache given, x is 1 tok  -> decode: update ring/linear cache at cache_index.
    """
    B, S, d = x.shape
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    path = None if cache is not None else attention_path(cfg, S, window,
                                                         flash=True)
    q = _q_projection(x, p["wq"], path).reshape(B, S, H, dh)
    k = (x @ p["wk"]).reshape(B, S, K, dh)
    v = (x @ p["wv"]).reshape(B, S, K, dh)
    q = shard(q, P(None, None, "model", None))
    k = rope(k, positions, cfg.rope_theta)
    q = rope(q, positions, cfg.rope_theta)

    if cache is None:
        _path_event(path, q, k, v)
        if path == "fused":
            from ..kernels import ops as kops
            o = kops.fused_causal_attention(q, k, v, dh ** -0.5,
                                            cfg.logit_softcap)
        elif path == "flash":
            from ..kernels import ops as kops
            o = kops.flash_attention(q, k, v, window=window,
                                     softcap=cfg.logit_softcap)
        elif path == "direct":
            mask = _causal_mask(positions, positions, window)
            o = _sdpa(q, k, v, mask, cfg.logit_softcap)
        else:
            o = _blockwise(q, k, v, positions, positions, window,
                           cfg.attn_chunk, cfg.logit_softcap)
        new_cache = {"k": k, "v": v}
    else:
        C = cache["k"].shape[1]
        slot = cache_index % C if window is not None else cache_index
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        # key positions: for ring buffers reconstruct absolute positions.
        idx = jnp.arange(C, dtype=jnp.int32)[None, :]
        if window is not None:
            # entry at idx holds the largest p <= cache_index with p % C == idx
            k_pos = cache_index - ((cache_index - idx) % C)
            k_pos = jnp.broadcast_to(k_pos, (B, C))
        else:
            k_pos = jnp.broadcast_to(idx, (B, C))
        valid = (k_pos <= positions[:, :1]) & (k_pos >= 0)
        mask = _causal_mask(positions, k_pos, window) & valid[:, None, None, :]
        o = _sdpa(q, ck, cv, mask, cfg.logit_softcap)
        new_cache = {"k": ck, "v": cv}

    out = o.reshape(B, S, H * dh) @ p["wo"]
    return out, new_cache


# ----------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1)
# ----------------------------------------------------------------------

def make_mla_params(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"wq": dense_init(ks[0], (d, h * (dn + dr)), cfg.param_dtype),
            "wkv_a": dense_init(ks[1], (d, r + dr), cfg.param_dtype),
            "kv_norm": {"scale": jnp.zeros((r,), cfg.param_dtype)},
            "wkv_b": dense_init(ks[2], (r, h * (dn + dv)), cfg.param_dtype),
            "wo": dense_init(ks[3], (h * dv, d), cfg.param_dtype,
                             fan_in=h * dv)}


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk head dim)**-0.5, times mscale**2 under YaRN."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        m = yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim)
        scale *= m * m
    return scale


def mla_attention(x, p, cfg: ModelConfig, positions):
    """Causal MLA over the whole sequence (no q LoRA):
    ``q = x Wq``; ``[c | k_pe] = x Wkv_a``, ``c`` RMS-normed; ``[k_nope |
    v] = c Wkv_b``; ``k = [k_nope, rope(k_pe)]`` with ``k_pe`` shared by
    all heads; ``q = [q_nope, rope(q_pe)]``; ``o = softmax(q k^T s) v``
    then ``Wo``.  Rotary pairs are split in halves, where HF stores
    them interleaved (a relabelling of weight columns)."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla"):
        path = attention_path(cfg, S)
        q = _q_projection(x, p["wq"], path).reshape(B, S, H, dn + dr)
        q = shard(q, P(None, None, "model", None))
        ckv = x @ p["wkv_a"]
        c = rms_norm(ckv[..., :r], p["kv_norm"]["scale"])
        kv = (c @ p["wkv_b"]).reshape(B, S, H, dn + dv)
        freq = None if cfg.yarn is None else yarn_freqs(dr, cfg.rope_theta,
                                                        cfg.yarn)
        q_pe = rope(q[..., dn:], positions, cfg.rope_theta, freq)
        k_pe = rope(ckv[:, :, None, r:], positions, cfg.rope_theta, freq)
        if cfg.yarn is not None:
            cs = yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale) / yarn_mscale(
                cfg.yarn.factor, cfg.yarn.mscale_all_dim)
            if cs != 1.0:
                q_pe, k_pe = q_pe * cs, k_pe * cs
        q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, H, dr))], axis=-1)
        v = kv[..., dn:]
        scale = mla_softmax_scale(cfg)
        _path_event(path, q, k, v)
        if path == "fused":
            from ..kernels import ops as kops
            o = kops.fused_causal_attention(q, k, v, scale, cfg.logit_softcap)
        elif path == "direct":
            o = _sdpa(q, k, v, _causal_mask(positions, positions, None),
                      cfg.logit_softcap, scale)
        else:
            o = _blockwise(q, k, v, positions, positions, None,
                           cfg.attn_chunk, cfg.logit_softcap, scale)
        return o.reshape(B, S, H * dv) @ p["wo"]


# ----------------------------------------------------------------------
# Cross attention (VLM image layers, Whisper enc-dec)
# ----------------------------------------------------------------------

def cross_attention(x, p, cfg: ModelConfig, cross_kv):
    """cross_kv: {"k": (B,L,K,dh), "v": (B,L,K,dh)} (precomputed from the
    frontend embeddings or encoder output; static during decode)."""
    B, S, d = x.shape
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, dh)
    q = shard(q, P(None, None, "model", None))
    L = cross_kv["k"].shape[1]
    mask = jnp.ones((B, 1, S, L), bool)
    o = _sdpa(q, cross_kv["k"], cross_kv["v"], mask, cfg.logit_softcap)
    return o.reshape(B, S, H * dh) @ p["wo"]


def make_cross_kv(emb, p, cfg: ModelConfig):
    """Project frontend/encoder embeddings once into cross K/V."""
    B, L, _ = emb.shape
    K, dh = cfg.n_kv_heads, cfg.head_dim
    k = (emb @ p["wk"]).reshape(B, L, K, dh)
    v = (emb @ p["wv"]).reshape(B, L, K, dh)
    return {"k": k, "v": v}
