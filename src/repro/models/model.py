"""Model assembly: embedding, scanned block groups, loss, prefill & decode.

Params layout::

  {"embed": (V, D),
   "prelude": (first_k_dense blocks, unstacked),
   "groups": tuple(len(layout)) of block trees, leaves lead with n_groups,
   "final_norm": {...},
   "encoder": {"groups": ..., "final_norm": ...}        # enc-dec only
  }

Layer stacking uses ``jax.lax.scan`` over groups so compile time and HLO
size are independent of depth (61-layer / 100-layer configs lower in
seconds).  Activation checkpointing (``cfg.remat``) wraps the group body
and each leading dense block.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..sharding import shard
from .attention import (cross_attention, make_attn_params, make_cross_kv,
                        make_mla_params, mla_attention, self_attention)
from .config import ModelConfig
from .layers import (apply_mlp, apply_norm, dense_init, make_mlp_params,
                     make_norm_params)
from .mamba import init_mamba_cache, make_mamba_params, mamba_mixer
from .moe import apply_expert_share, apply_moe, make_moe_params


# ----------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------

def _make_block_params(key, cfg: ModelConfig, entry, force_mlp=False):
    mixer, ffn = entry
    ks = jax.random.split(key, 6)
    p = {"ln1": make_norm_params(ks[0], cfg)}
    if mixer in ("attn", "swa"):
        p["attn"] = make_attn_params(ks[1], cfg)
    elif mixer == "mla":
        p["mla"] = make_mla_params(ks[1], cfg)
    elif mixer == "mamba":
        p["mamba"] = make_mamba_params(ks[1], cfg)
    elif mixer == "xattn":
        p["xattn"] = make_attn_params(ks[1], cfg, cross=True)
        p["xgate"] = jnp.zeros((), jnp.float32)
    elif mixer == "attn_x":
        p["attn"] = make_attn_params(ks[1], cfg)
        p["ln_x"] = make_norm_params(ks[2], cfg)
        p["xattn"] = make_attn_params(ks[3], cfg, cross=True)
    else:
        raise ValueError(mixer)
    if force_mlp:
        ffn = "mlp"
    if ffn == "mlp":
        p["ln2"] = make_norm_params(ks[4], cfg)
        p["mlp"] = make_mlp_params(ks[5], cfg)
    elif ffn == "moe":
        p["ln2"] = make_norm_params(ks[4], cfg)
        p["moe"] = make_moe_params(ks[5], cfg)
    elif ffn != "none":
        raise ValueError(ffn)
    return p


def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    params = {"embed": dense_init(ks[0], (cfg.padded_vocab, cfg.d_model),
                                  cfg.param_dtype, fan_in=cfg.d_model),
              "final_norm": make_norm_params(ks[1], cfg)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[5], (cfg.d_model, cfg.padded_vocab),
                                       cfg.param_dtype)

    if cfg.first_k_dense:
        pk = jax.random.split(ks[2], cfg.first_k_dense)
        params["prelude"] = [
            _make_block_params(pk[i], cfg, cfg.prelude_entry)
            for i in range(cfg.first_k_dense)]

    gk = jax.random.split(ks[3], cfg.n_groups)

    def one_group(k):
        eks = jax.random.split(k, len(cfg.layout))
        return tuple(_make_block_params(eks[i], cfg, e)
                     for i, e in enumerate(cfg.layout))

    params["groups"] = jax.vmap(one_group)(gk)

    if cfg.is_enc_dec:
        ek = jax.random.split(ks[4], cfg.n_enc_layers + 1)

        def one_enc(k):
            return (_make_block_params(k, cfg, ("attn", "mlp")),)
        params["encoder"] = {
            "groups": jax.vmap(one_enc)(ek[:-1]),
            "final_norm": make_norm_params(ek[-1], cfg)}
    return params


# ----------------------------------------------------------------------
# Blocks
# ----------------------------------------------------------------------

def _zero_aux(cfg: ModelConfig):
    """What a block adds up besides its output: the balance loss, and
    under a dropless expert layer the slots each held expert served."""
    loss = jnp.zeros((), jnp.float32)
    if cfg.dropless:
        return loss, jnp.zeros((cfg.n_held_experts,), jnp.int32)
    return loss


def _aux_loss(aux):
    return aux[0] if isinstance(aux, tuple) else aux


def _add_aux(a, b):
    return jax.tree.map(jnp.add, a, b)


def _run_block(x, bp, entry, cfg: ModelConfig, positions, cross_emb,
               cache, cache_index):
    mixer, ffn = entry
    aux = _zero_aux(cfg)
    h = apply_norm(x, bp["ln1"], cfg)
    new_cache = None
    if mixer == "mla":
        if cache is not None:
            raise NotImplementedError("mla has no decode cache")
        o = mla_attention(h, bp["mla"], cfg, positions)
    elif mixer in ("attn", "swa"):
        window = cfg.window if mixer == "swa" else None
        o, kv = self_attention(h, bp["attn"], cfg, positions, window,
                               cache=cache, cache_index=cache_index)
        new_cache = kv
    elif mixer == "mamba":
        o, new_cache = mamba_mixer(h, bp["mamba"], cfg, cache, cache_index)
    elif mixer == "xattn":
        kv = cache["cross"] if cache is not None else \
            make_cross_kv(cross_emb, bp["xattn"], cfg)
        o = cross_attention(h, bp["xattn"], cfg, kv)
        o = o * jnp.tanh(bp["xgate"]).astype(o.dtype)
        new_cache = {"cross": kv}
    elif mixer == "attn_x":
        o1, kv_self = self_attention(
            h, bp["attn"], cfg, positions, None,
            cache=None if cache is None else cache["self"],
            cache_index=cache_index)
        x = x + o1
        h2 = apply_norm(x, bp["ln_x"], cfg)
        kv = cache["cross"] if cache is not None else \
            make_cross_kv(cross_emb, bp["xattn"], cfg)
        o = cross_attention(h2, bp["xattn"], cfg, kv)
        new_cache = {"self": kv_self, "cross": kv}
    else:
        raise ValueError(mixer)
    x = x + o

    if ffn in ("mlp", "moe") or (ffn == "none" and "mlp" in bp):
        h = apply_norm(x, bp["ln2"], cfg)
        if "moe" in bp and cfg.dropless:
            f, loss, load = apply_expert_share(h, bp["moe"], cfg)
            aux = (loss, load)
        elif "moe" in bp:
            f, aux = apply_moe(h, bp["moe"], cfg)
        else:
            f = apply_mlp(h, bp["mlp"], cfg)
        x = x + f
    return x, new_cache, aux


def _scan_groups(x, groups, cfg: ModelConfig, positions, cross_emb,
                 cache, cache_index, decode, collect_cache=False):
    def gfn(carry, xs):
        xc, aux = carry
        gp, gc = xs
        new_gc = []
        for li, entry in enumerate(cfg.layout):
            c_in = None if gc is None else gc[li]
            xc, nc, a = _run_block(xc, gp[li], entry, cfg, positions,
                                   cross_emb, c_in, cache_index)
            new_gc.append(nc)
            aux = _add_aux(aux, a)
        ys = tuple(new_gc) if (decode or collect_cache) else None
        return (xc, aux), ys

    body = gfn
    if cfg.remat and not decode:
        body = jax.checkpoint(
            gfn, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), new_cache = jax.lax.scan(
        body, (x, _zero_aux(cfg)), (groups, cache))
    return x, aux, new_cache


# ----------------------------------------------------------------------
# Encoder (enc-dec archs; non-causal self attention over frame embeddings)
# ----------------------------------------------------------------------

def _encode(params, cfg: ModelConfig, enc_emb):
    B, L, D = enc_emb.shape
    x = enc_emb
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))

    def gfn(carry, gp):
        xc, _ = carry
        bp = gp[0]
        h = apply_norm(xc, bp["ln1"], cfg)
        # non-causal self attention: window=None, mask=all-valid
        from .attention import _sdpa
        H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = (h @ bp["attn"]["wq"]).reshape(B, L, H, dh)
        k = (h @ bp["attn"]["wk"]).reshape(B, L, K, dh)
        v = (h @ bp["attn"]["wv"]).reshape(B, L, K, dh)
        mask = jnp.ones((B, 1, L, L), bool)
        o = _sdpa(q, k, v, mask, cfg.logit_softcap)
        xc = xc + o.reshape(B, L, H * dh) @ bp["attn"]["wo"]
        h2 = apply_norm(xc, bp["ln2"], cfg)
        xc = xc + apply_mlp(h2, bp["mlp"], cfg)
        return (xc, carry[1]), None

    body = jax.checkpoint(gfn) if cfg.remat else gfn
    (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                             params["encoder"]["groups"])
    return apply_norm(x, params["encoder"]["final_norm"], cfg)


# ----------------------------------------------------------------------
# Forward / prefill
# ----------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, tokens):
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.embed_scale is not None:
        return x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    if cfg.norm == "rmsnorm":
        x = x * jnp.sqrt(cfg.d_model).astype(cfg.dtype)
    return x


def apply(params, cfg: ModelConfig, tokens, *, enc_emb=None, cross_emb=None,
          positions=None, want_cache=False):
    """Full-sequence forward.  Returns dict(hidden, aux, cache?); ``aux``
    as :func:`_zero_aux` shapes it, summed over the layers."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _embed(params, cfg, tokens)

    if cfg.is_enc_dec:
        assert enc_emb is not None, "enc-dec arch needs enc_emb"
        cross_emb = _encode(params, cfg, enc_emb.astype(cfg.dtype))
    elif cross_emb is not None:
        cross_emb = cross_emb.astype(cfg.dtype)

    aux_total = _zero_aux(cfg)
    prelude_cache = []
    block = _run_block
    if cfg.remat:
        block = jax.checkpoint(
            _run_block, static_argnums=(2, 3),
            policy=jax.checkpoint_policies.nothing_saveable)
    for bp in params.get("prelude", []):
        x, nc, a = block(x, bp, cfg.prelude_entry, cfg, positions,
                         cross_emb, None, None)
        prelude_cache.append(nc)
        aux_total = _add_aux(aux_total, a)

    x, aux, cache = _scan_groups(x, params["groups"], cfg, positions,
                                 cross_emb, None, None, decode=False,
                                 collect_cache=want_cache)
    aux_total = _add_aux(aux_total, aux)
    x = apply_norm(x, params["final_norm"], cfg)
    out = {"hidden": x, "aux": aux_total}
    if want_cache:
        out["cache"] = {"prelude": prelude_cache, "groups": cache}
    return out


def _mask_pad_logits(lg, cfg: ModelConfig):
    if cfg.padded_vocab == cfg.vocab_size:
        return lg
    col = jnp.arange(cfg.padded_vocab)
    return jnp.where(col < cfg.vocab_size, lg, -1e30)


def logits(params, cfg: ModelConfig, hidden):
    w = params.get("lm_head")
    if w is None:
        w = params["embed"].T
    return _mask_pad_logits(
        hidden.astype(jnp.float32) @ w.astype(jnp.float32), cfg)


# ----------------------------------------------------------------------
# Loss: chunked vocab-sharded cross entropy (never materializes full logits)
# ----------------------------------------------------------------------

def lm_loss(params, cfg: ModelConfig, hidden, targets, mask):
    """hidden: (B,S,D); targets/mask: (B,S)."""
    B, S, D = hidden.shape
    w = params.get("lm_head")
    if w is None:
        w = params["embed"].T                               # (D, V)
    T = B * S
    h = hidden.reshape(T, D)
    t = targets.reshape(T)
    m = mask.reshape(T).astype(jnp.float32)
    Q = min(cfg.loss_chunk, T)
    pad = (-T) % Q
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        t = jnp.pad(t, ((0, pad),))
        m = jnp.pad(m, ((0, pad),))
    n = h.shape[0] // Q

    def body(acc, xs):
        hc, tc, mc = xs
        lg = hc.astype(jnp.float32) @ w.astype(jnp.float32)  # (Q, V)
        lg = shard(lg, P(None, "model"))
        lg = _mask_pad_logits(lg, cfg)
        lse = jax.nn.logsumexp(lg, axis=-1)
        correct = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum((lse - correct) * mc), None

    xs = (h.reshape(n, Q, D), t.reshape(n, Q), m.reshape(n, Q))
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
    return total / jnp.maximum(m.sum(), 1.0)


def loss_fn(params, cfg: ModelConfig, batch):
    """batch: {"tokens": (B,S), optional "enc_emb"/"cross_emb"/"mask"}."""
    tokens = batch["tokens"]
    out = apply(params, cfg, tokens,
                enc_emb=batch.get("enc_emb"),
                cross_emb=batch.get("cross_emb"))
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(tokens)
    mask = mask.at[:, -1].set(0)
    return lm_loss(params, cfg, out["hidden"], targets, mask) + _aux_loss(
        out["aux"])


# ----------------------------------------------------------------------
# Decode (single token against a cache)
# ----------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    """Cache pytree matching the layout (leaves lead with n_groups)."""
    K, dh = cfg.n_kv_heads, cfg.head_dim

    def entry_cache(entry, stacked: bool):
        mixer, _ = entry
        lead = (cfg.n_groups,) if stacked else ()

        def z(*shape, dtype=None):
            return jnp.zeros(lead + shape, dtype or cfg.dtype)
        if mixer in ("attn", "swa"):
            C = cache_len if mixer == "attn" else min(cfg.window, cache_len)
            return {"k": z(batch, C, K, dh), "v": z(batch, C, K, dh)}
        if mixer == "mamba":
            return {"conv": z(batch, cfg.ssm_conv, cfg.d_inner),
                    "ssm": z(batch, cfg.d_inner, cfg.ssm_state,
                             dtype=jnp.float32)}
        if mixer == "xattn":
            return {"cross": {"k": z(batch, cfg.cross_len, K, dh),
                              "v": z(batch, cfg.cross_len, K, dh)}}
        if mixer == "attn_x":
            return {"self": {"k": z(batch, cache_len, K, dh),
                             "v": z(batch, cache_len, K, dh)},
                    "cross": {"k": z(batch, cfg.cross_len, K, dh),
                              "v": z(batch, cfg.cross_len, K, dh)}}
        raise ValueError(mixer)

    cache = {"groups": tuple(entry_cache(e, True) for e in cfg.layout)}
    if cfg.first_k_dense:
        cache["prelude"] = [entry_cache(cfg.prelude_entry, False)
                            for _ in range(cfg.first_k_dense)]
    return cache


def decode_step(params, cfg: ModelConfig, token, cache, cache_index):
    """token: (B,1) int32; cache_index: () int32 absolute position.

    Returns (logits (B,1,V), new_cache)."""
    B = token.shape[0]
    positions = jnp.broadcast_to(
        cache_index.astype(jnp.int32), (B, 1))
    x = _embed(params, cfg, token)

    new_prelude = []
    for bp, pc in zip(params.get("prelude", []), cache.get("prelude", [])):
        x, nc, _ = _run_block(x, bp, cfg.prelude_entry, cfg, positions,
                              None, pc, cache_index)
        new_prelude.append(nc)

    x, _, new_groups = _scan_groups(x, params["groups"], cfg, positions,
                                    None, cache["groups"], cache_index,
                                    decode=True)
    x = apply_norm(x, params["final_norm"], cfg)
    lg = logits(params, cfg, x)
    lg = shard(lg, P(None, None, "model"))
    new_cache = {"groups": new_groups}
    if new_prelude:
        new_cache["prelude"] = new_prelude
    return lg, new_cache
