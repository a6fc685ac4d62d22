"""Fused causal attention (DESIGN.md §12): JAX's splash kernel, forward
and backward, behind ``kernels.ops.fused_causal_attention``; on the CPU in
Pallas interpret mode, at toy sizes.

Contracts:

  * **same attention** — the wrapper equals ``_sdpa`` with a causal mask,
    forward and under ``jax.grad``, for GQA at head dim 80, for MLA's
    widths (qk 192, v 128, the YaRN score scale), for a window that
    covers the sequence, and for a sequence the kernel's tiles do not
    divide (padded at its end);
  * **the path rule** — training and prefill self-attention take the
    fused kernel on a TPU, with no model-sharded mesh and no window
    narrower than the sequence; everything else keeps ``_sdpa``,
    ``_blockwise`` or the forward-only flash kernel;
  * **what stays** — decode with a cache, the k and v a prefill caches
    and cross-attention are bit-for-bit the same whatever the backend;
  * **through the model** — a decoder's loss and gradient on the fused
    path equal the direct path's, and each trace records an
    ``attention_path`` event.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.fl import telemetry
from repro.kernels import ops
from repro.models import ModelConfig
from repro.models import attention as attn
from repro.models.attention import _causal_mask, _sdpa
from repro.models.config import Yarn

YARN = Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def _qkv(key, B, S, H, K, dqk, dv, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (B, S, H, dqk), jnp.float32).astype(dtype),
            jax.random.normal(kk, (B, S, K, dqk), jnp.float32).astype(dtype),
            jax.random.normal(kv, (B, S, K, dv), jnp.float32).astype(dtype))


def _mla_scale():
    cfg = ModelConfig(name="mla", n_layers=1, d_model=64, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab_size=64,
                      layout=(("mla", "mlp"),), kv_lora_rank=16,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128, yarn=YARN)
    return attn.mla_softmax_scale(cfg)


# name -> (B, S, H, K, dqk, dv, scale, window, dtype, tolerance)
WRAPPER_CASES = {
    "gqa_dh80": (2, 384, 4, 2, 80, 80, None, None, jnp.float32, 2e-5),
    "gqa_dh80_bf16": (1, 256, 4, 2, 80, 80, None, None, jnp.bfloat16, 3e-2),
    "mla_192_128_yarn": (1, 384, 2, 2, 192, 128, "mla", None, jnp.float32,
                         2e-5),
    "window_covers_seq": (1, 256, 2, 1, 64, 64, None, 300, jnp.float32, 2e-5),
    "seq_off_tile": (2, 200, 4, 2, 80, 80, None, None, jnp.float32, 2e-5),
}


@pytest.mark.parametrize("name", list(WRAPPER_CASES))
def test_fused_matches_sdpa_forward_and_grad(name):
    B, S, H, K, dqk, dv, scale, window, dtype, tol = WRAPPER_CASES[name]
    scale = _mla_scale() if scale == "mla" else dqk ** -0.5
    q, k, v = _qkv(jax.random.PRNGKey(len(name)), B, S, H, K, dqk, dv, dtype)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    mask = _causal_mask(pos, pos, window)
    w = jax.random.normal(jax.random.PRNGKey(7), (B, S, H, dv))

    def fused(q, k, v):
        return ops.fused_causal_attention(q, k, v, scale)

    def direct(q, k, v):
        return _sdpa(q, k, v, mask, scale=scale)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w)

    o_f, o_d = fused(q, k, v), direct(q, k, v)
    assert o_f.shape == (B, S, H, dv) and o_f.dtype == v.dtype
    np.testing.assert_allclose(np.asarray(o_f, np.float32),
                               np.asarray(o_d, np.float32),
                               rtol=tol, atol=tol)
    g_f = jax.grad(loss(fused), argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss(direct), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_d):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=tol,
                                   atol=tol * np.abs(b).max())


# ----------------------------------------------------------------------
# The path rule
# ----------------------------------------------------------------------

def _dense(**kw):
    base = dict(name="d", n_layers=1, d_model=64, n_heads=4, n_kv_heads=2,
                d_ff=64, vocab_size=64)
    base.update(kw)
    return ModelConfig(**base)


# name -> (backend, model shards, seq, window, use_kernels, flash, path)
PATH_CASES = {
    "cpu_direct": ("cpu", 1, 2048, None, False, True, "direct"),
    "cpu_blockwise": ("cpu", 1, 4096, None, False, True, "blockwise"),
    "cpu_flash": ("cpu", 1, 4096, None, True, True, "flash"),
    "tpu_fused": ("tpu", 1, 2048, None, False, True, "fused"),
    "tpu_fused_long": ("tpu", 1, 4096, None, True, True, "fused"),
    "tpu_window_covers_seq": ("tpu", 1, 2048, 4096, False, True, "fused"),
    "tpu_window_equals_seq": ("tpu", 1, 2048, 2048, False, True, "fused"),
    "tpu_sliding_window": ("tpu", 1, 2048, 1024, False, True, "direct"),
    "tpu_sliding_window_long": ("tpu", 1, 8192, 4096, True, True, "flash"),
    "tpu_seq_off_tile": ("tpu", 1, 2047, None, False, True, "fused"),
    "tpu_short_seq": ("tpu", 1, 64, None, False, True, "fused"),
    "tpu_model_sharded": ("tpu", 4, 2048, None, False, True, "direct"),
    "tpu_mla_never_flash": ("cpu", 1, 4096, None, True, False, "blockwise"),
}


@pytest.mark.parametrize("name", list(PATH_CASES))
def test_attention_path_rule(name, monkeypatch):
    backend, shards, seq, window, kernels, flash, want = PATH_CASES[name]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(attn, "model_shard_count", lambda: shards)
    cfg = _dense(use_kernels=kernels)
    assert attn.attention_path(cfg, seq, window, flash=flash) == want


def _decoder(**kw):
    base = dict(name="dec", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                d_ff=64, vocab_size=97, dtype="float32",
                param_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _on_both_backends(fn, monkeypatch):
    """``fn()`` with the backend the host has, then as a TPU would see it."""
    cpu = fn()
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        m.setattr(ops, "_interpret", lambda: True)
        tpu = fn()
    return cpu, tpu


def test_decode_and_prefill_cache_unchanged_on_the_fused_backend(monkeypatch):
    cfg = _decoder(layout=(("swa", "mlp"),), window=256, remat=False)
    params = models.init(jax.random.PRNGKey(0), cfg)
    S = 128
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, 97)

    def run():
        out = models.apply(params, cfg, tok, want_cache=True)
        cache = models.init_cache(cfg, 2, S + 4)
        for i in range(3):
            lg, cache = models.decode_step(params, cfg, tok[:, i:i + 1],
                                           cache, jnp.int32(i))
        return out["cache"], lg, cache
    (pre_c, lg_c, dec_c), (pre_t, lg_t, dec_t) = _on_both_backends(
        run, monkeypatch)
    for a, b in zip(jax.tree.leaves((lg_c, dec_c)),
                    jax.tree.leaves((lg_t, dec_t))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the first layer's k and v, which no attention output feeds; later
    # layers' see the fused kernel's output through the residual stream
    for a, b in zip(jax.tree.leaves(pre_c), jax.tree.leaves(pre_t)):
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


def test_cross_attention_unchanged_on_the_fused_backend(monkeypatch):
    cfg = _decoder()
    p = attn.make_attn_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))
    emb = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64))
    cpu, tpu = _on_both_backends(
        lambda: attn.cross_attention(x, p, cfg, attn.make_cross_kv(emb, p,
                                                                   cfg)),
        monkeypatch)
    np.testing.assert_array_equal(np.asarray(cpu), np.asarray(tpu))


# ----------------------------------------------------------------------
# Through the model
# ----------------------------------------------------------------------

MODEL_CASES = {
    "swa_window_covers_seq": dict(layout=(("swa", "mlp"),), window=4096),
    "mla_yarn": dict(n_heads=2, n_kv_heads=2, layout=(("mla", "mlp"),),
                     kv_lora_rank=16, qk_nope_head_dim=32,
                     qk_rope_head_dim=16, v_head_dim=24, yarn=YARN),
}


@pytest.mark.parametrize("name", list(MODEL_CASES))
def test_model_loss_and_grad_on_the_fused_path(name, monkeypatch):
    cfg = _decoder(**MODEL_CASES[name])
    params = models.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 256),
                                          0, 97)}

    def run():
        with telemetry.recording() as rec:
            out = jax.value_and_grad(models.loss_fn)(params, cfg, batch)
        return out, [r for r in rec.records
                     if r.get("kind") == "attention_path"]
    ((l_d, g_d), ev_d), ((l_f, g_f), ev_f) = _on_both_backends(
        run, monkeypatch)
    assert {e["path"] for e in ev_d} == {"direct"}
    assert {e["path"] for e in ev_f} == {"fused"}
    mla = name.startswith("mla")
    assert ev_f[0]["seq"] == 256 and ev_f[0]["heads"] == cfg.n_heads
    assert ev_f[0]["dv"] == (cfg.v_head_dim if mla else cfg.head_dim)
    np.testing.assert_allclose(float(l_f), float(l_d), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_f), jax.tree.leaves(g_d)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4 * float(np.abs(b).max()))


def test_kernel_made_inside_one_trace_serves_the_next():
    """The kernel is cached by shape; one first made while a jit traces
    holds concrete block tables, so a second program can use it."""
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 128, 2, 1, 64, 64)
    first = jax.jit(lambda q, k, v: ops.fused_causal_attention(q, k, v, 0.1))
    second = jax.jit(
        lambda q, k, v: 2 * ops.fused_causal_attention(q, k, v, 0.1))
    np.testing.assert_allclose(np.asarray(second(q, k, v)),
                               2 * np.asarray(first(q, k, v)), rtol=1e-6)
