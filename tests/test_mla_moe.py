"""Latent attention and the dropless expert share (DESIGN.md §12), at toy
widths in float32 on the CPU (the grouped products in Pallas interpret
mode).

Contracts:

  * **YaRN** — the rotary frequencies are HF DeepSeek-V2's closed form:
    at the published sizes the correction range is [10, 23], the pairs
    below it keep their base frequency and those above it are divided by
    the factor; the softmax scale is 192^-0.5 * mscale^2;
  * **MLA** — ``mla_attention`` equals an MLA written out by hand in
    float64;
  * **expert share** — the 8 shares' outputs, the shared expert counted
    once, add up to the uncut layer, which equals every expert applied
    densely and weighted by its (raw, scaled) gate;
  * **dropless** — a router that sends every token to one held expert
    loses no token: the layer equals the dense masked reference, and the
    held load reads back;
  * **work follows the routed rows** — the grouped products visit the
    row tiles of the held groups only, not the static t*K rows;
  * **the engine names and reports it** — the ``mla`` and
    ``routed_experts`` scopes sit inside ``client_sgd`` in the compiled
    round, the engine records an ``expert_share`` event, and the routing
    counters come back in the eval history with one host sync.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig
from repro.models.attention import (make_mla_params, mla_attention,
                                    mla_softmax_scale, yarn_freqs)
from repro.models.config import Yarn
from repro.models.moe import (GMM_ROWS, apply_expert_share,
                              make_moe_params)

PUBLISHED_YARN = Yarn(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707)


def _moe_cfg(n_held, share=0, **kw):
    base = dict(name="moe", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2,
                d_ff=32, vocab_size=64, layout=(("attn", "moe"),),
                n_experts=16, top_k=3, n_shared_experts=1, d_expert=8,
                capacity_factor=None, router_aux_coef=0.0,
                norm_topk_prob=False, routed_scale=1.5,
                n_held_experts=n_held, held_share=share,
                dtype="float32", param_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _swiglu(x, up, gate, down):
    g = x @ gate
    return ((g / (1.0 + np.exp(-g))) * (x @ up)) @ down


def _dense_masked(x, p, cfg, lo=0):
    """Every held expert on every token, times its gate (0 where the
    token did not route to it), plus the shared MLP: float64."""
    xf = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    logits = xf @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, axis=-1)[:, :cfg.top_k]
    gate = np.zeros_like(probs)
    np.put_along_axis(gate, top, np.take_along_axis(probs, top, -1), -1)
    gate *= cfg.routed_scale
    out = np.zeros_like(xf)
    for e in range(cfg.n_held_experts):
        w = [np.asarray(p[k][e], np.float64)
             for k in ("routed_up", "routed_gate", "routed_down")]
        out += gate[:, lo + e:lo + e + 1] * _swiglu(xf, *w)
    sh = [np.asarray(p["shared"][k], np.float64)
          for k in ("w_up", "w_gate", "w_down")]
    return (out + _swiglu(xf, *sh)).reshape(x.shape), top


# ----------------------------------------------------------------------
# YaRN and MLA
# ----------------------------------------------------------------------

def test_yarn_frequencies_closed_form():
    f = np.asarray(yarn_freqs(64, 10000.0, PUBLISHED_YARN), np.float64)
    i = np.arange(32)
    base = 10000.0 ** (-2.0 * i / 64)

    def d(r):
        return 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))
    low, high = math.floor(d(32)), math.ceil(d(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(f, base / 40 * ramp + base * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)


def test_mla_softmax_scale():
    cfg = ModelConfig(name="v2", n_layers=1, d_model=2048, n_heads=16,
                      n_kv_heads=16, d_ff=10944, vocab_size=256,
                      layout=(("mla", "mlp"),), kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128, yarn=PUBLISHED_YARN)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                   rel=1e-12)


def _hand_mla(x, p, cfg, freq, scale):
    """MLA written out in float64 from the equations."""
    f64 = lambda a: np.asarray(a, np.float64)        # noqa: E731
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    x = f64(x)
    q = (x @ f64(p["wq"])).reshape(B, S, H, dn + dr)
    ckv = x @ f64(p["wkv_a"])
    c = ckv[..., :r]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + 1e-6) * (
        1 + f64(p["kv_norm"]["scale"]))
    kv = (c @ f64(p["wkv_b"])).reshape(B, S, H, dn + dv)
    ang = np.arange(S)[:, None] * f64(freq)[None, :]           # (S, dr/2)
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]

    def rot(a):
        a1, a2 = a[..., :dr // 2], a[..., dr // 2:]
        return np.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)
    qf = np.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
    kpe = rot(ckv[:, :, None, r:])
    kf = np.concatenate([kv[..., :dn], np.broadcast_to(kpe, (B, S, H, dr))],
                        -1)
    s = np.einsum("bqhd,bshd->bhqs", qf, kf) * scale
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    o = np.einsum("bhqs,bshd->bqhd", pr, kv[..., dn:]).reshape(B, S, H * dv)
    return o @ f64(p["wo"])


@pytest.mark.parametrize("direct", [True, False], ids=["direct", "blockwise"])
def test_mla_matches_hand_written(direct):
    yarn = Yarn(factor=4.0, original_max_position=16, beta_fast=8.0,
                beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)
    cfg = ModelConfig(name="mla", n_layers=1, d_model=32, n_heads=4,
                      n_kv_heads=4, d_ff=64, vocab_size=64,
                      layout=(("mla", "mlp"),), kv_lora_rank=16,
                      qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=6,
                      yarn=yarn, attn_chunk=4,
                      attn_direct_max=64 if direct else 4,
                      dtype="float32", param_dtype="float32")
    p = make_mla_params(jax.random.PRNGKey(0), cfg)
    p["kv_norm"]["scale"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                                    (16,))
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, 32))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    got = mla_attention(x, p, cfg, pos)
    want = _hand_mla(x, p, cfg, yarn_freqs(8, cfg.rope_theta, yarn),
                     mla_softmax_scale(cfg))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


# ----------------------------------------------------------------------
# The expert share
# ----------------------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    full = _moe_cfg(16)
    p = make_moe_params(jax.random.PRNGKey(0), full)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 16))
    whole, _, load = apply_expert_share(x, p, full)
    assert int(load.sum()) == 2 * 10 * 3                 # nothing dropped
    want, _ = _dense_masked(x, p, full)
    np.testing.assert_allclose(np.asarray(whole), want, rtol=1e-4,
                               atol=1e-5)
    total, served = 0.0, 0
    for j in range(8):
        cfg = _moe_cfg(2, share=j)
        pj = dict(p, **{k: p[k][2 * j:2 * j + 2] for k in (
            "routed_up", "routed_gate", "routed_down")})
        out, _, lj = apply_expert_share(x, pj, cfg)
        total = total + out
        served += int(lj.sum())
    shared = _swiglu(np.asarray(x, np.float64), *(
        np.asarray(p["shared"][k], np.float64)
        for k in ("w_up", "w_gate", "w_down")))
    np.testing.assert_allclose(np.asarray(total) - 7 * shared, want,
                               rtol=1e-4, atol=1e-5)
    assert served == 2 * 10 * 3


def test_dropless_router_on_one_held_expert():
    """Every token's top 3 is held expert 0 and two absent experts: one
    group takes all t rows, nothing is dropped."""
    cfg = _moe_cfg(4)
    p = make_moe_params(jax.random.PRNGKey(0), cfg)
    r = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (16, 16))
    p["router"] = r.at[:, 0].set(3.0).at[:, 1:4].set(-3.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16))) + 0.1
    out, aux, load = apply_expert_share(x, p, cfg)
    want, top = _dense_masked(x, p, cfg)
    assert (top[:, 0] == 0).all() and (top[:, 1:] >= 4).all()
    np.testing.assert_array_equal(np.asarray(load), [48, 0, 0, 0])
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)
    assert float(aux) == 0.0


def test_router_stored_at_its_dtype_routes_in_float32():
    """A router stored in bfloat16 (as a bfloat16 checkpoint holds it)
    routes exactly as its values widened to float32 do; float32 stays
    the default."""
    assert make_moe_params(jax.random.PRNGKey(0),
                           _moe_cfg(4))["router"].dtype == jnp.float32
    cfg = _moe_cfg(4, router_dtype="bfloat16")
    p = make_moe_params(jax.random.PRNGKey(0), cfg)
    assert p["router"].dtype == jnp.bfloat16
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16))
    out, _, load = apply_expert_share(x, p, cfg)
    wide = dict(p, router=p["router"].astype(jnp.float32))
    want, _, want_load = apply_expert_share(x, wide, _moe_cfg(4))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_grouped_products_visit_the_routed_rows_only():
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    cfg = _moe_cfg(2, n_experts=16, top_k=6, d_model=32)
    p = make_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 32))
    _, _, load = apply_expert_share(x, p, cfg)
    slots = 2 * 256 * 6
    m = -(-slots // GMM_ROWS) * GMM_ROWS
    sizes = jnp.concatenate([load, jnp.array([m - int(load.sum())])])
    _, tiles = make_group_metadata(group_sizes=sizes.astype(jnp.int32), m=m,
                                   tm=GMM_ROWS, start_group=jnp.int32(0),
                                   num_nonzero_groups=2,
                                   visit_empty_groups=False)
    held = int(load.sum())
    assert 0 < held < slots // 3
    assert int(tiles) <= -(-held // GMM_ROWS) + 1
    assert int(tiles) < m // GMM_ROWS


def test_expert_share_under_vmap_and_grad():
    """Client SGD vmaps the loss over a chunk of clients and
    differentiates it: each client's gradient is its own run's."""
    cfg = _moe_cfg(4, share=1)
    p = make_moe_params(jax.random.PRNGKey(0), cfg)
    xs = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 8, 16))

    def loss(p, x):
        return jnp.sum(apply_expert_share(x, p, cfg)[0] ** 2)
    g = jax.vmap(jax.grad(loss), in_axes=(None, 0))(p, xs)
    for i in range(3):
        gi = jax.grad(loss)(p, xs[i])
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gi)):
            np.testing.assert_allclose(np.asarray(a[i]), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_config_rejects_a_share_with_capacity():
    with pytest.raises(ValueError, match="dropless"):
        _moe_cfg(4, capacity_factor=1.25)
    with pytest.raises(ValueError, match="outside"):
        _moe_cfg(4, share=4)


# ----------------------------------------------------------------------
# Through the engine
# ----------------------------------------------------------------------

def _tiny_v2():
    from repro.fl.zoo import zoo_model
    cfg = ModelConfig(
        name="v2-tiny", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_ff=48, vocab_size=64, layout=(("mla", "moe"),), first_k_dense=1,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=6, yarn=Yarn(factor=4.0, original_max_position=8),
        embed_scale=1.0, n_experts=8, n_held_experts=2, top_k=2,
        n_shared_experts=1, d_expert=8, capacity_factor=None,
        router_aux_coef=0.0, norm_topk_prob=False, tie_embeddings=False,
        dtype="float32", param_dtype="float32")
    return zoo_model(cfg, seq_len=7)


@pytest.fixture(scope="module")
def v2_fed():
    from repro.core.attacks import AttackConfig
    from repro.fl import FLConfig
    from repro.fl.zoo import make_zoo_federation
    model = _tiny_v2()
    cfg = FLConfig(n_clients=3, f=1, rounds=2, batch_size=2, local_steps=1,
                   eval_every=2, l2=0.0, aggregator="diversefl",
                   attack=AttackConfig(kind="sign_flip"), sample_frac=0.5,
                   streaming=True, client_chunk=1, use_kernel_agg=True)
    fed = make_zoo_federation(model, cfg, jax.random.PRNGKey(0),
                              per_client=4, n_test=2)
    return model, cfg, fed


def test_scopes_sit_in_the_compiled_client_sgd(v2_fed):
    from repro.fl import RoundEngine, telemetry
    model, cfg, fed = v2_fed
    with telemetry.recording() as rec:
        engine = RoundEngine(model, fed, cfg)
    events = [r for r in rec.records if r.get("kind") == "expert_share"]
    assert [{k: e[k] for k in ("held", "routed", "top_k", "dropless",
                               "grouped")} for e in events] == [
        {"held": 2, "routed": 8, "top_k": 2, "dropless": True,
         "grouped": "megablox.gmm"}]
    text = engine.lower_training(model.init(jax.random.PRNGKey(1)),
                                 jax.random.PRNGKey(3),
                                 [0.1, 0.1]).compile().as_text()
    paths = [p.split("/") for p in re.findall(r'op_name="([^"]*)"', text)]

    def inside(layer, stage):
        return any(any(stage in c for c in p[:i]) for p in paths
                   for i, c in enumerate(p) if c == layer)
    for layer in ("mla", "routed_experts"):
        assert inside(layer, "client_sgd") and inside(layer, "guide_sgd")


def test_routing_counters_ride_the_one_sync(v2_fed, monkeypatch):
    from repro.fl import run_federated_training
    from repro.fl import simulator as sim
    model, cfg, fed = v2_fed
    calls = []
    orig = sim.host_sync
    monkeypatch.setattr(sim, "host_sync",
                        lambda tree: calls.append(1) or orig(tree))
    h = run_federated_training(model, fed, cfg, lambda r: 0.1 + 0.0 * r)
    assert len(calls) == 1
    share, peak = h["held_slot_share"][0], h["held_load_peak"][0]
    assert 0.0 < share <= 1.0 and peak >= 1.0
    # the test set's own routing, recomputed outside the engine
    _, counters = model.apply_with_routing(h["params"], fed.test_x)
    assert share == pytest.approx(float(counters["held_slot_share"]))
