"""Every Pallas kernel compiles for a described TPU v5e at real widths.

Interpret mode (the rest of the suite) runs the kernel bodies as plain
XLA, so it cannot see what the chip's compiler refuses: blocks that break
the (8, 128) tiling rule, more VMEM than a kernel may use, primitives
Mosaic cannot lower.  Here the TPU compiler compiles each kernel for a
v5e chip that is described, not attached; nothing runs.  The topology is
described inside a fixture (never at import), so under several pytest
workers only the worker given this file loads the TPU library.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dequant_fold, flash_attention, mamba_scan
from repro.kernels import masked_agg, robust_agg, similarity

V5E_HBM_BYTES = 16 * 1024 ** 3

# fl-llm-100m (chip_smoke.decoder_model): one streaming block
# of client_chunk=1 rows over the flat decoder
DECODER_D = 100_369_280


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    described-chip executable is written there but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def vgg11_d():
    from repro.fl.small_models import vgg11
    shapes = jax.eval_shape(vgg11().init, jax.random.PRNGKey(0))
    return sum(s.size for s in jax.tree.leaves(shapes))


def _fold(n, d):
    def fn(u, w, acc):
        return masked_agg.masked_agg_update_kernel(u, w, acc)
    return fn, [((n, d), jnp.float32), ((n,), jnp.float32),
                ((d,), jnp.float32)]


def _fold_view(d, c=1, dtype=jnp.float32):
    """The streaming fold of a block of ``c`` clients into its own
    accumulator layout."""
    def fn(u, w, acc):
        return masked_agg.masked_agg_fold_kernel(u, w, acc)
    return fn, [((c, d), dtype), ((c,), jnp.float32),
                (masked_agg.fold_layout(d), jnp.float32)]


def _flash(b, h, k, s, dh, window=None):
    def fn(q, kk, v):
        return flash_attention.flash_attention_kernel(q, kk, v,
                                                      window=window)
    return fn, [((b, h, s, dh), jnp.bfloat16), ((b, k, s, dh), jnp.bfloat16),
                ((b, k, s, dh), jnp.bfloat16)]


def _leaf(form, r, l, n=23):
    """A leaf form on one stacked leaf viewed as (n, r, l)."""
    if form == "similarity":
        fn, specs = similarity.similarity_leaf_kernel, [
            ((n, r, l), jnp.float32)] * 2
    else:
        fn, specs = masked_agg.masked_agg_leaf_kernel, [
            ((n, r, l), jnp.float32), ((n,), jnp.float32)]
    return lambda dv: (fn, specs)


# name -> make(vgg11_d) -> (fn, [(shape, dtype), ...])
CASES = {
    # dense DiverseFL Step 4 over the paper's N=23 VGG-11 federation
    "similarity_vgg11_n23": lambda dv: (
        similarity.similarity_kernel,
        [((23, dv), jnp.float32)] * 2),
    # streaming Step 4 on one client block of the decoder
    "similarity_decoder_n1": lambda dv: (
        similarity.similarity_kernel,
        [((1, DECODER_D), jnp.float32)] * 2),
    "masked_agg_vgg11_n23": lambda dv: (
        masked_agg.masked_agg_kernel,
        [((23, dv), jnp.float32), ((23,), jnp.bool_)]),
    "masked_agg_update_decoder_n1": lambda dv: _fold(1, DECODER_D),
    # the silo cells' streaming fold: danube's and V2-Lite's D
    "masked_agg_fold_danube_n1": lambda dv: _fold_view(441_735_680),
    "masked_agg_fold_v2_lite_n1": lambda dv: _fold_view(535_060_992),
    # a dense bf16 payload in blocks of 3, and the (1, D) row of a D
    # that 128 does not divide
    "masked_agg_fold_bf16_n3": lambda dv: _fold_view(DECODER_D, 3,
                                                     jnp.bfloat16),
    "masked_agg_fold_row_n3": lambda dv: _fold_view((1 << 20) + 3, 3),
    "masked_agg_fold_n40": lambda dv: _fold_view(1 << 20, 40),
    # a cross-device block: the client axis is tiled, VMEM stays bounded
    "masked_agg_update_n1024": lambda dv: _fold(1024, (1 << 20) + 3),
    "dequant_fold_n1024": lambda dv: (
        lambda q, s, w, acc: dequant_fold.dequant_fold_update_kernel(
            q, s, w, acc, qblock=128),
        [((1024, (1 << 20) + 3), jnp.int8),
         ((1024, -(-((1 << 20) + 3) // 128)), jnp.float32),
         ((1024,), jnp.float32), (((1 << 20) + 3,), jnp.float32)]),
    # leaf forms on VGG-11's stacked leaves: the 4096x4096 head, a
    # 3x3x512x512 conv, the 4096x10 output layer, the first conv
    **{f"{form}_leaf_vgg11_n23_{r}x{l}": _leaf(form, r, l)
       for form in ("similarity", "masked_agg")
       for r, l in ((4096, 4096), (4608, 512), (4096, 10), (27, 64))},
    "robust_agg_vgg11_n23": lambda dv: (
        lambda u: robust_agg.robust_agg_kernel(u, 5),
        [((23, dv), jnp.float32)]),
    # falcon-mamba-7b: d_inner 8192, d_state 16
    "mamba_scan_falcon_mamba_7b": lambda dv: (
        mamba_scan.mamba_scan_kernel,
        [((1, 2048, 8192, 16), jnp.float32)] * 2
        + [((1, 2048, 16), jnp.float32)]),
    # the kernel serves sequences past attn_direct_max (2048)
    "flash_attention_gemma_2b": lambda dv: _flash(1, 8, 1, 4096, 256),
    "flash_attention_minitron_8b": lambda dv: _flash(1, 32, 8, 4096, 128),
    "flash_attention_h2o_danube_swa": lambda dv: _flash(1, 32, 8, 4096, 80,
                                                        window=4096),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, vgg11_d):
    fn, specs = CASES[name](vgg11_d)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert peak <= V5E_HBM_BYTES, (name, peak)


def test_expert_share_compiles_for_v5e(one_chip, monkeypatch):
    """DeepSeek-V2-Lite's expert share at published widths (hidden 2048,
    expert width 1408, 8 of 64 experts held, top 6, two shared), forward
    and backward on one client step of 2 x 2048 tokens: the grouped
    products (megablox ``gmm``/``tgmm``) take the chip's branch and
    compile, and no (tokens x experts) capacity buffer appears."""
    from repro.models import ModelConfig
    from repro.models.moe import apply_expert_share, make_moe_params
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="v2-share", n_layers=2, d_model=2048, n_heads=16,
                      n_kv_heads=16, d_ff=10944, vocab_size=12800,
                      layout=(("mla", "moe"),), first_k_dense=1,
                      kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128, n_experts=64,
                      n_held_experts=8, top_k=6, n_shared_experts=2,
                      d_expert=1408, capacity_factor=None,
                      router_aux_coef=0.0, norm_topk_prob=False)
    shapes = jax.eval_shape(lambda k: make_moe_params(k, cfg),
                            jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=one_chip),
                     shapes)
    x = jax.ShapeDtypeStruct((2, 2048, 2048), jnp.bfloat16, sharding=one_chip)

    def loss(p, x):
        out = apply_expert_share(x, p, cfg)[0]
        return jnp.sum(out.astype(jnp.float32) ** 2)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%(t?gmm)\.\d+ = \S+ custom-call", text)
    assert sorted(set(kernels)) == ["gmm", "tgmm"] and len(kernels) == 9
    assert not re.search(r"\[64,\d+,2048\]", text)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) <= V5E_HBM_BYTES


# the silo cells' decoders at published widths, one layer each:
# h2o-danube-1.8b (GQA 32/8, head dim 80, window 4096) and
# DeepSeek-V2-Lite's latent attention (16 heads, qk 192, v 128, YaRN)
SILO_MIXERS = {
    "danube_swa": dict(d_model=2560, n_heads=32, n_kv_heads=8, head_dim=80,
                       d_ff=6912, layout=(("swa", "mlp"),), window=4096),
    "v2_lite_mla": dict(d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
                        layout=(("mla", "mlp"),), kv_lora_rank=512,
                        qk_nope_head_dim=128, qk_rope_head_dim=64,
                        v_head_dim=128),
}


@pytest.mark.parametrize("mixer", sorted(SILO_MIXERS))
def test_silo_training_step_has_no_score_matrix(mixer, one_chip,
                                                monkeypatch):
    """One client step of a silo cell (2 sequences of 2048 tokens) for
    two clients: ``grad`` under ``vmap``, the layer under ``remat``.  On
    the TPU's path the attention is the fused kernel's forward and
    backward (under the ``mla`` scope in the latent mixer), and no float32
    (2048, 2048) score or probability buffer of the heads is left in the
    compiled program."""
    from repro import models
    from repro.models import ModelConfig
    from repro.models.config import Yarn
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yarn = Yarn(factor=40.0, original_max_position=4096, mscale=0.707,
                mscale_all_dim=0.707)
    cfg = ModelConfig(name=mixer, n_layers=1, vocab_size=1024,
                      yarn=yarn if mixer == "v2_lite_mla" else None,
                      **SILO_MIXERS[mixer])
    assert cfg.remat
    shapes = jax.eval_shape(lambda k: models.init(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    tokens = jax.ShapeDtypeStruct((2, 2, 2048), jnp.int32, sharding=one_chip)

    def client_grads(params, tokens):
        def grad(tok):
            return jax.grad(models.loss_fn)(params, cfg, {"tokens": tok})
        return jax.vmap(grad)(tokens)
    compiled = jax.jit(client_grads).lower(params, tokens).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # (..., 2048, 2048) float32 buffers: the scores or probabilities of
    # every head, else activations at a width of 2048 (leading dims the
    # clients and the batch only)
    per_head = [math.prod(int(d) for d in lead.split(",") if d)
                for lead in re.findall(r"f32\[([0-9,]*)2048,2048\]", text)]
    assert max(per_head, default=0) < cfg.n_heads
    # forward, remat forward, dq and dkv, each under the layer's scope
    # where the mixer opens one (``mla``, which ``mla_ms`` reads)
    calls = {}
    for instr in text.split("\n  %"):
        m = re.match(r"(splash_mha_[a-z]+)_[\w.]+ = ", instr)
        if m:
            calls.setdefault(m.group(1), []).append(
                re.search(r'op_name="([^"]+)"', instr).group(1))
    assert sorted(calls) == ["splash_mha_dkv", "splash_mha_dq",
                             "splash_mha_fwd"]
    if mixer == "v2_lite_mla":
        assert all("/mla/" in n for names in calls.values() for n in names)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) <= V5E_HBM_BYTES


def _instructions(comp: str):
    """(name, opcode, output shape text) of each instruction of one HLO
    computation."""
    out = []
    for line in comp.split("\n")[1:]:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(",
                     line)
        if m:
            out.append((m.group(1), m.group(3), m.group(2)))
    return out


def test_silo_round_folds_without_relayout(one_chip, monkeypatch):
    """A silo cell's streaming round, cut to one layer at small widths
    (bf16, 4 silos, client_chunk 1, the kernel fold), compiled for the
    chip: the block sweep folds each client with one Pallas call into
    the carried accumulator, and no op of the sweep slices, broadcasts,
    reshapes or reduces a float32 array of D elements (the relayouts of
    a flat (D,) carry around the kernel's 2-D block).  The one D-wide
    copy allowed is the carry's, in its own layout, into the call."""
    from repro.core.attacks import AttackConfig
    from repro.fl import FLConfig, RoundEngine, make_zoo_federation, \
        telemetry, zoo_model
    from repro.models import ModelConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mc = ModelConfig(name="silo-cut", n_layers=1, d_model=512, n_heads=4,
                     n_kv_heads=2, head_dim=128, d_ff=1024,
                     vocab_size=2048, layout=(("swa", "mlp"),),
                     window=4096, activation="swiglu", dtype="bfloat16",
                     param_dtype="bfloat16")
    model = zoo_model(mc, seq_len=255)
    cfg = FLConfig(n_clients=4, f=1, rounds=2, local_steps=1, batch_size=2,
                   l2=0.0, aggregator="diversefl",
                   attack=AttackConfig(kind="sign_flip"), sample_frac=0.5,
                   streaming=True, client_chunk=1, use_kernel_agg=True,
                   eval_every=2)
    fed = make_zoo_federation(model, cfg, jax.random.PRNGKey(0),
                              per_client=4, n_test=2)
    engine = RoundEngine(model, fed, cfg)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(1)))
    d = sum(p.size for p in jax.tree.leaves(params))
    assert d % 512 == 0 and 1e6 < d < 1e7
    with telemetry.recording() as rec:
        text = engine.lower_training(params, jax.random.PRNGKey(3),
                                     [0.1, 0.1]).compile().as_text()
    rows, lanes = masked_agg.fold_layout(d)
    assert [(r["rows"], r["lanes"], r["view"]) for r in rec.records
            if r.get("kind") == "fold_layout"] == [(rows, lanes, True)]
    sweep = [c for c in text.split("\n\n")
             if re.search(r"%masked_agg[\w.]* = \S+ custom-call\(", c)]
    assert len(sweep) == 1
    ops = _instructions(sweep[0])
    assert len([n for n, op, _ in ops if op == "custom-call"
                and n.startswith("masked_agg")]) == 1
    wide = re.compile(r"f32\[([\d,]+)\]")
    copies = []
    for name, op, shape in ops:
        m = wide.match(shape)
        if m is None or math.prod(int(x) for x in m.group(1).split(",")) != d:
            continue
        kind = op if op != "fusion" else name
        assert not any(k in kind for k in ("dynamic-slice", "broadcast",
                                           "reduce", "reshape")), \
            (name, op, shape)
        if "copy" in kind:
            copies.append(m.group(1))
    assert copies in ([], [f"{rows},{lanes}"]), copies
