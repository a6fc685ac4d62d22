"""The ``deepseek-v2-lite-cut`` configuration and its cell
``silo-moe-s2048``: sizes and FLOPs by hand, the program against the
plain reference at toy widths in float32 (loss, gradient, and whole FL
rounds through the harness), the danube cell's program unchanged by the
generalisations this configuration needed, and the three per-layer
readers on traces made to order."""
import hashlib
import math
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import correct, reference, run, spec, system
from bench import trace as tr
from bench import traffic as trf
from bench.scopes import ScopedOp
from bench.tests import tiny, tiny_moe

CONF_NAME, CELL = "deepseek-v2-lite-cut", "silo-moe-s2048"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
PROBE = Path(__file__).parent / "data" / "probe.xplane.pb"


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    run.program_on_path()
    import repro.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")


def v2():
    return spec.config(CONF_NAME), spec.config_module(CONF_NAME)


# ----------------------------------------------------------------------
# sizes and FLOPs by hand
# ----------------------------------------------------------------------

def test_params_by_hand():
    conf, mod = v2()
    d, h, r = 2048, 16, 512
    mla = d * h * 192 + d * (r + 64) + r + r * h * 256 + h * 128 * d
    assert mla == 13_763_072
    dense = mla + 3 * d * 10944 + 2 * d
    moe = mla + 8 * 3 * d * 1408 + 3 * d * 2816 + d * 64 + 2 * d
    assert (dense, moe) == (81_007_104, 100_405_760)
    ends = 2 * 12800 * d + d
    assert ends == 52_430_848
    assert conf["n_params"] == ends + dense + 4 * moe == 535_060_992
    shapes = jax.eval_shape(mod.build(conf, spec.traffic(CELL)).init,
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == conf["n_params"]


def test_weights_match_program_layout():
    conf, mod = v2()
    t = spec.traffic(CELL)
    want = jax.eval_shape(mod.build(conf, t).init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: mod.init_params(conf, t, k),
                         jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(got)]


def test_every_weight_stored_at_the_configured_dtype():
    """The reference holds every weight at ``param_dtype``: a weight the
    program stored wider would take exact steps where the reference's
    are rounded, and (the router) route on other weights."""
    conf, mod = v2()
    shapes = jax.eval_shape(mod.build(conf, spec.traffic(CELL)).init,
                            jax.random.PRNGKey(0))
    assert {str(a.dtype) for a in jax.tree.leaves(shapes)} == \
        {conf["param_dtype"]}


def test_forward_flops_by_hand():
    conf, mod = v2()
    t = spec.traffic(CELL)
    S, d = 2048, 2048
    mla = 2 * S * 13_762_560 + 2 * 16 * (192 + 128) * S * (S + 1) // 2
    dense = 2 * S * 3 * d * 10944
    rows = S * 6 * 8 / 64
    assert rows == 1536
    routed = 2 * rows * 3 * d * 1408
    moe = 2 * S * (d * 64 + 3 * d * 2816) + routed
    head = 2 * S * d * 12800
    hand = 5 * mla + dense + 4 * moe + head
    assert mod.forward_flops(conf, t) == pytest.approx(hand, rel=1e-12)
    shares = [5 * mla / hand, 4 * moe / hand, dense / hand, head / hand]
    assert [round(100 * s) for s in shares] == [33, 34, 24, 9]
    flops, nbytes = mod.expert_work(conf, t, 2)
    assert flops == pytest.approx(4 * 2 * routed, rel=1e-12)
    w = 3 * 8 * d * 1408 * 2
    rr = 2 * rows
    assert nbytes == pytest.approx(4 * (w + 2 * (3 * rr * d + 3 * rr * 1408)),
                                   rel=1e-12)


def test_reference_yarn_and_scale_are_the_programs():
    conf, mod = v2()
    from repro.models.attention import mla_softmax_scale, yarn_freqs
    cfg = mod._model_config(conf)
    np.testing.assert_allclose(
        mod.yarn_freqs(conf), np.asarray(yarn_freqs(64, 1e4, cfg.yarn)),
        rtol=1e-6)
    assert mod.softmax_scale(conf) == pytest.approx(mla_softmax_scale(cfg))
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mod.softmax_scale(conf) == pytest.approx(192 ** -0.5 * m * m)


# ----------------------------------------------------------------------
# the program against the plain reference, float32, toy widths
# ----------------------------------------------------------------------

@pytest.mark.parametrize("query_block", [256, 4], ids=["whole", "blocks"])
def test_zoo_loss_and_gradient_match_reference(query_block, monkeypatch):
    conf, mod = tiny_moe.conf(), tiny_moe.cfgmod()
    monkeypatch.setattr(mod, "QUERY_BLOCK", query_block)
    t = {"seq_tokens": 16}
    model = mod.build(conf, t)
    p = mod.init_params(conf, t, jax.random.PRNGKey(3))
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 15), 0, 256)
    y = jax.random.randint(jax.random.PRNGKey(2), (2,), 0, 256)
    lp, gp = jax.value_and_grad(model.loss)(p, x, y)
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(mod.reference_loss(conf, t))(
            p, x, y, lambda a: a)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        scale = float(np.abs(np.asarray(b)).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * scale)


def run_tiny_moe(seed):
    out = run.run_cell("tiny-moe", tiny_moe.conf(), tiny_moe.traffic(),
                       tiny_moe.cfgmod(), seed, 0.2, False, jax.devices(),
                       PEAKS, per_layer=[],
                       end_to_end=[{"name": "rounds_per_s"},
                                   {"name": "setup_s"}])
    assert out["attempted"] > 0 and out["failed"] == 0
    return out


def test_fl_rounds_match_reference():
    out = run_tiny_moe(2**33 + 7)
    assert out["correct"], out["compared"]
    assert all(v["limit"] == 1e-3 or k == "keep_mismatch"
               for k, v in out["compared"].items())


def test_control_is_not_correct():
    t = tiny_moe.traffic()
    refer = reference.Reference(tiny_moe.cfgmod(), tiny_moe.conf(), t,
                                trf.federation_data(t, 256))
    ref, ctl = refer.readings(7), refer.readings(7, dtype="bfloat16")
    ok, _ = correct.judge(correct.numbers(ctl, ref), t["limits"])
    assert not ok


def test_bfloat16_router_steps_as_the_reference():
    """At the configuration's bfloat16 the program's first step of the
    router leaf follows the reference's (a float32 router read 19 % off
    here), and no leaf strays further than bfloat16 rounding."""
    conf = tiny_moe.conf()
    conf.update({"torch_dtype": "bfloat16", "param_dtype": "bfloat16",
                 "hidden_size": 128, "vocab_size": 512})
    t = dict(tiny_moe.traffic(), seq_tokens=32, lr=0.1)
    mod = tiny_moe.cfgmod()
    cell = system.Cell(conf, t, mod)
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(cell.params(0))[0]]
    _, _, prog = cell.prime(1)
    ref = reference.Reference(mod, conf, t, cell.data).readings(1)
    router = [i for i, k in enumerate(paths) if "router" in k]
    g, r = np.asarray(prog["grad1"]), np.asarray(ref["grad1"])
    assert len(router) == 1 and r[router[0]] > 0
    assert abs(g[router[0]] - r[router[0]]) / r[router[0]] < 0.03
    assert correct.numbers(prog, ref)["grad1_gap"] < 0.01


# the danube tiny cell's weights after set-up's three moving rounds
# (seed 5), as the program computed them before latent attention, the
# per-model embedding multiplier and the value head width of ``_sdpa``
DANUBE_TINY_SHA256 = (
    "6fe1332b019a4ca963c8fee36537052b8e0356a284173ddb2ce6ba6fe370ea0c")


def test_danube_tiny_cell_is_bit_identical():
    cell = system.Cell(tiny.conf(), tiny.traffic(), tiny.cfgmod())
    p, _, _ = cell.prime(5)
    h = hashlib.sha256()
    for a in jax.tree.leaves(p):
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == DANUBE_TINY_SHA256


# ----------------------------------------------------------------------
# the per-layer readers
# ----------------------------------------------------------------------

GMM = '%gmm.5 = bf16[24576,1408]{1,0} custom-call(%a), custom_call_target="tpu_custom_call"'
TGMM = '%tgmm.2 = bf16[8,2048,1408]{2,1,0} custom-call(%a), custom_call_target="tpu_custom_call"'
DOT = "%fusion.3 = bf16[2,2048,3072]{2,1,0} fusion(%a), kind=kOutput"


def made_ctx(ops, rounds=2):
    """A context whose scoped trace holds ``ops`` (name, start, dur,
    tf_op, stage) on one chip, its window [0, 1000]."""
    conf, mod = v2()
    t = spec.traffic(CELL)
    trace = tr.Trace(devices={"/device:TPU:0": [
        ScopedOp(n, s, d, tf, st) for n, s, d, tf, st in ops]})
    return types.SimpleNamespace(
        scoped_trace=trace, lo=0.0, hi=1000.0, rounds=rounds, conf=conf,
        traffic=t, cfgmod=mod, sealed=trf.sealed_count(t),
        peaks=spec.peaks("TPU v5 lite"))


def test_layer_readers_sum_their_scopes():
    ctx = made_ctx([
        (DOT, 0, 100, "jit(t)/vmap(client_sgd)/mla/dot_general", "client_sgd"),
        (DOT, 100, 50, "jit(t)/transpose(jvp(guide_sgd))/mla/dot_general",
         "guide_sgd"),
        (GMM, 200, 30, "jit(t)/client_sgd/routed_experts/jit(gmm)/pallas_call",
         "client_sgd"),
        (TGMM, 300, 20, "jit(t)/transpose(jvp(client_sgd))/routed_experts/"
         "jit(tgmm)/pallas_call", "client_sgd"),
        (GMM, 400, 10, "jit(t)/eval/routed_experts/jit(gmm)/pallas_call",
         "eval"),
        (DOT, 2000, 99, "jit(t)/client_sgd/mla/dot_general", "client_sgd"),
        (DOT, 500, 40, "jit(t)/client_sgd/dot_general", "client_sgd")])
    assert spec.metric_module("mla_ms").read(ctx) == pytest.approx(
        1e3 * 150e-9 / 2)
    assert spec.metric_module("routed_experts_ms").read(ctx) == \
        pytest.approx(1e3 * 60e-9 / 2)
    conf, t = ctx.conf, ctx.traffic
    mod = ctx.cfgmod
    f = [a + b for a, b in zip(mod.expert_work(conf, t, 2),
                               mod.expert_work(conf, t, ctx.sealed))]
    least = max(3 * 4 * 2 * f[0] / 197e12, 3 * 4 * 2 * f[1] / 819e9)
    assert spec.metric_module("experts_roofline").read(ctx) == \
        pytest.approx(100 * least / 50e-9)


def test_layer_readers_read_nothing_without_the_layers():
    """A program without the layers (the parent commit's, or another
    cell's) gives no reading, and does not raise."""
    ctx = made_ctx([(DOT, 0, 100, "jit(t)/client_sgd/dot_general",
                     "client_sgd")])
    for m in ("mla_ms", "routed_experts_ms", "experts_roofline"):
        assert spec.metric_module(m).read(ctx) is None
    cell = types.SimpleNamespace(
        conf=spec.config(CONF_NAME), traffic=spec.traffic(CELL),
        cfgmod=spec.config_module(CONF_NAME), n_params=1)
    probe = run.Context(tr.load(PROBE), cell, 3, spec.peaks("TPU v5 lite"), 1)
    probe.trace_path = str(PROBE)
    for m in ("mla_ms", "routed_experts_ms", "experts_roofline"):
        assert spec.metric_module(m).read(probe) is None
