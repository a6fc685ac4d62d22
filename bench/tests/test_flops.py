"""The FLOP and byte functions against hand counts."""
import jax
import pytest

from bench import spec


def vgg():
    return spec.config("vgg11-paper"), spec.config_module("vgg11-paper")


def danube():
    return spec.config("danube-1.8b-cut"), spec.config_module(
        "danube-1.8b-cut")


def test_vgg11_forward_flops_by_hand():
    conf, mod = vgg()
    # 3x3 convolutions at 32, 16, 8, 8, 4, 4, 4, 4 pixels a side
    convs = [(3, 64, 32), (64, 128, 16), (128, 256, 8), (256, 256, 8),
             (256, 512, 4), (512, 512, 4), (512, 512, 4), (512, 512, 4)]
    hand = sum(2 * 9 * ci * co * s * s for ci, co, s in convs)
    hand += 2 * (512 * 4096 + 4096 * 4096 + 4096 * 10)
    assert hand == 456_605_696
    assert mod.forward_flops(conf, spec.traffic("paper-n23")) == hand


def test_vgg11_weights_match_program_layout():
    conf, mod = vgg()
    model = mod.build(conf, spec.traffic("paper-n23"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        conf["n_params"] == 28_146_762


def test_danube_layer_params_by_hand():
    conf, mod = danube()
    d, f, h, k, hd = 2560, 6912, 32, 8, 80
    attn = d * h * hd + 2 * d * k * hd + h * hd * d
    layer = attn + 3 * d * f + 2 * d                  # plus two norms
    assert layer == 69_473_280
    per_layer = sum(int(jax.numpy.prod(jax.numpy.array(s)))
                    for path, s, _ in mod._leaves(conf)
                    if path[0] not in ("embed", "lm_head", "final_norm"))
    assert per_layer == conf["num_hidden_layers"] * layer
    assert conf["published_n_params"] == 24 * layer + 2 * 32000 * d + d
    assert conf["n_params"] == 4 * layer + 2 * 32000 * d + d


def test_danube_weights_match_program_layout():
    conf, mod = danube()
    model = mod.build(conf, spec.traffic("silo-s2048"))
    assert model.param_count() == conf["n_params"]
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: mod.init_params(
        conf, spec.traffic("silo-s2048"), k), jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(got)]


@pytest.mark.parametrize("seq", [2048, 128])
def test_danube_forward_flops_by_hand(seq):
    conf, mod = danube()
    d, f, h, k, hd, v, L = 2560, 6912, 32, 8, 80, 32000, 4
    matmul = L * (d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * f) \
        + d * v
    # causal: query t sees t keys (the window of 4096 is longer)
    attn = L * 4 * h * hd * seq * (seq + 1) // 2
    traffic = dict(spec.traffic("silo-s2048"), seq_tokens=seq)
    assert mod.forward_flops(conf, traffic) == 2 * seq * matmul + attn


def test_roofline_bytes_by_hand():
    """Step 4 reads the (N, D) update and guide matrices once; Step 5
    reads the updates once and the accumulator twice per call."""
    class Ctx:
        n_params, rounds = 1000, 3
        peaks = {"hbm_bytes_per_s": 1e9}

        def __init__(self, traffic, secs):
            self.traffic, self.secs = traffic, secs

        def kernel_seconds(self, match):
            return self.secs

    dense = {"n_clients": 23, "streaming": False, "client_chunk": None}
    stream = {"n_clients": 4, "streaming": True, "client_chunk": 1}
    sim = spec.metric_module("similarity_roofline")
    fold = spec.metric_module("fold_roofline")
    need = 3 * 2 * 23 * 1000 * 4
    assert sim.read(Ctx(dense, need / 1e9)) == pytest.approx(100.0)
    need = 3 * (23 * 1000 * 4 + 2 * 1000 * 4)
    assert fold.read(Ctx(dense, 2 * need / 1e9)) == pytest.approx(50.0)
    need = 3 * (4 * 1000 * 4 + 4 * 2 * 1000 * 4)
    assert fold.read(Ctx(stream, need / 1e9)) == pytest.approx(100.0)
    assert fold.read(Ctx(stream, 0.0)) is None
