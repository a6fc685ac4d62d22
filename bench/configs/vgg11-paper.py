"""VGG-11 with group norm (arXiv:2010.07541 Table I) on 32x32x3 images.

The plain reference below is written from the configuration file alone:
convolutions, group norm, relu, max pools, a global average pool and a
three-layer head, in float32.  ``q`` rounds every stored activation and
every operand of a convolution or matrix product, so the same code
computes the reference (``q`` the identity, at ``highest`` precision)
and its lower-precision control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def build(conf: dict, traffic: dict):
    from repro.fl.small_models import vgg11
    return vgg11(n_classes=conf["n_classes"],
                 gn_group_channels=conf["group_norm_channels"])


def _leaves(conf: dict):
    """(name, shape, kind) of every weight, in the program's layout."""
    k = conf["conv_kernel"]
    out = []
    for i, (ci, co) in enumerate(conf["conv_channels"]):
        out += [(f"c{i}", (k, k, ci, co), "conv"), (f"gs{i}", (co,), "one"),
                (f"gb{i}", (co,), "zero")]
    w = conf["fc_widths"]
    for j in range(len(w) - 1):
        out += [(f"w{j + 1}", (w[j], w[j + 1]), "dense"),
                (f"b{j + 1}", (w[j + 1],), "zero")]
    return out


def init_params(conf: dict, traffic: dict, key):
    """Glorot-uniform weights from ``key``, one jitted call on device."""
    leaves = _leaves(conf)

    @jax.jit
    def make(key):
        p = {}
        for i, (name, shape, kind) in enumerate(leaves):
            if kind == "one":
                p[name] = jnp.ones(shape, jnp.float32)
            elif kind == "zero":
                p[name] = jnp.zeros(shape, jnp.float32)
            else:
                rf = shape[0] * shape[1] if kind == "conv" else 1
                fan_in, fan_out = rf * shape[-2], rf * shape[-1]
                lim = (6.0 / (fan_in + fan_out)) ** 0.5
                p[name] = jax.random.uniform(jax.random.fold_in(key, i),
                                             shape, jnp.float32, -lim, lim)
        return p
    return make(key)


def reference_loss(conf: dict, traffic: dict):
    """Plain ``loss(params, x, y, q)``: mean cross entropy plus the l2
    term the job states, in float32."""
    chans = conf["conv_channels"]
    pools = set(conf["pool_after"])
    gch, eps = conf["group_norm_channels"], conf["group_norm_eps"]
    n_fc = len(conf["fc_widths"]) - 1
    l2 = traffic["l2"]

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def group_norm(x, scale, shift, groups):
        n, h, w, c = x.shape
        g = x.reshape(n, h, w, groups, c // groups)
        mu = g.mean((1, 2, 4), keepdims=True)
        var = ((g - mu) ** 2).mean((1, 2, 4), keepdims=True)
        g = (g - mu) / jnp.sqrt(var + eps)
        return g.reshape(n, h, w, c) * scale + shift

    def max_pool(x):
        n, h, w, c = x.shape
        return x.reshape(n, h // 2, 2, w // 2, 2, c).max((2, 4))

    def loss(p, x, y, q):
        h = q(x)
        for i, (_, co) in enumerate(chans):
            h = q(conv(h, q(p[f"c{i}"])))
            h = q(jax.nn.relu(group_norm(h, q(p[f"gs{i}"]), q(p[f"gb{i}"]),
                                         co // gch)))
            if i in pools:
                h = max_pool(h)
        h = q(h.mean((1, 2)))
        for j in range(1, n_fc + 1):
            h = q(h @ q(p[f"w{j}"]) + q(p[f"b{j}"]))
            if j < n_fc:
                h = jax.nn.relu(h)
        logp = jax.nn.log_softmax(h.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
        if l2:
            nll = nll + 0.5 * l2 * sum(jnp.sum(v * v)
                                       for v in jax.tree.leaves(p))
        return nll
    return loss


def forward_flops(conf: dict, traffic: dict) -> float:
    """Model FLOPs of one image's forward pass: 2 per multiply-add of
    the convolutions and the head; norms, pools and relus not counted."""
    h, w = conf["input_shape"][:2]
    k = conf["conv_kernel"]
    total = 0
    for i, (ci, co) in enumerate(conf["conv_channels"]):
        total += 2 * k * k * ci * co * h * w
        if i in conf["pool_after"]:
            h, w = h // 2, w // 2
    fc = conf["fc_widths"]
    total += sum(2 * a * b for a, b in zip(fc[:-1], fc[1:]))
    return float(total)
