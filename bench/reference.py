"""Algorithm 1, plainly: DiverseFL rounds of the reference.

One round, written from the paper (arXiv:2010.07541, Algorithm 1) and
nothing of the program: every client runs E steps of SGD from the
global weights on its rows and uploads the change (a Byzantine client
uploads it negated, the sign-flip attack); the enclave runs the same E
steps on the client's sealed sample (the guiding update, Step 3); a
client is kept iff its update points the guide's way and its length is
within (eps2, eps3) of the guide's (Eq. 2-5, Step 4); the global weights
move by the mean of the kept updates (Eq. 6, Step 5).

Weights are held at the dtype the configuration stores them in, and
everything else is float32 at ``highest`` matmul precision.  The
control is the same code in the next precision down: every stored
weight and activation rounded to it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import traffic as tr

# the nearest precision below each stated one: the step a later change
# would be tempted to take
CONTROL_DTYPE = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def rounder(dtype):
    """x -> x rounded to ``dtype``, kept in float32; None: identity."""
    if dtype is None or jnp.dtype(dtype) == jnp.float32:
        return lambda x: x
    dt = jnp.dtype(dtype)

    def q(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return x.astype(dt).astype(jnp.float32)
    return q


# faults that a later change could bring into the round, planted here to
# read what each does to the compared numbers: half of every client's
# batch left out (the mean over the rest), and every kept update folded
# at half its weight
FAULTS = ("half_batch", "half_fold")


def make_round(loss, traffic: dict, store_dtype, act_dtype, fault=None):
    """Jitted ``round(params, x, y, gx, gy, rows, byz, lr) -> (params,
    keep (N,), c1c2 (N,), cos (N,))``; ``params`` come back in their own
    dtypes; ``cos`` is each update's cosine with its guide, whose sign
    is C1.  ``fault`` plants one of :data:`FAULTS`."""
    E, m = traffic["local_steps"], traffic["batch_size"]
    eps1, eps2, eps3 = traffic["eps"]
    flip = traffic["attack"] == "sign_flip"
    store, q = rounder(store_dtype), rounder(act_dtype)
    f32 = jnp.float32

    def sgd(p, xs, ys, lr):
        """E plain SGD steps from p; returns p - theta_E."""
        def step(th, b):
            g = jax.grad(loss)(th, b[0], b[1], q)
            return jax.tree.map(lambda t, gg: store(t - lr * gg), th, g), None
        th, _ = jax.lax.scan(step, p, (xs, ys))
        return jax.tree.map(jnp.subtract, p, th)

    def vdot(a, b):
        return sum(jnp.sum(u * v) for u, v in zip(jax.tree.leaves(a),
                                                  jax.tree.leaves(b)))

    def round_fn(params, x, y, gx, gy, rows, byz, lr):
        dtypes = jax.tree.map(lambda a: a.dtype, params)
        p = jax.tree.map(lambda a: store(a.astype(f32)), params)

        def client(carry, inp):
            acc, count = carry
            xi, yi, ri, gxi, gyi, bi = inp
            xb = xi[ri].reshape((E, m) + xi.shape[1:])
            yb = yi[ri].reshape(E, m)
            if fault == "half_batch":
                xb, yb = xb[:, :max(1, m // 2)], yb[:, :max(1, m // 2)]
            gu = sgd(p, jnp.broadcast_to(gxi, (E,) + gxi.shape),
                     jnp.broadcast_to(gyi, (E,) + gyi.shape), lr)
            u = sgd(p, xb, yb, lr)
            if flip:
                u = jax.tree.map(lambda v: jnp.where(bi, -v, v), u)
            dot, zz, gg = vdot(u, gu), vdot(u, u), vdot(gu, gu)
            ratio = zz / jnp.maximum(gg, 1e-30)
            keep = (dot > eps1) & (ratio > eps2 ** 2) & (ratio < eps3 ** 2)
            w = 0.5 if fault == "half_fold" else 1.0
            acc = jax.tree.map(lambda a, v: a + jnp.where(keep, w * v, 0.0),
                               acc, u)
            cos = dot / jnp.sqrt(jnp.maximum(zz * gg, 1e-30))
            return (acc, count + keep.astype(f32)), (
                keep, jnp.sign(dot) * jnp.sqrt(ratio), cos)

        zero = jax.tree.map(jnp.zeros_like, p)
        (acc, count), (keep, c1c2, cos) = jax.lax.scan(
            client, (zero, f32(0.0)), (x, y, rows, gx, gy, byz))
        new = jax.tree.map(lambda a, s: store(a - s / jnp.maximum(count, 1.0)),
                           p, acc)
        return (jax.tree.map(lambda a, d: a.astype(d), new, dtypes), keep,
                c1c2, cos)

    jitted = jax.jit(round_fn)

    def run(*args):
        with jax.default_matmul_precision("highest"):
            return jitted(*args)
    return run


@jax.jit
def leaf_norms(a, b):
    """Per-leaf ||a - b|| in float32, in the pytree's leaf order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def active_round_keys(train_key, rounds_per_call: int):
    """Subkeys of the three rounds that move the weights in set-up: the
    last round of the first call and the last two of the second (the
    other rounds of those calls run at learning rate 0)."""
    R = rounds_per_call
    _, subs = tr.round_keys(train_key, 2 * R)
    return [subs[R - 1], subs[2 * R - 2], subs[2 * R - 1]]


class Reference:
    """The reference for one cell: its data, and one compiled round per
    precision and fault, kept for every seed this process reads."""

    def __init__(self, cfgmod, conf: dict, traffic: dict, data: dict):
        self.cfgmod, self.conf, self.traffic = cfgmod, conf, traffic
        n = traffic["n_clients"]
        take = jax.vmap(lambda a, i: a[i])
        sealed = tr.sealed_rows(traffic)
        self.x, self.y = data["x"], data["y"]
        self.gx, self.gy = take(self.x, sealed), take(self.y, sealed)
        self.byz = jnp.asarray(tr.byzantine_mask(n, traffic["f"]))
        self._steps = {}

    def step(self, dtype=None, fault=None):
        if (dtype, fault) not in self._steps:
            store = self.conf["param_dtype"] if dtype is None else dtype
            self._steps[dtype, fault] = make_round(
                self.cfgmod.reference_loss(self.conf, self.traffic),
                self.traffic, store, dtype, fault)
        return self._steps[dtype, fault]

    def readings(self, seed: int, dtype=None, fault=None) -> dict:
        """The reference's readings of the three set-up rounds: per-leaf
        norms of the first round's step and of the change after three
        rounds, and the keep mask and C1*C2 of rounds 1 and 3.
        ``dtype`` None is the reference; otherwise every stored weight
        and activation is rounded to ``dtype`` (the control); ``fault``
        plants one of :data:`FAULTS`.  ``seconds`` holds each round's
        wall time, the first with its compile, and ``cos`` each client's
        cosine with its guide in all three rounds."""
        import time
        t = self.traffic
        keys = tr.seed_keys(seed)
        step = self.step(dtype, fault)
        n, per = t["n_clients"], t["per_client"]
        rows_per_round = t["local_steps"] * t["batch_size"]
        lr = jnp.float32(t["lr"])
        p0 = self.cfgmod.init_params(self.conf, t, keys["params"])
        p = p0
        out = {"keep": [], "c1c2": [], "cos": [], "seconds": []}
        for i, sub in enumerate(active_round_keys(keys["train"],
                                                  t["rounds_per_call"])):
            t0 = time.perf_counter()
            rows = tr.client_rows(sub, n, per, rows_per_round)
            p, keep, c1c2, cos = step(p, self.x, self.y, self.gx, self.gy,
                                      rows, self.byz, lr)
            out["cos"].append(np.asarray(cos))
            if i == 0:
                out["grad1"] = np.asarray(leaf_norms(p0, p))
            if i != 1:
                out["keep"].append(np.asarray(keep))
                out["c1c2"].append(np.asarray(c1c2))
            jax.block_until_ready(p)
            out["seconds"].append(time.perf_counter() - t0)
        out["change3"] = np.asarray(leaf_norms(p, p0))
        for k in ("keep", "c1c2", "cos"):
            out[k] = np.stack(out[k])
        return out
