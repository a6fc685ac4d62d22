"""Drive the system under test: build a cell's federation and engine,
take the program's readings of the first three rounds, and run calls of
``RoundEngine.run_training`` back to back.

The engine is built once per process; the weights and the training key
chain come from ``--seed``.  Set-up's two calls go through the window's
own compiled program: the first runs its rounds at learning rate 0 but
the last, the second at 0 but the last two, so that the weights move in
exactly three rounds whose every reading the reference can follow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import correct, reference
from . import traffic as tr


class Cell:
    """One cell's federation and compiled engine."""

    def __init__(self, conf: dict, traffic: dict, cfgmod):
        from repro.core.attacks import AttackConfig
        from repro.core.diversefl import DiverseFLConfig
        from repro.data import FederatedData
        from repro.fl import FLConfig, Federation, RoundEngine
        self.conf, self.traffic, self.cfgmod = conf, traffic, cfgmod
        t = traffic
        self.R = int(t["rounds_per_call"])
        if self.R < 2:
            raise ValueError("rounds_per_call must be at least 2")
        self.model = cfgmod.build(conf, t)
        self.data = tr.federation_data(t, conf.get("vocab_size"))
        eps1, eps2, eps3 = t["eps"]
        self.flcfg = FLConfig(
            n_clients=t["n_clients"], f=t["f"], rounds=self.R,
            local_steps=t["local_steps"], batch_size=t["batch_size"],
            l2=t["l2"], aggregator="diversefl",
            attack=AttackConfig(kind=t["attack"]),
            dfl=DiverseFLConfig(eps1=eps1, eps2=eps2, eps3=eps3),
            sample_frac=t["sample_frac"], streaming=t["streaming"],
            client_chunk=t["client_chunk"],
            use_kernel_stats=t["use_kernel_stats"],
            use_kernel_agg=t["use_kernel_agg"], eval_every=self.R)
        fdata = FederatedData(x=self.data["x"], y=self.data["y"],
                              n_classes=self.data["n_classes"])
        self.fed = Federation.create(
            self.model, fdata, self.data["test_x"], self.data["test_y"],
            self.flcfg, tr.data_keys(t)["federation"])
        self.engine = RoundEngine(self.model, self.fed, self.flcfg,
                                  eval_every=self.R)
        lr = float(t["lr"])
        zeros = [0.0] * self.R
        self.lrs = {"first": jnp.asarray(zeros[:-1] + [lr], jnp.float32),
                    "second": jnp.asarray(zeros[:-2] + [lr, lr], jnp.float32),
                    "window": jnp.full((self.R,), lr, jnp.float32)}
        self.n_params = int(sum(np.prod(a.shape) for a in jax.tree.leaves(
            jax.eval_shape(self.model.init, jax.random.PRNGKey(0)))))
        # one compiled maker for the three copies set-up needs (each
        # call donates the weights it is given)
        self._make = jax.jit(functools.partial(cfgmod.init_params, conf,
                                               traffic))

    def params(self, seed: int):
        """The seed's weights, made by the benchmark in the program's
        layout; the layout must be the program's own."""
        p = self._make(tr.seed_keys(seed)["params"])
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           p)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("the configuration's weights do not match the "
                             "program's parameter layout")
        return p

    def call(self, params, key, lrs):
        """One call of the window's program; returns (params, key,
        metrics) with nothing fetched."""
        p, key, metrics, _ = self.engine.run_training(params, key, lrs)
        return p, key, metrics

    def prime(self, seed: int):
        """Set-up's two calls.  Returns (params, key, readings) where the
        readings are the program's, as :mod:`bench.correct` compares."""
        key = tr.seed_keys(seed)["train"]
        p, key, m1 = self.call(self.params(seed), key, self.lrs["first"])
        grad1 = reference.leaf_norms(self.params(seed), p)
        p, key, m2 = self.call(p, key, self.lrs["second"])
        change3 = reference.leaf_norms(p, self.params(seed))
        m1, m2, grad1, change3, fin = jax.device_get(
            (m1, m2, grad1, change3, all_finite(p)))
        c1c2 = np.stack([np.asarray(m1["c1c2"][-1]),
                         np.asarray(m2["c1c2"][-1])])
        readings = {"grad1": np.asarray(grad1),
                    "change3": np.asarray(change3), "c1c2": c1c2,
                    "keep": correct.keep_from_c1c2(c1c2, self.traffic["eps"]),
                    "finite": bool(fin)}
        return p, key, readings


@jax.jit
def all_finite(tree):
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(x))
                              for x in jax.tree.leaves(tree)]))


def metrics_finite(metrics) -> bool:
    return all(bool(np.all(np.isfinite(np.asarray(v, np.float64))))
               for v in jax.tree.leaves(metrics))
