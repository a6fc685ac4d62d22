"""Shared benchmark scaffolding: timed FL runs and CSV emission.

Every benchmark module maps to one paper table/figure and emits rows
``name,us_per_call,derived`` where us_per_call is wall-time per FL round
(or per op call) and derived is the figure's metric (accuracy, ratio...).
"""
from __future__ import annotations

import time

import jax

from repro.core.attacks import AttackConfig
from repro.data import FederatedData, make_mnist_like, partition_sorted_shards
from repro.fl import FLConfig, Federation, run_federated_training
from repro.optim import inv_sqrt_lr

ROWS = []


def emit(name: str, us_per_call: float, derived):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def mnist_like_federation(n_clients=23, n_train=4600, n_test=800, seed=0):
    x, y = make_mnist_like(jax.random.PRNGKey(seed), n_train)
    tx, ty = make_mnist_like(jax.random.PRNGKey(seed + 9), n_test)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, n_clients), 10)
    return data, tx, ty


def timed_fl_run(model, data, tx, ty, aggregator: str, attack: AttackConfig,
                 rounds: int = 60, lr0: float = 0.05, seed: int = 2, **kw):
    cfg = FLConfig(n_clients=data.n_clients, rounds=rounds,
                   aggregator=aggregator, attack=attack, batch_size=50,
                   eval_every=rounds, **kw)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(seed))
    t0 = time.time()
    hist = run_federated_training(model, fed, cfg, inv_sqrt_lr(lr0))
    dt = time.time() - t0
    return hist, fed, dt / rounds * 1e6
