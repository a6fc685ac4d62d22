"""Reproduce a small Table-style results grid in ONE invocation.

The paper's tables sweep attack kind x aggregator x seed; the sweep
engine (fl/sweep.py) runs the whole grid batched — cells sharing a
trace (same attack kind + aggregator here) compile once and execute as
a single vmapped device program, seeds batched along the scenario axis,
with per-cell results bitwise-equal to running each cell alone.

    PYTHONPATH=src python examples/paper_grid.py
"""
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.attacks import AttackConfig
from repro.data import FederatedData, make_mnist_like, partition_sorted_shards
from repro.fl import (FLConfig, Federation, SweepSpec, group_cells,
                      run_federated_sweep, trace_counter)
from repro.fl.small_models import softmax_regression
from repro.optim import inv_sqrt_lr

ATTACKS = (AttackConfig(kind="gaussian", sigma=1e4),
           AttackConfig(kind="sign_flip"),
           AttackConfig(kind="label_flip"),
           AttackConfig(kind="backdoor", source_class=3, target_class=4))
AGGREGATORS = ("diversefl", "oracle", "mean", "fltrust")
SEEDS = (0, 1, 2)


def main():
    enable_compile_cache()
    x, y = make_mnist_like(jax.random.PRNGKey(0), 4600)
    tx, ty = make_mnist_like(jax.random.PRNGKey(9), 1000)
    data = FederatedData.from_partitions(partition_sorted_shards(x, y, 23), 10)
    model = softmax_regression()

    base = FLConfig(rounds=60, batch_size=50, eval_every=60)
    spec = SweepSpec(base=base, seeds=SEEDS, aggregators=AGGREGATORS,
                     attacks=ATTACKS)
    cells = spec.cells()
    fed = Federation.create(model, data, tx, ty, base, jax.random.PRNGKey(2))

    with trace_counter() as tc:
        t0 = time.time()
        results = run_federated_sweep(model, fed, spec, inv_sqrt_lr(0.05))
        dt = time.time() - t0
    compiles = tc["training"]
    print(f"{len(cells)} runs in {dt:.1f}s "
          f"({len(cells) / dt:.2f} experiments/sec), "
          f"{compiles} compiles for {len(group_cells(cells))} "
          f"structural groups\n")

    print(f"final accuracy, mean ± spread over {len(SEEDS)} seeds "
          f"(60 rounds, 23 clients, f=5):")
    header = "attack      " + "".join(f"{a:>16s}" for a in AGGREGATORS)
    print(header)
    for ai, atk in enumerate(ATTACKS):
        row = f"{atk.kind:12s}"
        for gi in range(len(AGGREGATORS)):
            # cells() order: aggregator outermost, then attack, seeds inner
            accs = [results[(gi * len(ATTACKS) + ai) * len(SEEDS) + s]
                    ["final_acc"] for s in range(len(SEEDS))]
            row += f"{np.mean(accs):10.3f}±{np.std(accs):.3f}"
        print(row)


if __name__ == "__main__":
    main()
