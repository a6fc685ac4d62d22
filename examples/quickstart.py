"""Quickstart — the paper in two minutes.

23 clients with extreme non-IID shards, 5 Byzantine clients sign-flipping
their updates.  DiverseFL filters them with the per-client C1/C2 criteria
and matches OracleSGD; coordinate-median limps; undefended mean collapses
under a Gaussian attack.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.compile_cache import enable_compile_cache
from repro.core.attacks import AttackConfig
from repro.data import FederatedData, make_mnist_like, partition_sorted_shards
from repro.fl import (FLConfig, Federation, available_aggregators,
                      run_federated_training)
from repro.fl.small_models import softmax_regression
from repro.optim import inv_sqrt_lr


def main():
    enable_compile_cache()
    x, y = make_mnist_like(jax.random.PRNGKey(0), 4600)
    tx, ty = make_mnist_like(jax.random.PRNGKey(9), 1000)
    data = FederatedData.from_partitions(partition_sorted_shards(x, y, 23), 10)
    model = softmax_regression()

    print("registered aggregation rules:", ", ".join(available_aggregators()))
    print(f"{'aggregator':12s} {'attack':11s} {'acc':>6s} {'TPR':>5s} {'FPR':>5s}")
    for agg, attack in [("oracle", "sign_flip"), ("diversefl", "sign_flip"),
                        ("median", "sign_flip"), ("mean", "gaussian"),
                        ("diversefl", "gaussian"), ("diversefl", "label_flip")]:
        cfg = FLConfig(rounds=60, aggregator=agg,
                       attack=AttackConfig(kind=attack, sigma=1e4),
                       batch_size=50, eval_every=60)
        fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
        h = run_federated_training(model, fed, cfg, inv_sqrt_lr(0.05))
        tpr = f"{h['mask_tpr'][-1]:.2f}" if h["mask_tpr"] else "   -"
        fpr = f"{h['mask_fpr'][-1]:.2f}" if h["mask_fpr"] else "   -"
        print(f"{agg:12s} {attack:11s} {h['final_acc']:6.3f} {tpr:>5s} {fpr:>5s}")


if __name__ == "__main__":
    main()
