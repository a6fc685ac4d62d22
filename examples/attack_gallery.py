"""Attack gallery — reproduce Fig. 2's separation: plot (ASCII) the
C1 x C2 similarity product per client over training under each attack.
Benign clients hover near +1; Byzantine clients go negative or explode.

    PYTHONPATH=src python examples/attack_gallery.py
"""
import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.attacks import AttackConfig
from repro.data import FederatedData, make_mnist_like, partition_sorted_shards
from repro.fl import FLConfig, Federation, run_federated_training
from repro.fl.small_models import mlp3
from repro.optim import inv_sqrt_lr


def main():
    enable_compile_cache()
    x, y = make_mnist_like(jax.random.PRNGKey(0), 4600)
    tx, ty = make_mnist_like(jax.random.PRNGKey(9), 500)
    data = FederatedData.from_partitions(partition_sorted_shards(x, y, 23), 10)
    model = mlp3()

    for attack in ("sign_flip", "label_flip", "same_value"):
        cfg = FLConfig(rounds=30, aggregator="diversefl",
                       attack=AttackConfig(kind=attack, sigma=1e4),
                       batch_size=50, eval_every=5, l2=0.0005)
        fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
        h = run_federated_training(model, fed, cfg, inv_sqrt_lr(0.05))
        byz = np.asarray(fed.byz_mask)
        c = np.stack(h["c1c2"])              # (evals, 23)
        print(f"\n=== attack: {attack} — C1xC2 per client "
              f"(last eval; B=Byzantine) ===")
        for j in range(23):
            tag = "B" if byz[j] else " "
            val = c[-1, j]
            bar = "#" * min(40, int(abs(val) * 20))
            side = "-" if val < 0 else "+"
            print(f"  client {j:2d}{tag} {val:+8.3f} {side}{bar}")


if __name__ == "__main__":
    main()
