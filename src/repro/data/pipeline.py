"""Federated data pipeline: per-client datasets padded to a common size so
the whole federation stacks into (N, n_i, ...) arrays and client training
can be vmapped; plus the once-before-training enclave sample draw (Step 1).
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class ClientDataset:
    x: jnp.ndarray
    y: jnp.ndarray

    @property
    def n(self) -> int:
        return int(self.y.shape[0])


@dataclasses.dataclass
class FederatedData:
    """Stacked federation: x (N, n, ...), y (N, n); n = min client size."""
    x: jnp.ndarray
    y: jnp.ndarray
    n_classes: int

    @property
    def n_clients(self) -> int:
        return int(self.y.shape[0])

    @property
    def per_client(self) -> int:
        return int(self.y.shape[1])

    @classmethod
    def from_partitions(cls, parts: List[Tuple[jnp.ndarray, jnp.ndarray]],
                        n_classes: int):
        n = min(int(p[1].shape[0]) for p in parts)
        x = jnp.stack([p[0][:n] for p in parts])
        y = jnp.stack([p[1][:n] for p in parts])
        return cls(x=x, y=y, n_classes=n_classes)

    def minibatch(self, key, batch_size: int):
        """One random mini-batch per client: (N, m, ...), (N, m)."""
        keys = jax.random.split(key, self.n_clients)

        def take(k, xs, ys):
            idx = jax.random.randint(k, (batch_size,), 0, self.per_client)
            return xs[idx], ys[idx]
        return jax.vmap(take)(keys, self.x, self.y)

    def enclave_samples(self, key, frac: float):
        """Step 1: uniform sample M_j^0 (size s = frac * n_j) per client."""
        s = max(1, int(self.per_client * frac))
        keys = jax.random.split(key, self.n_clients)

        def take(k, xs, ys):
            idx = jax.random.choice(k, self.per_client, (s,), replace=False)
            return xs[idx], ys[idx]
        return jax.vmap(take)(keys, self.x, self.y)


def batch_iterator(key, x, y, batch_size: int):
    n = y.shape[0]
    while True:
        key, sub = jax.random.split(key)
        idx = jax.random.randint(sub, (batch_size,), 0, n)
        yield x[idx], y[idx]
