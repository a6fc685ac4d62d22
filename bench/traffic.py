"""The one traffic generator: a federation and its job from a data file.

A traffic file (``bench/traffic/<name>.json``) gives the federation's
shape (clients, Byzantine count and attack, data per client, the sealed
share, the test set), the job (local steps, batch, learning rate, rounds
per call) and the engine's switches.  ``data`` says what a client holds:

* ``images``: CIFAR-like 32x32x3 images in 10 classes, each class an
  anchored random template plus noise, cut into clients by the paper's
  sort-by-class partition (Sec. IV-A), so every client holds about one
  class;
* ``tokens``: Zipf-distributed token sequences with a random shift per
  sequence, ``seq_tokens`` long, split into (first ``seq_tokens - 1``
  tokens, last token), the layout ``fl/zoo.make_zoo_data`` gives.

The data set, the sealed sample and the test set are drawn from the
file's ``data_seed``: the engine captures them into its compiled
program, so a data set that moved with ``--seed`` would make every run
compile afresh.  ``--seed`` draws the weights and the training key
chain, and with it which rows every client and every round uses.

The generators are copies of ``repro.data.synthetic`` and
``repro.data.partition`` as they stood when the benchmark was defined,
so that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

IMAGE_SHAPE = (32, 32, 3)
N_CLASSES = 10


def root_key(seed: int):
    """A key from any non-negative whole number, also above 2**32."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def seed_keys(seed: int) -> dict:
    """The run's keys: the weights and the training key chain."""
    kp, kt = jax.random.split(root_key(seed))
    return {"params": kp, "train": kt}


def data_keys(traffic: dict) -> dict:
    kd, kt, kf = jax.random.split(root_key(traffic["data_seed"]), 3)
    return {"data": kd, "test": kt, "federation": kf}


# ----------------------------------------------------------------------
# generators (copies of repro.data.synthetic / repro.data.partition)
# ----------------------------------------------------------------------

def classification(key, n: int, n_classes: int, dim: int, noise: float,
                   template_seed: int = 1234):
    """x = T[y] + noise * N(0, I), templates from a fixed seed so every
    split shares the class structure."""
    k2, k3 = jax.random.split(key, 2)
    templates = jax.random.normal(
        jax.random.PRNGKey(template_seed + dim), (n_classes, dim))
    y = jax.random.randint(k2, (n,), 0, n_classes)
    x = templates[y] + noise * jax.random.normal(k3, (n, dim))
    return x.astype(jnp.float32), y.astype(jnp.int32)


def cifar_like(key, n: int):
    x, y = classification(key, n, N_CLASSES, int(np.prod(IMAGE_SHAPE)),
                          noise=0.6)
    return x.reshape((n,) + IMAGE_SHAPE), y


def token_stream(key, n_seqs: int, seq_len: int, vocab: int,
                 zipf_a: float = 1.2):
    """Zipf ranks plus a random shift per sequence, modulo the vocab."""
    k1, k2 = jax.random.split(key)
    u = jax.random.uniform(k1, (n_seqs, seq_len), minval=1e-6, maxval=1.0)
    ranks = jnp.floor(jnp.exp(jnp.log(u) / (-zipf_a + 1e-9))) % vocab
    shift = jax.random.randint(k2, (n_seqs, 1), 0, vocab)
    return ((ranks.astype(jnp.int32) + shift) % vocab).astype(jnp.int32)


def sorted_shards(x, y, n_clients: int):
    """Sort by class (stable), cut into n_clients contiguous equal
    parts: (N, n // N, ...) stacks."""
    order = np.argsort(np.asarray(y), kind="stable")
    per = len(order) // n_clients
    order = order[:per * n_clients]
    xs = np.asarray(x)[order].reshape((n_clients, per) + x.shape[1:])
    ys = np.asarray(y)[order].reshape(n_clients, per)
    return jnp.asarray(xs), jnp.asarray(ys)


def federation_data(traffic: dict, vocab: int | None = None) -> dict:
    """Client stacks x (N, n, ...), y (N, n) and the test split."""
    keys = data_keys(traffic)
    n, per = traffic["n_clients"], traffic["per_client"]
    if traffic["data"] == "images":
        x, y = cifar_like(keys["data"], n * per)
        x, y = sorted_shards(x, y, n)
        tx, ty = cifar_like(keys["test"], traffic["n_test"])
        n_classes = N_CLASSES
    elif traffic["data"] == "tokens":
        s = traffic["seq_tokens"]
        tr = token_stream(keys["data"], n * per, s, vocab)
        te = token_stream(keys["test"], traffic["n_test"], s, vocab)
        tr = tr.reshape(n, per, s)
        x, y = tr[:, :, :s - 1], tr[:, :, s - 1]
        tx, ty = te[:, :s - 1], te[:, s - 1]
        n_classes = vocab
    else:
        raise ValueError(f"unknown traffic data kind {traffic['data']!r}")
    return {"x": x, "y": y, "test_x": tx, "test_y": ty,
            "n_classes": n_classes}


def sealed_count(traffic: dict) -> int:
    """Rows each client seals into the enclave (Step 1)."""
    return max(1, int(traffic["per_client"] * traffic["sample_frac"]))


def byzantine_mask(n_clients: int, f: int) -> np.ndarray:
    """The paper's fixed Byzantine identities: f clients evenly spaced
    over the client index."""
    mask = np.zeros(n_clients, bool)
    if f > 0:
        mask[np.round(np.linspace(0, n_clients - 1, f)).astype(int)] = True
    return mask


# ----------------------------------------------------------------------
# Which rows a round uses.  Algorithm 1 leaves the draw to the system;
# these are the draws the engine makes, written out so that the
# reference follows the same rows.
# ----------------------------------------------------------------------

def round_keys(key, n_rounds: int):
    """The per-round keys of a run: ``key, sub = split(key)`` each round.
    Returns (advanced key, (n_rounds, 2) subkeys)."""
    subs = []
    for _ in range(n_rounds):
        key, sub = jax.random.split(key)
        subs.append(sub)
    return key, jnp.stack(subs)


def client_rows(sub, n_clients: int, per_client: int, rows: int):
    """(N, rows) indices each client trains on in the round of ``sub``:
    the round key splits four ways, the first splits per client, and
    each client draws ``rows`` indices with replacement."""
    kb = jax.random.split(sub, 4)[0]
    keys = jax.random.split(kb, n_clients)
    return jax.vmap(lambda k: jax.random.randint(k, (rows,), 0,
                                                 per_client))(keys)


def sealed_rows(traffic: dict):
    """(N, s) indices of each client's sealed sample (without
    replacement), drawn from the federation key."""
    k1 = jax.random.split(data_keys(traffic)["federation"])[0]
    keys = jax.random.split(k1, traffic["n_clients"])
    s = sealed_count(traffic)
    return jax.vmap(lambda k: jax.random.choice(
        k, traffic["per_client"], (s,), replace=False))(keys)
