"""Federate the real model zoo: the ``models/`` + ``configs/`` stack as
first-class FL citizens of the compiled round engine (DESIGN.md §12).

The engine's model contract is ``fl/small_models.SmallModel``: ``init``,
``apply(params, x) -> logits``, ``loss(params, x, y, l2)``, an
``input_shape`` and ``n_classes``.  :class:`ZooModel` satisfies it for
any decoder-style :class:`~repro.models.ModelConfig` by casting the
paper's classification framing onto language modeling:

  * an **example** is a token sequence of length ``seq_len + 1``;
    ``x`` is its first ``seq_len`` tokens, ``y`` the final token —
    next-token prediction IS the classification task (``n_classes =
    vocab_size``), so every existing attack (label flip permutes the
    target token), metric (accuracy = next-token top-1) and eval path
    works unchanged;
  * ``loss`` is the full-sequence LM loss over the re-joined
    ``concat(x, y)`` tokens (``models.loss_fn`` — chunked vocab-sharded
    cross entropy), so local SGD trains every position, not just the
    label; ``apply`` returns the last-position next-token logits.

Token ``x`` arrays survive the enclave's f32 seal/unseal round trip
(core/tee.py stores f32) because token ids are exact in f32 up to
2^24 — far beyond any vocab — and both ``loss`` and ``apply`` cast
back to int32 at the boundary.

On a client x model mesh the params take the MODEL_AXIS partition
table's tensor-parallel placement (``sharding.place_params``) and the
engine folds the flattened updates over the sharded flat D — see
DESIGN.md §12 for the full 2D contract.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import models
from ..data.pipeline import FederatedData
from ..data.synthetic import make_token_stream
from ..models import ModelConfig


def _as_tokens(x):
    """Int32 token ids from whatever the pipeline delivered — the
    enclave seals f32 (core/tee.py), so guide batches come back float;
    ids are exact in f32 up to 2^24, so the cast is lossless."""
    return x if jnp.issubdtype(x.dtype, jnp.integer) else \
        jnp.round(x).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class ZooModel:
    """A zoo :class:`ModelConfig` wearing the SmallModel contract.

    ``loss`` accepts the FLConfig ``l2`` knob for interface parity but
    zoo runs should set ``l2=0.0`` — a ridge over 10^8 bf16 parameters
    is neither the paper's setting nor numerically meaningful, and it
    costs a full extra pass over the params per gradient."""
    name: str
    cfg: ModelConfig
    seq_len: int

    @property
    def input_shape(self) -> Tuple[int, ...]:
        return (self.seq_len,)

    @property
    def n_classes(self) -> int:
        return self.cfg.vocab_size

    def param_count(self) -> int:
        return self.cfg.param_count()

    def init(self, key):
        return models.init(key, self.cfg)

    def apply(self, params, x):
        """Last-position next-token logits, (B, vocab_size) f32 — the
        classification head the metrics stack scores."""
        return self._forward(params, _as_tokens(x))[0]

    def _forward(self, params, tok):
        out = models.apply(params, self.cfg, tok)
        lg = models.logits(params, self.cfg, out["hidden"][:, -1:, :])
        return lg[:, 0, :self.cfg.vocab_size], out["aux"]

    @property
    def expert_share(self):
        """What the expert layers hold and how they dispatch, for the
        engine's ``expert_share`` event; None without routed experts."""
        c = self.cfg
        if not c.n_experts:
            return None
        return {"held": c.n_held_experts, "routed": c.n_experts,
                "top_k": c.top_k, "dropless": c.dropless,
                "grouped": "megablox.gmm" if c.dropless else None}

    def apply_with_routing(self, params, x):
        """``apply`` plus the routing counters of a dropless expert
        share: ``held_slot_share``, the share of the token
        slots of every expert layer that land on held experts, and
        ``held_load_peak``, the largest held expert's load over the mean
        held load, the loads summed over the layers."""
        tok = _as_tokens(x)
        lg, (_, load) = self._forward(params, tok)
        load = load.astype(jnp.float32)
        layers = self.cfg.n_groups * sum(f == "moe"
                                         for _, f in self.cfg.layout)
        slots = tok.size * self.cfg.top_k * layers
        return lg, {"held_slot_share": load.sum() / slots,
                    "held_load_peak": load.max() / jnp.maximum(
                        load.mean(), 1.0)}

    def loss(self, params, x, y, l2: float = 0.0):
        """Full-sequence LM loss over ``concat(x, y)`` — every position
        trains, and the final position's target is exactly ``y``."""
        tok = jnp.concatenate(
            [_as_tokens(x), _as_tokens(y)[..., None]], axis=-1)
        nll = models.loss_fn(params, self.cfg, {"tokens": tok})
        if l2:
            nll = nll + 0.5 * l2 * sum(
                jnp.sum(jnp.square(p.astype(jnp.float32)))
                for p in jax.tree.leaves(params))
        return nll

    def accuracy(self, params, x, y, batch: int = 256):
        correct, n = 0, y.shape[0]
        for i in range(0, n, batch):
            lg = self.apply(params, x[i:i + batch])
            correct += int((jnp.argmax(lg, -1) == y[i:i + batch]).sum())
        return correct / n


def zoo_model(arch, seq_len: int = 64, smoke: bool = True) -> ZooModel:
    """A :class:`ZooModel` from an arch id (``configs.get``), or wrap an
    explicit :class:`ModelConfig` (``arch`` may be either)."""
    if isinstance(arch, ModelConfig):
        cfg = arch
    else:
        from .. import configs
        cfg = configs.get(arch, smoke=smoke)
    if cfg.is_enc_dec or cfg.has_cross:
        raise ValueError(
            f"{cfg.name!r} needs encoder/cross-attention inputs "
            f"(enc_emb/cross_emb) that the FL data pipeline does not "
            f"carry — federate a decoder-only arch, or extend "
            f"FederatedData with modality sidecars first")
    return ZooModel(name=cfg.name, cfg=cfg, seq_len=seq_len)


def make_zoo_data(key, model: ZooModel, n_clients: int, per_client: int,
                  n_test: int = 64):
    """Synthetic federated token data for ``model``: per-client stacks
    of (seq_len+1)-token examples split into (x = prefix, y = next
    token), plus a held-out test split — the zoo twin of
    ``data.make_mnist_like`` + ``FederatedData.from_partitions``."""
    total = n_clients * per_client + n_test
    toks = make_token_stream(key, total, model.seq_len + 1,
                             model.cfg.vocab_size)
    S = model.seq_len
    tr = toks[:n_clients * per_client].reshape(n_clients, per_client, S + 1)
    data = FederatedData(x=tr[:, :, :S], y=tr[:, :, S],
                         n_classes=model.cfg.vocab_size)
    te = toks[n_clients * per_client:]
    return data, te[:, :S], te[:, S]


def make_zoo_federation(model: ZooModel, cfg, key=None,
                        per_client: int = 32, n_test: int = 64):
    """Data + sealed enclave samples + SecureServer for a zoo model —
    ``Federation.create`` on synthetic token shards.  Returns the
    federation; drive it with ``run_federated_training(model, fed, cfg,
    ...)`` or a :class:`~repro.fl.engine.RoundEngine` built on a client
    x model mesh."""
    from .simulator import Federation
    if key is None:
        key = jax.random.PRNGKey(cfg.seed + 17)
    kd, kf = jax.random.split(key)
    data, tx, ty = make_zoo_data(kd, model, cfg.n_clients, per_client,
                                 n_test)
    return Federation.create(model, data, tx, ty, cfg, kf)
