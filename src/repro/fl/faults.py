"""Device-malfunction models for async federated rounds (DESIGN.md §13).

The paper's threat model has two axes: *adversarial* clients (the
attack registry in ``core/attacks.py``) and *faulty* clients — devices
that malfunction during training.  This module is the fault axis:

  * :class:`FaultConfig` — a frozen, hashable config describing one
    malfunction model, carried on ``FLConfig.fault`` so sweeps treat it
    structurally (same contract as ``AttackConfig``);
  * :func:`make_cohort_chain` — the precomputed ``(R, N)`` per-round
    participation masks threaded as a traced scenario operand (the PR-5
    byz-mask plumbing is the template: magnitudes batch, shapes don't);
  * :func:`draw_faults` / :func:`corrupt_updates` — the per-round fault
    draw from the scan's RNG chain and the client-boundary corruption,
    both pure jittable functions of traced operands.

Faults COMPOSE with attacks: a Byzantine client can also straggle, and
the contract (pinned by tests/test_async.py) is that Eq. 6 tags its
update when it *lands*, not that it silently vanishes from the byz-mask
accounting.

Kinds:

``none``
    No faults.  The async machinery is structurally absent — the
    round body traces the exact PR-9 jaxpr.
``dropout``
    With per-client probability ``rate`` each round, the update never
    arrives: the client leaves the round's live set (zero fold weight
    via the ``live`` context channel; the no-op-round semantics of an
    empty cohort are defined by the fold's ``floor``).
``straggler``
    With probability ``rate``, the client finishes ``delay`` rounds
    late.  Its update enters the bounded-staleness buffer in the scan
    carry and folds through the same AggState monoid when it lands,
    with guides recomputed at the *landing* round (Eq. 6 filters
    stale-and-diverged updates per client, no cohort vote).
``intermittent``
    With probability ``rate``, the update is corrupted in flight:
    ``mode="nan"`` / ``"inf"`` burst the whole update non-finite
    (caught by the streaming fold's non-finite guard), ``"bitflip"``
    scales it by ``bitflip_scale`` — the float image of a flipped
    exponent bit (caught by Eq. 6's C2 norm-ratio band).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

FAULT_KINDS = ("none", "dropout", "straggler", "intermittent")
CORRUPTION_MODES = ("nan", "inf", "bitflip")


class DegenerateCohortError(ValueError):
    """A cohort chain selects zero clients in some round.

    Raised host-side at scenario construction for *explicit* chains.
    Runtime-empty live sets (cohort minus dropouts) are NOT an error:
    the weighted-mean fold's ``floor`` makes an empty round a defined
    no-op (delta = 0/floor = 0) — see DESIGN.md §13.
    """


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """One device-malfunction model.

    ``rate`` is the per-client, per-round malfunction probability,
    drawn i.i.d. from the scan's RNG chain — the paper's "devices
    become faulty during training", not a fixed faulty set.  ``delay``
    (stragglers) is how many rounds late the update lands;
    ``mode``/``bitflip_scale`` shape the intermittent corruption.
    """
    kind: str = "none"
    rate: float = 0.0
    delay: int = 1
    mode: str = "nan"
    bitflip_scale: float = 2.0 ** 7

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(
                f"unknown fault kind {self.kind!r}; registered kinds: "
                f"{FAULT_KINDS}")
        if not (0.0 <= float(self.rate) <= 1.0):
            raise ValueError(
                f"fault rate must be in [0, 1], got {self.rate}")
        if isinstance(self.delay, bool) or not isinstance(self.delay, int) \
                or self.delay < 1:
            raise ValueError(
                f"fault delay must be a positive int, got {self.delay!r}")
        if self.mode not in CORRUPTION_MODES:
            raise ValueError(
                f"unknown corruption mode {self.mode!r}; registered "
                f"modes: {CORRUPTION_MODES}")


def cohort_size(n_clients: int, participation: float) -> int:
    """Per-round cohort size — ceil like ``FLConfig.n_selected``, never
    zero (an all-zero *expected* cohort is a config error upstream)."""
    return max(1, min(n_clients, math.ceil(participation * n_clients)))


def make_cohort_chain(n_clients: int, rounds: int, participation: float,
                      key) -> jnp.ndarray:
    """Precompute the ``(R, N)`` boolean cohort-mask chain.

    Each round draws a fresh ``cohort_size`` subset without replacement
    and scatters it to an ``(N,)`` mask — the whole chain is one traced
    scenario operand, so per-round resampling costs zero retraces and
    sweeps batch chains along a leading axis exactly like the byz mask.
    """
    c = cohort_size(n_clients, participation)

    def row(k):
        sel = jax.random.choice(k, n_clients, (c,), replace=False)
        return jnp.zeros((n_clients,), bool).at[sel].set(True)

    return jax.vmap(row)(jax.random.split(key, rounds))


def validate_cohort_chain(chain, n_clients: int, rounds: int) -> None:
    """Host-side named-error validation for an explicit cohort chain."""
    chain = jnp.asarray(chain)
    if chain.shape != (rounds, n_clients):
        raise DegenerateCohortError(
            f"cohort chain shape {chain.shape} != (rounds, n_clients) = "
            f"({rounds}, {n_clients})")
    per_round = jnp.sum(chain.astype(jnp.int32), axis=1)
    if bool(jnp.any(per_round == 0)):
        bad = int(jnp.argmax(per_round == 0))
        raise DegenerateCohortError(
            f"cohort chain selects zero clients in round {bad}; every "
            "round needs at least one participant (dropout faults may "
            "still empty a round at runtime — that is a defined no-op, "
            "see DESIGN.md §13)")


def draw_faults(key, n_clients: int, fcfg: FaultConfig) -> jnp.ndarray:
    """Per-round i.i.d. fault draw: ``(N,)`` bool, True = malfunctions
    this round.  Pure function of the traced ``key`` — rides the scan's
    per-round subkey chain, so fault patterns are reproducible and
    sweep-batchable without retraces."""
    if fcfg.kind == "none" or fcfg.rate <= 0.0:
        return jnp.zeros((n_clients,), bool)
    return jax.random.uniform(key, (n_clients,)) < jnp.float32(fcfg.rate)


def corrupt_updates(U, fault_rows, fcfg: FaultConfig):
    """Apply intermittent corruption at the client boundary.

    ``U`` is a block of flat updates (``(c, D)`` or blocked
    ``(c, ms, L)``), ``fault_rows`` the per-row fault bits.  NaN/Inf
    bursts overwrite the whole row; bitflip scales it (one flipped
    exponent bit multiplies the magnitude by a power of two).  Rows
    with ``fault_rows == False`` pass through bitwise untouched
    (``where`` with a False predicate is the identity)."""
    if fcfg.kind != "intermittent":
        return U
    rows = fault_rows.reshape(fault_rows.shape + (1,) * (U.ndim - 1))
    with jax.named_scope("attack"):
        if fcfg.mode == "nan":
            bad = jnp.full_like(U, jnp.nan)
        elif fcfg.mode == "inf":
            bad = jnp.full_like(U, jnp.inf)
        else:
            bad = U * jnp.asarray(fcfg.bitflip_scale, U.dtype)
        return jnp.where(rows, bad, U)


def init_async_state(cfg, flat_shape) -> Optional[dict]:
    """Build the async scan-carry state, or ``None`` when the config's
    async machinery is off (the carry is then structurally the PR-9
    carry — the jaxpr-identity contract of DESIGN.md §13).

    ``flat_shape`` is the flat-update shape: ``(d,)`` or the blocked
    ``(ms, L)`` at model_shards > 1.  The buffer is an O(buffer·D)
    pending slab: ``u`` holds the late updates, ``cid`` their client
    ids, ``ttl`` rounds until landing, ``on`` slot occupancy, and ``r``
    the round counter that indexes the cohort chain."""
    if not cfg.async_rounds:
        return None
    state = {"r": jnp.zeros((), jnp.int32)}
    b = cfg.staleness_buffer
    if b > 0:
        state.update(
            u=jnp.zeros((b,) + tuple(flat_shape), jnp.float32),
            cid=jnp.zeros((b,), jnp.int32),
            ttl=jnp.zeros((b,), jnp.int32),
            on=jnp.zeros((b,), bool),
        )
    return state
