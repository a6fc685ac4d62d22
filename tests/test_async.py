"""Async federated rounds (ISSUE 10): cohorts, faults, bounded staleness.

Pinned here, per DESIGN.md §13:

* the fault registry's named validation errors (``FaultConfig``,
  ``FLConfig`` async knobs, ``DegenerateCohortError``);
* the cohort chain: shape, per-round size, determinism, validation;
* the compatibility tiers — trivial async (full cohort, no faults,
  zero buffer) bitwise-equal to the baseline engine path, and the
  engine vs the per-round reference loop agreeing bitwise under real
  async;
* the non-finite guard: NaN/Inf rows weighted out of the streaming
  fold (values sanitized, not just weights), popcounted into the
  telemetry block, inert on finite data;
* staleness bookkeeping: stragglers buffered then folded (buffer > 0)
  or expired (buffer 0), committed to the audit chain;
* attack x fault composition: a Byzantine straggler is judged by
  Eq. 6 where it LANDS, with ``mask_rates(..., valid=)`` restricting
  the TPR/FPR accounting to rows that actually participated;
* ``round_telemetry_bytes`` pricing the async telemetry fields;
* ``SweepSpec.faults`` / ``.stalenesses`` as structural axes, each
  cell bitwise-equal to its solo run.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attacks import AttackConfig
from repro.data import FederatedData, make_classification
from repro.data.partition import partition_sorted_shards
from repro.fl import (DegenerateCohortError, Federation, FLConfig,
                      FaultConfig, SweepSpec, run_federated_sweep,
                      run_federated_training, structural_key, telemetry)
from repro.fl.faults import (cohort_size, corrupt_updates, draw_faults,
                             make_cohort_chain, validate_cohort_chain)
from repro.fl.metrics import mask_rates, round_telemetry_bytes
from repro.fl.server import AggregationContext
from repro.fl.small_models import softmax_regression
from repro.fl.streaming import get_streaming, stream_aggregate
from repro.fl.sweep import group_cells
from repro.optim import inv_sqrt_lr

N, F, DIM, NC = 23, 5, 8, 4
FED_KEY = jax.random.PRNGKey(2)


@pytest.fixture(scope="module")
def fed_data():
    x, y = make_classification(jax.random.PRNGKey(0), N * 16, NC, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N), NC)
    tx, ty = make_classification(jax.random.PRNGKey(9), 64, NC, DIM)
    return softmax_regression(input_dim=DIM, n_classes=NC), data, tx, ty


def _cfg(**kw):
    kw.setdefault("n_clients", N)
    kw.setdefault("f", F)
    kw.setdefault("rounds", 4)
    kw.setdefault("eval_every", 2)
    kw.setdefault("batch_size", 4)
    kw.setdefault("l2", 0.0)
    kw.setdefault("aggregator", "diversefl")
    kw.setdefault("streaming", True)
    kw.setdefault("attack", AttackConfig(kind="sign_flip"))
    return FLConfig(**kw)


def _train(fed_data, cfg):
    model, data, tx, ty = fed_data
    fed = Federation.create(model, data, tx, ty, cfg, FED_KEY)
    return run_federated_training(model, fed, cfg, inv_sqrt_lr(0.05)), fed


def _flat(params):
    return np.concatenate(
        [np.asarray(v).ravel() for v in jax.tree.leaves(params)])


def _assert_hist_bitwise(a, b, label):
    assert np.array_equal(_flat(a["params"]), _flat(b["params"])), \
        f"{label}: final params differ"
    assert set(a) == set(b), f"{label}: history keys differ"
    for k in a:
        if k != "params":
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), \
                f"{label}: history[{k!r}] differs"


def _audit_kinds(fed):
    kinds = {}
    for e in fed.server.audit.entries:
        kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
    return kinds


# ----------------------------------------------------------------------
# named-error validation
# ----------------------------------------------------------------------

def test_fault_config_named_errors():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultConfig(kind="meteor")
    with pytest.raises(ValueError, match="rate must be in"):
        FaultConfig(kind="dropout", rate=1.5)
    with pytest.raises(ValueError, match="delay must be a positive int"):
        FaultConfig(kind="straggler", delay=0)
    with pytest.raises(ValueError, match="delay must be a positive int"):
        FaultConfig(kind="straggler", delay=True)
    with pytest.raises(ValueError, match="unknown corruption mode"):
        FaultConfig(kind="intermittent", rate=0.1, mode="gamma_ray")


def test_flconfig_async_named_errors():
    with pytest.raises(ValueError, match="cohort_participation"):
        _cfg(cohort_participation=0.0)
    with pytest.raises(ValueError, match="cohort_participation"):
        _cfg(cohort_participation=1.5)
    with pytest.raises(ValueError, match="staleness_buffer"):
        _cfg(staleness_buffer=-1)
    with pytest.raises(ValueError, match="staleness_discount"):
        _cfg(staleness_discount=0.0)
    # async replaces the static participation subsample
    with pytest.raises(ValueError, match="cohort_participation"):
        _cfg(cohort_participation=0.5, participation=0.5)
    # async needs the streaming fold...
    with pytest.raises(ValueError, match="streaming"):
        _cfg(cohort_participation=0.5, streaming=False)
    # ...a rule that CAN stream...
    with pytest.raises(ValueError, match="streaming"):
        _cfg(cohort_participation=0.5, aggregator="median")
    # ...and a lossless wire format
    with pytest.raises(ValueError, match="lossy"):
        _cfg(cohort_participation=0.5, compression="int8")


def test_cohort_chain_shape_size_determinism():
    key = jax.random.PRNGKey(7)
    chain = make_cohort_chain(N, 6, 0.5, key)
    assert chain.shape == (6, N) and chain.dtype == bool
    c = cohort_size(N, 0.5)
    assert np.all(np.asarray(chain.sum(axis=1)) == c)
    assert np.array_equal(np.asarray(chain),
                          np.asarray(make_cohort_chain(N, 6, 0.5, key)))
    # rows actually resample (astronomically unlikely to all coincide)
    assert not all(np.array_equal(np.asarray(chain[0]), np.asarray(chain[r]))
                   for r in range(1, 6))
    assert cohort_size(N, 1e-9) == 1 and cohort_size(N, 1.0) == N


def test_explicit_chain_validation():
    validate_cohort_chain(jnp.ones((3, N), bool), N, 3)
    with pytest.raises(DegenerateCohortError, match="shape"):
        validate_cohort_chain(jnp.ones((3, N + 1), bool), N, 3)
    bad = jnp.ones((3, N), bool).at[1].set(False)
    with pytest.raises(DegenerateCohortError, match="round 1"):
        validate_cohort_chain(bad, N, 3)


def test_draw_and_corrupt_primitives():
    key = jax.random.PRNGKey(0)
    assert not np.any(np.asarray(draw_faults(key, N, FaultConfig())))
    rows = draw_faults(key, 1000, FaultConfig(kind="dropout", rate=0.3))
    frac = float(np.mean(np.asarray(rows)))
    assert 0.2 < frac < 0.4
    U = jnp.ones((4, 6), jnp.float32)
    hit = jnp.asarray([True, False, True, False])
    out = np.asarray(corrupt_updates(
        U, hit, FaultConfig(kind="intermittent", rate=0.5, mode="nan")))
    assert np.all(np.isnan(out[[0, 2]])) and np.array_equal(
        out[[1, 3]], np.ones((2, 6), np.float32))
    out = np.asarray(corrupt_updates(
        U, hit, FaultConfig(kind="intermittent", rate=0.5, mode="bitflip",
                            bitflip_scale=8.0)))
    assert np.all(out[[0, 2]] == 8.0) and np.all(out[[1, 3]] == 1.0)
    # non-intermittent kinds pass through bitwise
    same = corrupt_updates(U, hit, FaultConfig(kind="straggler", rate=0.5))
    assert same is U


# ----------------------------------------------------------------------
# compatibility tiers
# ----------------------------------------------------------------------

def test_trivial_async_bitwise_vs_baseline(fed_data):
    base, _ = _train(fed_data, _cfg())
    triv, _ = _train(fed_data, _cfg(cohort_participation=1.0))
    _assert_hist_bitwise(base, triv, "trivial-async")


def test_async_engine_matches_seed_loop(fed_data, seed_loop):
    cfg = _cfg(cohort_participation=0.6,
               fault=FaultConfig(kind="dropout", rate=0.3))
    eng, _ = _train(fed_data, cfg)
    model, data, tx, ty = fed_data
    seed = seed_loop(model, Federation.create(model, data, tx, ty, cfg,
                                              FED_KEY),
                     cfg, inv_sqrt_lr(0.05))
    # the reference records the loop's own keys: params and the eval
    # history (not the engine's config-derived wire stats)
    assert set(seed) <= set(eng)
    _assert_hist_bitwise({k: eng[k] for k in seed}, seed,
                         "engine-vs-seed-loop")


# ----------------------------------------------------------------------
# faults end to end
# ----------------------------------------------------------------------

def test_dropout_cohort_run_and_audit(fed_data):
    cfg = _cfg(rounds=6, cohort_participation=0.6, telemetry=True,
               fault=FaultConfig(kind="dropout", rate=0.3))
    with telemetry.recording() as rec:
        hist, fed = _train(fed_data, cfg)
    assert np.isfinite(_flat(hist["params"])).all()
    rounds = [r for r in rec.records if r.get("kind") == "round"]
    assert len(rounds) == 6
    # live cohort = resampled cohort minus dropouts, committed per round
    assert all(0 <= r["cohort"] <= cohort_size(N, 0.6) for r in rounds)
    assert any(r["cohort"] < cohort_size(N, 0.6) for r in rounds)
    kinds = _audit_kinds(fed)
    assert kinds.get("cohort_resample") == 6
    assert telemetry.verify_entries(fed.server.audit.entries)


def test_intermittent_nan_guard_end_to_end(fed_data):
    cfg = _cfg(rounds=6, telemetry=True,
               fault=FaultConfig(kind="intermittent", rate=0.4, mode="nan"))
    with telemetry.recording() as rec:
        hist, _fed = _train(fed_data, cfg)
    # 40% of clients burst NaN every round; the guard must keep the
    # model finite and the telemetry must count the screened rows
    assert np.isfinite(_flat(hist["params"])).all()
    assert np.isfinite(np.asarray(hist["acc"])).all()
    rounds = [r for r in rec.records if r.get("kind") == "round"]
    assert sum(r["nonfinite"] for r in rounds) > 0


def test_faulty_diversefl_within_a_point_of_faultfree_oracle():
    """DiverseFL with 20% of each round's cohort sending NaN bursts lands
    within one accuracy point of OracleSGD with no faults at all, on the
    N=256 federation the async acceptance was first stated on."""
    from repro.fl.small_models import mlp3
    n, dim, nc = 256, 256, 10
    x, y = make_classification(jax.random.PRNGKey(0), n * 6, nc, dim)
    data = FederatedData.from_partitions(partition_sorted_shards(x, y, n), nc)
    tx, ty = make_classification(jax.random.PRNGKey(9), 64, nc, dim)
    model = mlp3(input_dim=dim, n_classes=nc, hidden=128)
    acc = {}
    for name, kw in (("faulty", dict(cohort_participation=0.9,
                                     fault=FaultConfig(kind="intermittent",
                                                       rate=0.2, mode="nan"))),
                     ("oracle", dict(aggregator="oracle"))):
        cfg = _cfg(n_clients=n, f=n // 5, rounds=12, eval_every=12,
                   batch_size=5, client_chunk=64, **kw)
        fed = Federation.create(model, data, tx, ty, cfg, FED_KEY)
        acc[name] = float(run_federated_training(
            model, fed, cfg, inv_sqrt_lr(0.05))["final_acc"])
    assert acc["oracle"] > 0.5, acc
    assert acc["faulty"] >= acc["oracle"] - 0.01, acc


def test_straggler_buffered_then_folded(fed_data):
    cfg = _cfg(rounds=6, staleness_buffer=N, telemetry=True,
               fault=FaultConfig(kind="straggler", rate=0.4, delay=1))
    with telemetry.recording() as rec:
        hist, fed = _train(fed_data, cfg)
    assert np.isfinite(_flat(hist["params"])).all()
    rounds = [r for r in rec.records if r.get("kind") == "round"]
    buf = sum(r["stale_buffered"] for r in rounds)
    fold = sum(r["stale_folded"] for r in rounds)
    exp = sum(r["stale_expired"] for r in rounds)
    assert buf > 0 and fold > 0
    assert exp == 0                         # N slots never overflow
    assert fold <= buf                      # land only what was buffered
    # delay=1: everything buffered in rounds 1..R-1 lands next round
    assert fold == sum(r["stale_buffered"] for r in rounds[:-1])
    kinds = _audit_kinds(fed)
    assert kinds.get("stale_buffered", 0) > 0
    assert kinds.get("stale_folded", 0) > 0
    assert "stale_expired" not in kinds     # zero counts stay off the chain
    assert telemetry.verify_entries(fed.server.audit.entries)


def test_straggler_without_buffer_expires(fed_data):
    cfg = _cfg(rounds=6, telemetry=True,
               fault=FaultConfig(kind="straggler", rate=0.4, delay=1))
    with telemetry.recording() as rec:
        hist, fed = _train(fed_data, cfg)
    assert np.isfinite(_flat(hist["params"])).all()
    rounds = [r for r in rec.records if r.get("kind") == "round"]
    assert sum(r["stale_expired"] for r in rounds) > 0
    assert sum(r["stale_buffered"] for r in rounds) == 0
    assert sum(r["stale_folded"] for r in rounds) == 0
    kinds = _audit_kinds(fed)
    assert kinds.get("stale_expired", 0) > 0 and "stale_folded" not in kinds


def test_staleness_cap_expires_over_delay(fed_data):
    # cap < delay: the buffer exists but refuses everything (static)
    cfg = _cfg(rounds=4, staleness_buffer=4, staleness_cap=1,
               telemetry=True,
               fault=FaultConfig(kind="straggler", rate=0.4, delay=2))
    with telemetry.recording() as rec:
        hist, _fed = _train(fed_data, cfg)
    assert np.isfinite(_flat(hist["params"])).all()
    rounds = [r for r in rec.records if r.get("kind") == "round"]
    assert sum(r["stale_expired"] for r in rounds) > 0
    assert sum(r["stale_folded"] for r in rounds) == 0


# ----------------------------------------------------------------------
# attack x fault composition
# ----------------------------------------------------------------------

def test_mask_rates_valid_channel_exact():
    mask = jnp.asarray([True, False, False, True, False, True])
    byz = jnp.asarray([False, True, True, False, True, False])
    valid = jnp.asarray([True, True, False, True, False, False])
    # all-rows accounting unchanged
    tpr, fpr = mask_rates(mask, byz)
    assert float(tpr) == 1.0 and float(fpr) == 0.0
    # valid restricts both numerators and denominators to live rows:
    # byz rows {1} live (flagged), benign rows {0, 3} live (kept)
    tpr, fpr = mask_rates(mask, byz, valid)
    assert float(tpr) == 1.0 and float(fpr) == 0.0
    # a kept Byzantine row only counts against TPR while it is live
    tpr_live, _ = mask_rates(mask.at[1].set(True), byz, valid)
    tpr_dead, _ = mask_rates(mask.at[1].set(True), byz,
                             valid.at[1].set(False))
    assert float(tpr_live) == 0.0 and float(tpr_dead) == 1.0
    # degenerate live cohorts keep the legacy conventions
    none_live = jnp.zeros((6,), bool)
    tpr, fpr = mask_rates(mask, byz, none_live)
    assert float(tpr) == 1.0 and float(fpr) == 0.0


def test_byzantine_straggler_tagged_at_landing(fed_data):
    # sign-flipped Byzantine clients straggle: their updates land a
    # round late and Eq. 6 (guides recomputed at the landing round)
    # must still tag them — detection follows the update, not the round
    cfg = _cfg(rounds=6, staleness_buffer=N,
               fault=FaultConfig(kind="straggler", rate=0.5, delay=1))
    hist, _fed = _train(fed_data, cfg)
    assert np.isfinite(_flat(hist["params"])).all()
    assert float(np.asarray(hist["mask_tpr"])[-1]) >= 0.99
    assert float(np.asarray(hist["mask_fpr"])[-1]) <= 0.5


# ----------------------------------------------------------------------
# the non-finite guard, unit level
# ----------------------------------------------------------------------

def test_nonfinite_guard_unit():
    d = 17
    rng = np.random.default_rng(3)
    U = rng.normal(size=(8, d)).astype(np.float32)
    U[2] = np.nan
    U[5, 0] = np.inf
    rule = get_streaming("mean").bind(AggregationContext())

    def block_fn(blk, valid):
        (u_b,) = blk
        return u_b, {}

    delta, _agg, logs = stream_aggregate(rule, block_fn, (jnp.asarray(U),),
                                         4, d=d)
    assert np.array_equal(np.asarray(logs["nonfinite"]),
                          [False, False, True, False, False, True,
                           False, False])
    fin = np.delete(U, [2, 5], axis=0)
    assert np.isfinite(np.asarray(delta)).all()
    # screened rows contribute exactly 0 to numerator AND denominator
    np.testing.assert_allclose(np.asarray(delta),
                               fin.sum(axis=0) / len(fin), rtol=1e-6)
    # inert on finite data: same fold, nonfinite bits all clear
    d2, _a2, logs2 = stream_aggregate(rule, block_fn,
                                      (jnp.ones((8, d), jnp.float32),),
                                      4, d=d)
    assert not np.any(np.asarray(logs2["nonfinite"]))
    assert np.array_equal(np.asarray(d2), np.ones(d, np.float32))


def test_round_telemetry_bytes_async_fields(fed_data):
    sync_cfg = _cfg()
    async_cfg = _cfg(cohort_participation=0.5)
    # streaming raw-f32 carries the nonfinite popcount either way; async
    # adds cohort + the three staleness decision counts (4 x int32)
    assert round_telemetry_bytes(async_cfg) \
        == round_telemetry_bytes(sync_cfg) + 16
    # lossy codec drops the guard field on an otherwise-equal config
    assert round_telemetry_bytes(_cfg(compression="int8")) \
        == round_telemetry_bytes(sync_cfg) - 4


# ----------------------------------------------------------------------
# sweep axes
# ----------------------------------------------------------------------

def test_sweep_fault_staleness_axes_structural(fed_data):
    base = _cfg()
    spec = SweepSpec(
        base=base, seeds=(0,),
        faults=(FaultConfig(),
                FaultConfig(kind="straggler", rate=0.4, delay=1)),
        stalenesses=(0, 4))
    cells = spec.cells()
    assert len(cells) == 4
    assert len(group_cells(cells)) == 4      # every point its own trace
    keys = {structural_key(c.cfg) for c in cells}
    assert len(keys) == 4
    # seeds batch within a (fault, staleness) point
    spec2 = dataclasses.replace(spec, seeds=(0, 1))
    assert len(group_cells(spec2.cells())) == 4


def test_sweep_async_cells_bitwise_vs_solo(fed_data):
    model, data, tx, ty = fed_data
    base = _cfg(cohort_participation=0.6)
    spec = SweepSpec(
        base=base, seeds=(0, 1),
        faults=(FaultConfig(kind="dropout", rate=0.3),))
    fed = Federation.create(model, data, tx, ty, base, FED_KEY)
    results = run_federated_sweep(model, fed, spec, inv_sqrt_lr(0.05))
    assert len(results) == 2
    for cell, got in zip(spec.cells(), results):
        solo, _ = _train(fed_data, cell.cfg)
        _assert_hist_bitwise(solo, got, f"cell seed={cell.cfg.seed}")
