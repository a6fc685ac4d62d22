"""One-dispatch training: in-scan device eval (ISSUE 4, DESIGN.md §7).

Contracts:

  * **in-scan eval == host-loop eval, bitwise** — the one-dispatch path
    (eval folded into the outer scan, one host sync) reproduces the
    legacy per-segment host-eval loop and the seed per-round loop
    bit-for-bit: params, every metric history, every eval round index —
    including partial participation, backdoor attacks (main-task +
    backdoor accuracy), streaming aggregation, and a final partial
    segment when ``rounds % eval_every != 0``.
  * **metrics are jittable where-masked reductions** — no boolean
    indexing, no ``float()`` casts: the same function jits, returns
    device scalars, and matches a NumPy reference computed with the
    seed's dynamic-shape indexing semantics.
  * **the host sync is one, and counted** — every device→host
    materialization flows through ``repro.fl.simulator.host_sync``; a
    multi-segment run syncs exactly once on the one-dispatch path and
    once per segment on the legacy path.
  * **the donate knob threads** — FLConfig.donate → RoundEngine,
    tri-state (None = backend auto).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.fl.simulator as sim
from repro.core.attacks import AttackConfig
from repro.data import (FederatedData, make_classification,
                        partition_sorted_shards)
from repro.fl import (FLConfig, Federation, RoundEngine,
                      run_federated_training, softmax_regression)
from repro.fl.metrics import (accuracy, backdoor_accuracy, make_backdoor_eval,
                              main_task_accuracy, mask_rates, masked_accuracy,
                              stamp_trigger)
from repro.optim import inv_sqrt_lr

N_CLIENTS, DIM, N_CLASSES = 32, 16, 4


@pytest.fixture(scope="module")
def fed_data():
    x, y = make_classification(jax.random.PRNGKey(0), N_CLIENTS * 8,
                               N_CLASSES, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N_CLIENTS), N_CLASSES)
    tx, ty = make_classification(jax.random.PRNGKey(9), 128, N_CLASSES, DIM)
    return data, tx, ty


def _cfg(**kw):
    kw.setdefault("n_clients", N_CLIENTS)
    kw.setdefault("f", 6)
    kw.setdefault("rounds", 6)
    kw.setdefault("batch_size", 2)
    kw.setdefault("eval_every", 3)
    kw.setdefault("l2", 0.0)
    kw.setdefault("attack", AttackConfig(kind="sign_flip"))
    return FLConfig(**kw)


def _train(fed_data, cfg, **kw):
    data, tx, ty = fed_data
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    return run_federated_training(model, fed, cfg, inv_sqrt_lr(0.05), **kw)


def _flat(params):
    return np.concatenate(
        [np.asarray(v).ravel() for v in jax.tree.leaves(params)])


def _assert_histories_bitwise(a, b):
    assert a["round"] == b["round"]
    for k in ("acc", "main_acc", "backdoor_acc", "mask_tpr", "mask_fpr"):
        assert a.get(k, []) == b.get(k, []), k
    for ca, cb in zip(a.get("c1c2", []), b.get("c1c2", [])):
        np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
    assert np.array_equal(_flat(a["params"]), _flat(b["params"]))


# ----------------------------------------------------------------------
# in-scan eval == host-loop eval == seed loop: bitwise
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {},                                                    # divisible rounds
    {"rounds": 7},                                         # partial tail seg
    {"participation": 0.5, "rounds": 4},                   # cohort sampling
    {"attack": AttackConfig(kind="backdoor", source_class=1,
                            target_class=2), "rounds": 4},  # backdoor metrics
    {"streaming": True, "client_chunk": 8, "rounds": 4,
     "attack": AttackConfig(kind="gaussian")},             # streaming rounds
])
def test_in_scan_eval_matches_host_loop_bitwise(fed_data, kw):
    cfg = _cfg(**kw)
    h_dev = _train(fed_data, cfg)
    h_host = _train(fed_data, cfg, host_eval=True)
    _assert_histories_bitwise(h_dev, h_host)


def test_in_scan_eval_matches_seed_loop_bitwise(fed_data):
    cfg = _cfg(eval_every=2)
    h_dev = _train(fed_data, cfg)
    h_seed = _train(fed_data, cfg, use_engine=False)
    _assert_histories_bitwise(h_dev, h_seed)


def test_backdoor_history_has_attack_metrics(fed_data):
    cfg = _cfg(attack=AttackConfig(kind="backdoor", source_class=1,
                                   target_class=2), rounds=3)
    h = _train(fed_data, cfg)
    assert len(h["main_acc"]) == len(h["round"])
    assert len(h["backdoor_acc"]) == len(h["round"])
    h_plain = _train(fed_data, _cfg(rounds=3))
    assert "main_acc" not in h_plain or not h_plain["main_acc"]


# ----------------------------------------------------------------------
# metrics: jittable, device scalars, reference semantics
# ----------------------------------------------------------------------

def test_metrics_are_jittable_device_scalars(fed_data):
    data, tx, ty = fed_data
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    params = model.init(jax.random.PRNGKey(1))
    acfg = AttackConfig(kind="backdoor", source_class=1, target_class=2)
    for fn in (lambda p: accuracy(model, p, tx, ty),
               lambda p: main_task_accuracy(model, p, tx, ty, acfg),
               lambda p: backdoor_accuracy(model, p, tx, ty, acfg)):
        eager, jitted = fn(params), jax.jit(fn)(params)
        assert isinstance(eager, jax.Array) and eager.shape == ()
        assert np.asarray(eager) == np.asarray(jitted)


def test_metrics_match_numpy_reference(fed_data):
    """Where-masked reductions == the seed's boolean-indexing semantics."""
    data, tx, ty = fed_data
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    params = model.init(jax.random.PRNGKey(4))
    acfg = AttackConfig(kind="backdoor", source_class=1, target_class=2)
    preds = np.argmax(np.asarray(model.apply(params, tx)), -1)
    ty_np = np.asarray(ty)

    assert np.asarray(accuracy(model, params, tx, ty)) == pytest.approx(
        (preds == ty_np).mean(), abs=1e-6)
    sel = ty_np != acfg.source_class
    assert np.asarray(main_task_accuracy(model, params, tx, ty, acfg)) == \
        pytest.approx((preds[sel] == ty_np[sel]).mean(), abs=1e-6)
    # backdoor: stamp only the source rows (the seed gathered them first)
    xs = np.asarray(tx).copy()
    xs[:, :3] = 1.0
    bd_preds = np.argmax(np.asarray(model.apply(params, jnp.asarray(xs))), -1)
    src = ty_np == acfg.source_class
    want = (bd_preds[src] == acfg.target_class).mean() if src.any() else 0.0
    assert np.asarray(backdoor_accuracy(model, params, tx, ty, acfg)) == \
        pytest.approx(want, abs=1e-6)


def test_masked_accuracy_empty_mask_is_zero():
    model = softmax_regression(input_dim=4, n_classes=3)
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.ones((5, 4))
    y = jnp.zeros((5,), jnp.int32)
    out = masked_accuracy(model, params, x, y, jnp.zeros((5,), bool))
    assert np.asarray(out) == 0.0


def test_mask_rates_edge_cases():
    mask = jnp.asarray([True, False, True, False])
    byz = jnp.asarray([False, True, False, True])
    tpr, fpr = mask_rates(mask, byz)
    assert (np.asarray(tpr), np.asarray(fpr)) == (1.0, 0.0)
    # no Byzantine client -> TPR defaults to 1.0; no benign -> FPR 0.0
    tpr, _ = mask_rates(mask, jnp.zeros((4,), bool))
    assert np.asarray(tpr) == 1.0
    _, fpr = mask_rates(mask, jnp.ones((4,), bool))
    assert np.asarray(fpr) == 0.0


def test_stamp_trigger_shapes():
    img = jnp.zeros((2, 8, 8, 3))
    assert np.asarray(stamp_trigger(img))[:, :3, :3].min() == 1.0
    assert np.asarray(stamp_trigger(img))[:, 3:, 3:].max() == 0.0
    flat = jnp.zeros((2, 8))
    assert np.asarray(stamp_trigger(flat))[:, :3].min() == 1.0


def test_federation_backdoor_eval_is_cached(fed_data):
    """The trigger-stamped test set is built once per federation and
    reused; a different source/target pair rebuilds it."""
    data, tx, ty = fed_data
    acfg = AttackConfig(kind="backdoor", source_class=1, target_class=2)
    cfg = _cfg(attack=acfg)
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    ev1 = fed.backdoor_eval(acfg)
    assert fed.backdoor_eval(acfg) is ev1
    ev2 = fed.backdoor_eval(AttackConfig(kind="backdoor", source_class=2,
                                         target_class=3))
    assert ev2 is not ev1 and ev2.source_class == 2
    np.testing.assert_array_equal(
        np.asarray(ev1.x), np.asarray(make_backdoor_eval(tx, ty, acfg).x))


# ----------------------------------------------------------------------
# host syncs: one per run (one-dispatch) vs one per segment (legacy)
# ----------------------------------------------------------------------

def _count_syncs(fed_data, cfg, monkeypatch, **kw):
    counter = {"n": 0}
    orig = sim.host_sync

    def counting(tree):
        counter["n"] += 1
        return orig(tree)

    monkeypatch.setattr(sim, "host_sync", counting)
    h = _train(fed_data, cfg, **kw)
    return counter["n"], h


def test_one_dispatch_syncs_once(fed_data, monkeypatch):
    cfg = _cfg(rounds=6, eval_every=2)          # 3 segments
    n_dev, _ = _count_syncs(fed_data, cfg, monkeypatch)
    assert n_dev == 1
    n_host, _ = _count_syncs(fed_data, cfg, monkeypatch, host_eval=True)
    assert n_host == 3


def test_one_dispatch_under_transfer_guard(fed_data):
    """Nothing on the one-dispatch path reaches the host outside the
    choke point: the whole run executes under a device→host guard."""
    cfg = _cfg(rounds=4, eval_every=2)
    _train(fed_data, cfg)                       # compile outside the guard
    with jax.transfer_guard_device_to_host("disallow_explicit"):
        h = _train(fed_data, cfg)
    assert len(h["acc"]) == 2


def test_telemetry_keeps_single_sync_under_transfer_guard(fed_data,
                                                          monkeypatch):
    """ISSUE 8: the per-round telemetry block rides the existing metric
    buffer — a telemetry-enabled 10-segment run still reaches the host
    in exactly ONE final sync, under the d2h guard, with the history
    bitwise-identical to the telemetry-off run."""
    from repro.fl import telemetry

    cfg = _cfg(rounds=10, eval_every=1, telemetry=True)      # 10 segments
    h_off = _train(fed_data, _cfg(rounds=10, eval_every=1))
    _train(fed_data, cfg)                       # compile outside the guard
    with telemetry.recording() as rec:
        with jax.transfer_guard_device_to_host("disallow_explicit"):
            n, h_on = _count_syncs(fed_data, cfg, monkeypatch)
    assert n == 1
    _assert_histories_bitwise(h_off, h_on)
    # the recorder saw the same single sync, and one record per round
    syncs = [r for r in rec.records if r.get("kind") == "sync"]
    rounds = [r for r in rec.records if r.get("kind") == "round"]
    assert len(syncs) == 1
    assert [r["index"] for r in rounds] == list(range(1, 11))


# ----------------------------------------------------------------------
# donate knob: FLConfig -> RoundEngine, tri-state
# ----------------------------------------------------------------------

def test_donate_knob_threads_through(fed_data):
    data, tx, ty = fed_data
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)

    def engine(**kw):
        cfg = _cfg(**kw)
        fed = Federation.create(model, data, tx, ty, cfg,
                                jax.random.PRNGKey(2))
        return RoundEngine(model, fed, cfg)

    auto = jax.default_backend() != "cpu"
    assert engine().donate is auto              # None -> backend auto
    assert engine(donate=True).donate is True   # forced on (measurement)
    assert engine(donate=False).donate is False


def test_lower_training_traces_the_program_run_training_runs(fed_data):
    """lower_training hands the one-dispatch program run_training's own
    operands: after lowering, the run reuses that trace instead of
    tracing a program of its own."""
    from repro.fl.engine import trace_counter
    data, tx, ty = fed_data
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    cfg = _cfg()
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    engine = RoundEngine(model, fed, cfg)
    params = model.init(jax.random.PRNGKey(1))
    lrs = jnp.full((cfg.rounds,), 0.05, jnp.float32)
    with trace_counter() as tc:
        compiled = engine.lower_training(
            params, jax.random.PRNGKey(0), lrs).compile()
        assert tc["training"] == 1
        engine.run_training(params, jax.random.PRNGKey(0), lrs)
        assert tc["training"] == 1
    assert "while" in compiled.as_text()


def test_donate_forced_on_still_runs(fed_data):
    """donate=True on CPU compiles and runs (XLA ignores the request);
    the numbers cannot change."""
    cfg_on, cfg_off = _cfg(rounds=4, donate=True), _cfg(rounds=4)
    h_on = _train(fed_data, cfg_on)
    h_off = _train(fed_data, cfg_off)
    assert np.array_equal(_flat(h_on["params"]), _flat(h_off["params"]))
