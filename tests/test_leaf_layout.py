"""Dense Step 4+5 on the stacked leaves (DESIGN.md §3).

On the dense path, the masked-mean family's kernel rounds read each
stacked update and guide leaf in place and build no (N, D) rows.  The
engine decides this from the rule's leaf form, its kernel flags and the
round's mode, and says which layout it took (``dense_layout``) and why
rows where it keeps them.  A leaf round must match the XLA dense round
of the same config to fp tolerance, and exactly on the keep decisions.
"""
import jax
import numpy as np
import pytest

from repro.core.attacks import AttackConfig
from repro.data import (FederatedData, make_cifar_like,
                        partition_sorted_shards)
from repro.fl import (FLConfig, Federation, RoundEngine, SweepSpec,
                      run_federated_sweep, run_federated_training, telemetry)
from repro.fl.faults import FaultConfig
from repro.fl.small_models import small_cnn
from repro.optim import inv_sqrt_lr

N, F = 11, 3
FED_KEY = jax.random.PRNGKey(2)


@pytest.fixture(scope="module")
def fed_data():
    x, y = make_cifar_like(jax.random.PRNGKey(0), N * 12)
    data = FederatedData.from_partitions(partition_sorted_shards(x, y, N),
                                         10)
    tx, ty = make_cifar_like(jax.random.PRNGKey(9), 40)
    return small_cnn(), data, tx, ty


def _cfg(**kw):
    kw.setdefault("n_clients", N)
    kw.setdefault("f", F)
    kw.setdefault("rounds", 3)
    kw.setdefault("eval_every", 3)
    kw.setdefault("batch_size", 4)
    kw.setdefault("attack", AttackConfig(kind="sign_flip"))
    return FLConfig(**kw)


def _flat(params):
    return np.concatenate(
        [np.asarray(v).ravel() for v in jax.tree.leaves(params)])


def _engine(fed_data, cfg):
    model, data, tx, ty = fed_data
    fed = Federation.create(model, data, tx, ty, cfg, FED_KEY)
    with telemetry.recording() as rec:
        engine = RoundEngine(model, fed, cfg)
    events = [r for r in rec.records if r.get("kind") == "dense_layout"]
    return model, fed, engine, events


LEAVES = {
    "diversefl-agg": dict(aggregator="diversefl", use_kernel_stats=True,
                          use_kernel_agg=True),
    "diversefl-stats": dict(aggregator="diversefl", use_kernel_stats=True),
    "oracle-agg": dict(aggregator="oracle", use_kernel_agg=True),
    "mean-agg": dict(aggregator="mean", use_kernel_agg=True),
}

ROWS = {
    "xla": (dict(aggregator="diversefl"), "XLA dense path"),
    "streaming": (dict(aggregator="diversefl", use_kernel_agg=True,
                       streaming=True), "streaming"),
    "int8": (dict(aggregator="diversefl", use_kernel_stats=True,
                  compression="int8"), "lossy codec"),
    "async": (dict(aggregator="diversefl", use_kernel_agg=True,
                   streaming=True,
                   fault=FaultConfig(kind="dropout", rate=0.2)),
              "async rounds"),
    "gaussian": (dict(aggregator="diversefl", use_kernel_agg=True,
                      attack=AttackConfig(kind="gaussian", sigma=1e4)),
                 "gaussian attack"),
    "median": (dict(aggregator="median"), "no leaf form"),
    "oracle-stats": (dict(aggregator="oracle", use_kernel_stats=True),
                     "XLA dense path"),
}


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_kernel_rounds_read_leaves(fed_data, name):
    _, _, engine, events = _engine(fed_data, _cfg(**LEAVES[name]))
    assert engine.dense_layout == "leaves"
    assert engine.dense_layout_reason is None
    assert [(e["aggregator"], e["layout"], e["reason"]) for e in events] \
        == [(LEAVES[name]["aggregator"], "leaves", None)]


@pytest.mark.parametrize("name", sorted(ROWS))
def test_other_rounds_keep_rows(fed_data, name):
    kw, why = ROWS[name]
    _, _, engine, events = _engine(fed_data, _cfg(**kw))
    assert engine.dense_layout == "rows"
    assert why in engine.dense_layout_reason
    assert [(e["layout"], e["reason"]) for e in events] \
        == [("rows", engine.dense_layout_reason)]


def test_leaf_round_builds_no_rows(fed_data):
    """The (N, D) update and guide rows exist in the XLA dense program
    and nowhere in the leaf program."""
    def program(cfg):
        model, _, engine, _ = _engine(fed_data, cfg)
        params = model.init(jax.random.PRNGKey(1))
        d = sum(p.size for p in jax.tree.leaves(params))
        text = engine.lower_training(params, jax.random.PRNGKey(3),
                                     [0.05] * cfg.rounds).as_text()
        return text, f"tensor<{N}x{d}xf32>"

    text, rows = program(_cfg(aggregator="diversefl"))
    assert rows in text
    text, rows = program(_cfg(**LEAVES["diversefl-agg"]))
    assert rows not in text


def _train(fed_data, cfg):
    model, fed, engine, _ = _engine(fed_data, cfg)
    hist = run_federated_training(model, fed, cfg, inv_sqrt_lr(0.05),
                                  engine=engine)
    params, _, logs = engine.run_segment(
        model.init(jax.random.PRNGKey(1)), jax.random.PRNGKey(3),
        [0.05, 0.05])
    return hist, params, logs


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_leaf_round_matches_xla_dense(fed_data, name):
    kw = LEAVES[name]
    h_leaf, p_leaf, l_leaf = _train(fed_data, _cfg(**kw))
    h_xla, p_xla, l_xla = _train(fed_data,
                                 _cfg(aggregator=kw["aggregator"]))
    np.testing.assert_allclose(_flat(h_leaf["params"]),
                               _flat(h_xla["params"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(_flat(p_leaf), _flat(p_xla), rtol=1e-5,
                               atol=1e-6)
    for k in ("mask_tpr", "mask_fpr"):
        if k in h_xla:
            assert h_leaf[k] == h_xla[k], k
    if "mask" in l_xla:
        np.testing.assert_array_equal(np.asarray(l_leaf["mask"]),
                                      np.asarray(l_xla["mask"]))
        assert np.asarray(l_xla["mask"]).any()


def test_leaf_sweep_equals_solo(fed_data):
    model, data, tx, ty = fed_data
    base = _cfg(**LEAVES["diversefl-agg"])
    spec = SweepSpec(base=base, seeds=(0, 1))
    fed = Federation.create(model, data, tx, ty, base, FED_KEY)
    results = run_federated_sweep(model, fed, spec, inv_sqrt_lr(0.05))
    for cell, hist in zip(spec.cells(), results):
        fed_c = Federation.create(model, data, tx, ty, cell.cfg, FED_KEY)
        solo = run_federated_training(model, fed_c, cell.cfg,
                                      inv_sqrt_lr(0.05))
        assert set(hist) == set(solo)
        assert np.array_equal(_flat(hist["params"]), _flat(solo["params"]))
        for k in solo:
            if k != "params":
                assert np.array_equal(np.asarray(hist[k]),
                                      np.asarray(solo[k])), k
