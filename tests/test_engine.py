"""RoundEngine / simulator equivalence (ISSUE 2 satellite).

The scan-compiled engine must be a pure compilation strategy, not a new
algorithm: it reproduces the per-round jitted reference loop
(``conftest.run_seed_loop``) bit-for-bit on fixed seeds, and chunked
execution (``client_chunk < N``) matches unchunked to fp tolerance
across aggregators.  The mesh-sharded path must be bit-identical to the
meshless one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.attacks import AttackConfig
from repro.data import (FederatedData, make_mnist_like,
                        partition_sorted_shards)
from repro.fl import (FLConfig, Federation, RoundEngine, chunked_vmap,
                      run_federated_training, softmax_regression)
from repro.optim import inv_sqrt_lr

N_CLIENTS, F, ROUNDS = 23, 5, 6


@pytest.fixture(scope="module")
def small_fed():
    x, y = make_mnist_like(jax.random.PRNGKey(0), 460)
    tx, ty = make_mnist_like(jax.random.PRNGKey(9), 200)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N_CLIENTS), 10)
    return data, tx, ty


def _cfg(**kw):
    kw.setdefault("n_clients", N_CLIENTS)
    kw.setdefault("f", F)
    kw.setdefault("rounds", ROUNDS)
    kw.setdefault("batch_size", 10)
    kw.setdefault("eval_every", 3)
    kw.setdefault("attack", AttackConfig(kind="sign_flip"))
    return FLConfig(**kw)


def _flat(params):
    return np.concatenate(
        [np.asarray(v).ravel() for v in jax.tree.leaves(params)])


def _train(data, tx, ty, cfg, loop=run_federated_training):
    model = softmax_regression()
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    return loop(model, fed, cfg, inv_sqrt_lr(0.05))


# ----------------------------------------------------------------------
# scan engine vs the per-round reference loop: bit-for-bit
# ----------------------------------------------------------------------

@pytest.mark.parametrize("eval_every", [1, 3])
def test_scan_engine_reproduces_seed_loop_bitwise(small_fed, seed_loop,
                                                  eval_every):
    data, tx, ty = small_fed
    cfg = _cfg(eval_every=eval_every)
    h_eng = _train(data, tx, ty, cfg)
    h_seed = _train(data, tx, ty, cfg, loop=seed_loop)
    assert np.array_equal(_flat(h_eng["params"]), _flat(h_seed["params"]))
    assert h_eng["round"] == h_seed["round"]
    assert h_eng["acc"] == h_seed["acc"]
    assert h_eng["mask_tpr"] == h_seed["mask_tpr"]
    assert h_eng["mask_fpr"] == h_seed["mask_fpr"]


def test_chunk_equal_to_n_is_bitwise(small_fed):
    """client_chunk=N must take the exact vmap path (same traced graph)."""
    data, tx, ty = small_fed
    h_full = _train(data, tx, ty, _cfg())
    h_cn = _train(data, tx, ty, _cfg(client_chunk=N_CLIENTS))
    assert np.array_equal(_flat(h_full["params"]), _flat(h_cn["params"]))


# ----------------------------------------------------------------------
# chunked vs unchunked: fp tolerance, >= 3 aggregators
# ----------------------------------------------------------------------

@pytest.mark.parametrize("aggregator",
                         ["diversefl", "mean", "trimmed_mean", "krum"])
@pytest.mark.parametrize("chunk", [4, 10])
def test_chunked_matches_unchunked(small_fed, aggregator, chunk):
    data, tx, ty = small_fed
    h_full = _train(data, tx, ty, _cfg(aggregator=aggregator, rounds=4))
    h_chunk = _train(data, tx, ty,
                     _cfg(aggregator=aggregator, rounds=4,
                          client_chunk=chunk))
    np.testing.assert_allclose(_flat(h_chunk["params"]),
                               _flat(h_full["params"]),
                               rtol=1e-5, atol=1e-6)


def test_chunked_vmap_matches_vmap_with_padding():
    """Non-divisible chunking (pad + discard) equals plain vmap."""
    xs = jnp.arange(21.0).reshape(7, 3)
    fn = lambda row: jnp.sum(row ** 2) + row
    want = jax.vmap(fn)(xs)
    for chunk in (1, 2, 3, 4, 7, 100):
        got = chunked_vmap(fn, (xs,), chunk)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------------------
# mesh sharding
# ----------------------------------------------------------------------

def _engine_segment(model, fed, cfg, **kw):
    engine = RoundEngine(model, fed, cfg, **kw)
    params0 = model.init(jax.random.PRNGKey(cfg.seed + 1))
    lrs = [float(inv_sqrt_lr(0.05)(r)) for r in range(1, 4)]
    return engine.run_segment(params0, jax.random.PRNGKey(cfg.seed), lrs)


def test_mesh_sharded_engine_is_bitwise(small_fed):
    """An active ("data","model") mesh (client-axis NamedShardings)
    must not change the numbers."""
    data, tx, ty = small_fed
    cfg = _cfg(client_chunk=8)
    model = softmax_regression()
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    p_ref, _, _ = _engine_segment(model, fed, cfg)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    p_mesh, _, logs = _engine_segment(model, fed, cfg, mesh=mesh)
    assert np.array_equal(_flat(p_ref), _flat(p_mesh))
    assert "mask" in logs


# ----------------------------------------------------------------------
# satellite fixes
# ----------------------------------------------------------------------

def test_n_selected_uses_ceil():
    """Step 2: C = ceil(participation * N); round() under-selected."""
    cfg = FLConfig(n_clients=23, participation=0.1)
    assert cfg.n_selected == 3          # round(2.3) == 2 was the bug
    assert FLConfig(n_clients=23, participation=1.0).n_selected == 23
    assert FLConfig(n_clients=23, participation=0.5).n_selected == 12
    assert FLConfig(n_clients=10, participation=0.0).n_selected == 1


def test_engine_partial_participation_matches_seed(small_fed, seed_loop):
    """Selection RNG (ks subkey) is part of the bit-for-bit contract."""
    data, tx, ty = small_fed
    cfg = _cfg(participation=0.5, rounds=4)
    h_eng = _train(data, tx, ty, cfg)
    h_seed = _train(data, tx, ty, cfg, loop=seed_loop)
    assert np.array_equal(_flat(h_eng["params"]), _flat(h_seed["params"]))


def test_compute_guides_select_and_chunk(small_fed):
    """Chunked + selected guide computation equals the full vmap path."""
    data, tx, ty = small_fed
    cfg = _cfg()
    model = softmax_regression()
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    params = model.init(jax.random.PRNGKey(1))

    def grad_fn(p, batch):
        x, y = batch
        return jax.grad(lambda q: model.loss(q, x, y))(p)

    full = fed.server.compute_guides(params, grad_fn, lr=0.05, E=2)
    sel = jnp.asarray([3, 7, 11, 19, 2])
    picked = fed.server.compute_guides(params, grad_fn, lr=0.05, E=2,
                                       select=sel)
    chunked = fed.server.compute_guides(params, grad_fn, lr=0.05, E=2,
                                        select=sel, client_chunk=2)
    want = jax.tree.map(lambda u: u[np.asarray(sel)], full)
    for a, b, c in zip(jax.tree.leaves(want), jax.tree.leaves(picked),
                       jax.tree.leaves(chunked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-6, atol=1e-7)


def test_segment_logs_are_last_round(small_fed):
    """run_segment returns the final round's logs (what the eval reads)."""
    data, tx, ty = small_fed
    cfg = _cfg()
    model = softmax_regression()
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    _, _, logs = _engine_segment(model, fed, cfg)
    assert logs["mask"].shape == (cfg.n_selected,)
    assert logs["byz"].shape == (cfg.n_selected,)
