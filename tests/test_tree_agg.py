"""Hierarchical two-tier aggregation (ISSUE 6, DESIGN.md §9).

The contract tested here: the two-tier fold — P contiguous pod-major
block groups, each folded with the PR 3 left fold (S-way shard-parallel
within the pod), tier-1 partials combined per pod and the P per-pod
AggStates combined across pods, both by ``tree_merge``'s canonical
balanced-binary association — is a **pure function of (client order,
chunk, S, pods)**:

  * ``pods=1`` *is* the single-tier fold — bitwise (delta AND
    per-client logs), for every streaming rule, because P <= 1 routes
    through the identical code path;
  * per-client criterion logs are bitwise at every (S, pods) — neither
    tier's association touches per-row statistics;
  * depth-2 monoid laws: merging the per-pod partials of a pod-order
    permutation reproduces the canonical result on exact data, and the
    merge of pod partials equals the flat fold bitwise when every add
    is exact (0/1 weights, integer updates);
  * executing the same P-way fold under an active ("pod", "data",
    "model") mesh matches the meshless fold (subprocess, forced host
    devices) — placement cannot change the association.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attacks import AttackConfig
from repro.data import (FederatedData, make_classification,
                        partition_sorted_shards)
from repro.fl import (FLConfig, Federation, run_federated_training,
                      softmax_regression, stream_aggregate, tree_merge)
from repro.fl.chunking import group_blocks_2d, resolve_pods
from repro.fl.server import AggregationContext
from repro.fl.streaming import get_streaming
from repro.optim import inv_sqrt_lr
from repro.sharding import ShardMismatchError
from repro.fl.sweep import SweepSpec, group_cells, structural_key

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_CLIENTS, DIM, N_CLASSES = 64, 8, 4
RULES = ["mean", "oracle", "diversefl", "fltrust"]


def _flat(params):
    return np.concatenate(
        [np.asarray(v).ravel() for v in jax.tree.leaves(params)])


def _bound(name, n, d, rng):
    U = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    G = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    byz = jnp.asarray(rng.random(n) < 0.3)
    root = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    rule = get_streaming(name).bind(
        AggregationContext(byz_mask=byz, guides=G, root_update=root))

    def block_fn(blk, valid):
        u_blk, g_blk, byz_b = blk
        return u_blk, {"byz": byz_b, "guide": g_blk}

    return rule, block_fn, (U, G, byz)


# ----------------------------------------------------------------------
# the fold itself: stream_aggregate at pods ∈ {1, 2, 4} per rule
# ----------------------------------------------------------------------

@pytest.mark.parametrize("name", RULES)
def test_pods_one_is_single_tier_bitwise(name):
    """P <= 1 routes through the verbatim single-tier code path: delta
    AND logs bitwise, with and without an explicit shard count."""
    rng = np.random.default_rng(0)
    n, d, chunk = 32, 23, 4
    rule, block_fn, args = _bound(name, n, d, rng)
    d_seq, _, logs_seq = stream_aggregate(rule, block_fn, args, chunk, d=d)
    for kw in ({"pods": 1}, {"pods": 1, "shards": 2}):
        ref = stream_aggregate(rule, block_fn, args, chunk, d=d,
                               shards=kw.get("shards"))
        got = stream_aggregate(rule, block_fn, args, chunk, d=d, **kw)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(ref[0]))
        for a, b in zip(jax.tree.leaves(got[2]), jax.tree.leaves(ref[2])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", RULES)
@pytest.mark.parametrize("pods,shards", [(2, None), (4, None), (2, 2)])
def test_two_tier_per_client_logs_bitwise(name, pods, shards):
    """Neither tier's merge touches per-row statistics: per-client
    criterion logs are bitwise at every (S, pods); the delta reassembles
    through log2(P)+log2(S) merge adds -> tight fp tolerance."""
    rng = np.random.default_rng(1)
    n, d, chunk = 32, 23, 4
    rule, block_fn, args = _bound(name, n, d, rng)
    d_seq, _, logs_seq = stream_aggregate(rule, block_fn, args, chunk, d=d)
    d_p, _, logs_p = stream_aggregate(rule, block_fn, args, chunk, d=d,
                                      pods=pods, shards=shards)
    for a, b in zip(jax.tree.leaves(logs_seq), jax.tree.leaves(logs_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_seq),
                               rtol=1e-5, atol=1e-6)


def test_two_tier_deterministic_per_pod_count():
    rng = np.random.default_rng(2)
    n, d, chunk = 32, 17, 4
    rule, block_fn, args = _bound("diversefl", n, d, rng)
    a = stream_aggregate(rule, block_fn, args, chunk, d=d, pods=4)[0]
    b = stream_aggregate(rule, block_fn, args, chunk, d=d, pods=4)[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# depth-2 monoid laws on exact data
# ----------------------------------------------------------------------

def _exact_oracle(rng, n, d):
    U = jnp.asarray(rng.integers(-8, 8, size=(n, d)).astype(np.float32))
    byz = jnp.asarray(rng.random(n) < 0.3)
    rule = get_streaming("oracle").bind(AggregationContext(byz_mask=byz))

    def block_fn(blk, valid):
        u_blk, byz_b = blk
        return u_blk, {"byz": byz_b}

    return rule, block_fn, (U, byz)


def test_exact_data_two_tier_equals_flat_bitwise():
    """With integer updates and 0/1 weights every add is exact, so the
    merge of pod partials reproduces the flat fold bit for bit at every
    (pods, shards) — both tiers change association, never math."""
    rng = np.random.default_rng(3)
    n, d, chunk = 32, 11, 2
    rule, block_fn, args = _exact_oracle(rng, n, d)
    ref = np.asarray(stream_aggregate(rule, block_fn, args, chunk, d=d)[0])
    for pods, shards in [(2, None), (4, None), (8, None), (2, 2), (4, 2)]:
        got = stream_aggregate(rule, block_fn, args, chunk, d=d,
                               pods=pods, shards=shards)[0]
        np.testing.assert_array_equal(np.asarray(got), ref)


def test_exact_data_pod_order_insensitive_under_canonical_association():
    """Depth-2 law: fold each pod's clients separately, merge the
    stacked per-pod partials with tree_merge — on exact data any pod
    permutation yields the same state (the monoid is commutative and
    every add exact), and the result matches the two-tier fold."""
    rng = np.random.default_rng(4)
    n, d, chunk, P = 32, 11, 2, 4
    rule, block_fn, (U, byz) = _exact_oracle(rng, n, d)
    per = n // P

    def pod_partial(p):
        lo, hi = p * per, (p + 1) * per
        # fold ONE pod's clients from the identity — tier 1 in isolation
        state = rule.init(d)
        for i in range(lo, hi):
            state, _ = rule.update(state, U[i], {"byz": byz[i]})
        return state

    parts = [pod_partial(p) for p in range(P)]
    ref = np.asarray(stream_aggregate(rule, block_fn, (U, byz), chunk,
                                      d=d, pods=P)[0])
    for order in ([0, 1, 2, 3], [3, 2, 1, 0], [2, 0, 3, 1]):
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[parts[i] for i in order])
        delta, _ = rule.finalize(tree_merge(rule.merge, stacked, P))
        np.testing.assert_array_equal(np.asarray(delta), ref)


# ----------------------------------------------------------------------
# partitioning primitives: resolve_pods / group_blocks_2d
# ----------------------------------------------------------------------

def test_resolve_pods_auto_clamps_explicit_raises():
    assert resolve_pods(None, 8, auto=4) == 4
    assert resolve_pods(None, 8, auto=3) == 2    # clamp like resolve_shards
    assert resolve_pods(None, 7, auto=4) == 1
    assert resolve_pods(2, 8) == 2
    with pytest.raises(ShardMismatchError, match="must divide"):
        resolve_pods(3, 8)
    with pytest.raises(ShardMismatchError, match="must divide"):
        resolve_pods(16, 8)
    with pytest.raises(ShardMismatchError, match=">= 1"):
        resolve_pods(0, 8)


def test_group_blocks_2d_shape_and_order():
    """(k, ...) -> (pods, shards, k/(P·S), ...) with pod-major,
    shard-contiguous block order — the layout the ("pod", "data")
    client placement produces."""
    k, P, S = 8, 2, 2
    blocks = jnp.arange(k * 3.0).reshape(k, 3)
    g = group_blocks_2d(blocks, k, P, S)
    assert g.shape == (P, S, k // (P * S), 3)
    np.testing.assert_array_equal(
        np.asarray(g.reshape(k, 3)), np.asarray(blocks))
    assert float(g[1, 0, 0, 0]) == float(blocks[4, 0])  # pod 1 starts at k/P


def test_group_blocks_2d_divisibility_errors():
    blocks = jnp.zeros((6, 2))
    with pytest.raises(ShardMismatchError, match="must divide"):
        group_blocks_2d(blocks, 6, 4, 1)
    with pytest.raises(ShardMismatchError, match="must divide"):
        group_blocks_2d(blocks, 6, 2, 2)


# ----------------------------------------------------------------------
# training level: FLConfig.pods
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def fed_data():
    x, y = make_classification(jax.random.PRNGKey(0), N_CLIENTS * 8,
                               N_CLASSES, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N_CLIENTS), N_CLASSES)
    tx, ty = make_classification(jax.random.PRNGKey(9), 64, N_CLASSES, DIM)
    return data, tx, ty


def _train(fed_data, **kw):
    data, tx, ty = fed_data
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    kw.setdefault("n_clients", N_CLIENTS)
    kw.setdefault("f", 12)
    kw.setdefault("rounds", 2)
    kw.setdefault("batch_size", 2)
    kw.setdefault("eval_every", 2)
    kw.setdefault("l2", 0.0)
    kw.setdefault("client_chunk", 8)
    kw.setdefault("streaming", True)
    kw.setdefault("attack", AttackConfig(kind="sign_flip"))
    cfg = FLConfig(**kw)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    return run_federated_training(model, fed, cfg, inv_sqrt_lr(0.05))


@pytest.mark.parametrize("aggregator", RULES)
def test_training_pods_one_is_single_tier(fed_data, aggregator):
    h_seq = _train(fed_data, aggregator=aggregator)
    h_p1 = _train(fed_data, aggregator=aggregator, pods=1)
    assert np.array_equal(_flat(h_seq["params"]), _flat(h_p1["params"]))


@pytest.mark.parametrize("pods", [2, 4])
def test_training_pods_close_and_masks_bitwise(fed_data, pods):
    h_seq = _train(fed_data)
    h_p = _train(fed_data, pods=pods)
    np.testing.assert_allclose(_flat(h_p["params"]), _flat(h_seq["params"]),
                               rtol=1e-5, atol=1e-6)
    # keep-mask counts derive from per-row stats -> bitwise at any P
    assert h_seq["mask_tpr"] == h_p["mask_tpr"]
    assert h_seq["mask_fpr"] == h_p["mask_fpr"]


def test_flconfig_pods_validation():
    base = dict(n_clients=N_CLIENTS, f=12, client_chunk=8, streaming=True)
    with pytest.raises(ValueError, match="pods must be None"):
        FLConfig(**base, pods=0)
    with pytest.raises(ValueError, match="requires streaming"):
        FLConfig(n_clients=N_CLIENTS, f=12, client_chunk=8,
                 streaming=False, pods=2)
    with pytest.raises(ValueError, match="requires client_chunk"):
        FLConfig(n_clients=N_CLIENTS, f=12, streaming=True, pods=2)
    with pytest.raises(ValueError, match="cannot tile"):
        FLConfig(**base, pods=3)       # k = 8 blocks, 3 does not divide
    assert FLConfig(**base, pods=2).pods == 2


# ----------------------------------------------------------------------
# sweep: pods is a structural axis — never batched across pod counts
# ----------------------------------------------------------------------

def test_sweep_pods_axis_is_structural():
    base = FLConfig(n_clients=N_CLIENTS, f=12, rounds=2, batch_size=2,
                    eval_every=2, client_chunk=8, streaming=True,
                    attack=AttackConfig(kind="sign_flip"))
    spec = SweepSpec(base=base, seeds=(0, 1), pods=(None, 1, 2))
    cells = spec.cells()
    assert len(cells) == 6
    groups = group_cells(cells)
    # one structural group per pod count: seeds batch, pods never do
    assert len(groups) == 3
    for members in groups.values():
        assert len({c.cfg.pods for _, c in members}) == 1
        assert len(members) == 2       # the two seeds batched together
    assert structural_key(cells[0].cfg) != structural_key(cells[2].cfg)


# ----------------------------------------------------------------------
# mesh execution (forced host devices)
# ----------------------------------------------------------------------

def test_pod_mesh_fold_and_pipeline_bitwise_subprocess():
    """On a forced-8-device host: (a) make_host_pod_mesh builds the
    ("pod", "data", "model") mesh and pod_data_counts sees it; (b)
    training under the pod mesh (pods auto-derived) matches the
    meshless run."""
    script = """
    import numpy as np, jax
    from repro.launch.mesh import make_host_pod_mesh, client_axes, n_clients
    from repro.sharding import (use_mesh, data_shard_count, pod_count,
                                pod_data_counts)
    from repro.data import (FederatedData, make_classification,
                            partition_sorted_shards)

    mesh = make_host_pod_mesh(pods=4, data=2, model=1)
    assert client_axes(mesh) == ("pod", "data") and n_clients(mesh) == 8

    N, DIM, NC = 16, 6, 4
    x, y = make_classification(jax.random.PRNGKey(0), N * 10, NC, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N), NC)
    with use_mesh(mesh):
        assert data_shard_count() == 8 and pod_count() == 4
        assert pod_data_counts() == (4, 2)

    from repro.core.attacks import AttackConfig
    from repro.fl import (FLConfig, Federation, run_federated_training,
                          softmax_regression)
    from repro.optim import inv_sqrt_lr
    tx, ty = make_classification(jax.random.PRNGKey(9), 64, NC, DIM)
    model = softmax_regression(input_dim=DIM, n_classes=NC)
    cfg = FLConfig(n_clients=N, f=3, rounds=2, batch_size=2, eval_every=2,
                   l2=0.0, client_chunk=2, streaming=True,
                   attack=AttackConfig(kind="sign_flip"))
    fed0 = Federation.create(model, data, tx, ty, cfg,
                             jax.random.PRNGKey(2))
    h0 = run_federated_training(model, fed0, cfg, inv_sqrt_lr(0.05))
    with use_mesh(mesh):
        fed = Federation.create(model, data, tx, ty, cfg,
                                jax.random.PRNGKey(2))
        h = run_federated_training(model, fed, cfg, inv_sqrt_lr(0.05))
    flat = lambda p: np.concatenate([np.asarray(v).ravel()
                                     for v in jax.tree.leaves(p)])
    assert np.allclose(flat(h["params"]), flat(h0["params"]),
                       rtol=1e-5, atol=1e-6)
    assert h["mask_tpr"] == h0["mask_tpr"]
    assert h["mask_fpr"] == h0["mask_fpr"]
    print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["TF_CPP_MIN_LOG_LEVEL"] = "2"
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert p.returncode == 0, \
        f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-4000:]}"
    assert "OK" in p.stdout


def test_host_pod_mesh_insufficient_devices_named_error():
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        from repro.launch.mesh import make_host_pod_mesh
        make_host_pod_mesh(pods=64, data=64, model=64)
