"""Shard-parallel streaming fold (ISSUE 4, DESIGN.md §7).

The contract tested here: the S-way fold — contiguous block groups,
each folded with the PR 3 left fold, partial AggStates combined by
``tree_merge``'s canonical fixed association — is a **pure function of
(client order, chunk, S)**:

  * ``S == 1`` *is* the sequential sweep — bitwise, for every streaming
    rule (no merge happens at all);
  * per-client criterion logs are bitwise-identical at every S (the
    fold association never touches per-row statistics);
  * executing the same S-way fold on an S-shard mesh is bitwise-equal
    to executing it sequentially on one device (subprocess test with
    forced host devices) — parallel placement cannot change the bits;
  * across *different* shard counts the delta agrees to fp tolerance
    (the log2(S) merge adds reassociate — documented, not hidden).
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
                      softmax_regression, stream_aggregate, streaming_rules,
                      tree_merge)
from repro.fl.chunking import group_blocks, resolve_shards
from repro.fl.server import AggregationContext
from repro.fl.streaming import get_streaming
from repro.optim import inv_sqrt_lr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_CLIENTS, DIM, N_CLASSES = 64, 8, 4


def _flat(params):
    return np.concatenate(
        [np.asarray(v).ravel() for v in jax.tree.leaves(params)])


# ----------------------------------------------------------------------
# the fold itself: stream_aggregate at S ∈ {1, 2, 4} per rule
# ----------------------------------------------------------------------

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


@pytest.mark.parametrize("name", ["mean", "oracle", "diversefl", "fltrust"])
def test_one_shard_is_sequential_bitwise(name):
    rng = np.random.default_rng(0)
    n, d, chunk = 32, 23, 4
    rule, block_fn, args = _bound(name, n, d, rng)
    d_seq, _, logs_seq = stream_aggregate(rule, block_fn, args, chunk, d=d)
    d_s1, _, logs_s1 = stream_aggregate(rule, block_fn, args, chunk, d=d,
                                        shards=1)
    np.testing.assert_array_equal(np.asarray(d_seq), np.asarray(d_s1))
    for a, b in zip(jax.tree.leaves(logs_seq), jax.tree.leaves(logs_s1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", ["mean", "oracle", "diversefl", "fltrust"])
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_fold_per_client_logs_bitwise(name, shards):
    """The merge association never touches per-row statistics: criterion
    logs are bitwise at every shard count."""
    rng = np.random.default_rng(1)
    n, d, chunk = 32, 23, 4
    rule, block_fn, args = _bound(name, n, d, rng)
    d_seq, _, logs_seq = stream_aggregate(rule, block_fn, args, chunk, d=d)
    d_s, _, logs_s = stream_aggregate(rule, block_fn, args, chunk, d=d,
                                      shards=shards)
    for a, b in zip(jax.tree.leaves(logs_seq), jax.tree.leaves(logs_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # delta: S-1 merge adds reassociate -> tight fp tolerance, not bitwise
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_seq),
                               rtol=1e-5, atol=1e-6)


def test_sharded_fold_deterministic_per_shard_count():
    """Same S -> same bits, run to run: the association is a pure
    function of (client order, chunk, S)."""
    rng = np.random.default_rng(2)
    n, d, chunk = 32, 17, 4
    rule, block_fn, args = _bound("diversefl", n, d, rng)
    a, _, _ = stream_aggregate(rule, block_fn, args, chunk, d=d, shards=4)
    b, _, _ = stream_aggregate(rule, block_fn, args, chunk, d=d, shards=4)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_exact_data_sharded_equals_sequential_bitwise():
    """With integer-valued updates and 0/1 weights every add is exact,
    so the S-way tree-merge reproduces the sequential fold bit-for-bit —
    the merge changes association, never the math."""
    rng = np.random.default_rng(3)
    n, d, chunk = 16, 11, 2
    U = jnp.asarray(rng.integers(-8, 8, size=(n, d)).astype(np.float32))
    byz = jnp.asarray(rng.random(n) < 0.3)
    rule = get_streaming("oracle").bind(AggregationContext(byz_mask=byz))

    def block_fn(blk, valid):
        u_blk, byz_b = blk
        return u_blk, {"byz": byz_b}

    d_seq, _, _ = stream_aggregate(rule, block_fn, (U, byz), chunk, d=d)
    for s in (2, 4):
        d_s, _, _ = stream_aggregate(rule, block_fn, (U, byz), chunk, d=d,
                                     shards=s)
        np.testing.assert_array_equal(np.asarray(d_s), np.asarray(d_seq))


# ----------------------------------------------------------------------
# tree_merge: the canonical association
# ----------------------------------------------------------------------

def test_tree_merge_canonical_order():
    """tree_merge(n) == the documented balanced pairwise order — checked
    against a hand-rolled reference, including the odd-tail case."""
    calls = []

    def merge(a, b):
        calls.append((a[1], b[1]))
        return (a[0] + b[0], f"({a[1]}+{b[1]})")

    states = (jnp.arange(5.0), np.array(["s0", "s1", "s2", "s3", "s4"]))
    # hand-build the stacked pytree: leaves with leading axis n
    stacked = (jnp.stack([states[0] + i for i in range(5)]), states[1])
    out = tree_merge(merge, stacked, 5)
    assert out[1] == "(((s0+s1)+(s2+s3))+s4)"


def test_tree_merge_single_state_is_identity():
    state = (jnp.arange(3.0)[None], jnp.ones((1,)))
    out = tree_merge(lambda a, b: pytest.fail("no merge at n=1"), state, 1)
    np.testing.assert_array_equal(np.asarray(out[0]), np.arange(3.0))


def test_resolve_shards_clamps_to_divisor():
    assert resolve_shards(4, 8) == 4
    assert resolve_shards(3, 8) == 2     # largest divisor of 8 below 3
    assert resolve_shards(5, 12) == 4
    assert resolve_shards(16, 4) == 4    # never exceeds the block count
    assert resolve_shards(1, 7) == 1
    assert resolve_shards(7, 7) == 7


def test_group_blocks_requires_divisibility():
    blocks = jnp.zeros((6, 2, 3))
    grouped = group_blocks(blocks, 6, 3)
    assert grouped.shape == (3, 2, 2, 3)
    with pytest.raises(ValueError, match="must divide"):
        group_blocks(blocks, 6, 4)


# ----------------------------------------------------------------------
# training level: FLConfig.stream_shards
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


@pytest.mark.parametrize("aggregator", ["diversefl", "oracle", "mean",
                                        "fltrust"])
def test_training_stream_shards_one_is_sequential(fed_data, aggregator):
    h_seq = _train(fed_data, aggregator=aggregator)
    h_s1 = _train(fed_data, aggregator=aggregator, stream_shards=1)
    assert np.array_equal(_flat(h_seq["params"]), _flat(h_s1["params"]))


@pytest.mark.parametrize("shards", [2, 4])
def test_training_stream_shards_close_and_masks_bitwise(fed_data, shards):
    h_seq = _train(fed_data)
    h_s = _train(fed_data, stream_shards=shards)
    np.testing.assert_allclose(_flat(h_s["params"]), _flat(h_seq["params"]),
                               rtol=1e-5, atol=1e-6)
    # keep-mask counts derive from per-row stats -> bitwise at any S
    assert h_seq["mask_tpr"] == h_s["mask_tpr"]
    assert h_seq["mask_fpr"] == h_s["mask_fpr"]


def test_every_streaming_rule_covered():
    assert set(streaming_rules()) == {"mean", "oracle", "diversefl",
                                      "fltrust"}


def test_sharded_kernel_block_fold_runs(fed_data):
    """use_kernel_agg's per-block Pallas fold composes with the shard
    groups (the kernel vmaps over group lanes); block association was
    already fp-tolerance, so the merge adds stay inside it."""
    h_seq = _train(fed_data)
    h_k = _train(fed_data, use_kernel_agg=True, stream_shards=2)
    np.testing.assert_allclose(_flat(h_k["params"]), _flat(h_seq["params"]),
                               rtol=1e-4, atol=1e-6)


# ----------------------------------------------------------------------
# mesh execution: S shards on S devices == the same fold on one device
# ----------------------------------------------------------------------

def test_mesh_sharded_fold_bitwise_subprocess():
    """At 1/2/4 mesh shards the shard-parallel sweep (client/group axis
    sharded over the mesh's data axes, auto shard count) is bitwise-
    equal to the same fold executed sequentially without a mesh, for
    every streaming rule — parallel placement cannot change the bits."""
    script = """
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.attacks import AttackConfig
    from repro.data import FederatedData, make_classification, \\
        partition_sorted_shards
    from repro.fl import (FLConfig, Federation, RoundEngine,
                          softmax_regression)
    from repro.optim import inv_sqrt_lr

    N, DIM, NC = 64, 8, 4
    x, y = make_classification(jax.random.PRNGKey(0), N * 8, NC, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N), NC)
    tx, ty = make_classification(jax.random.PRNGKey(9), 64, NC, DIM)
    model = softmax_regression(input_dim=DIM, n_classes=NC)

    def flat(p):
        return np.concatenate([np.asarray(v).ravel()
                               for v in jax.tree.leaves(p)])

    def segment(agg, mesh=None, **kw):
        cfg = FLConfig(n_clients=N, f=12, rounds=2, batch_size=2,
                       eval_every=2, l2=0.0, client_chunk=8, streaming=True,
                       aggregator=agg, attack=AttackConfig(kind="sign_flip"),
                       **kw)
        fed = Federation.create(model, data, tx, ty, cfg,
                                jax.random.PRNGKey(2))
        eng = RoundEngine(model, fed, cfg, mesh=mesh)
        params = model.init(jax.random.PRNGKey(1))
        lrs = [float(inv_sqrt_lr(0.05)(r)) for r in (1, 2)]
        p, _, logs = eng.run_segment(params, jax.random.PRNGKey(0), lrs)
        return flat(p), logs

    for agg in ("diversefl", "oracle", "mean", "fltrust"):
        for S in (1, 2, 4):
            mesh = Mesh(np.array(jax.devices()[:S]).reshape(S, 1),
                        ("data", "model"))
            # the mesh run auto-resolves shards = S from the data axes;
            # the reference runs the same S-way fold on one device
            p_mesh, lg_mesh = segment(agg, mesh=mesh)
            p_ref, lg_ref = segment(agg, stream_shards=S)
            if agg == "fltrust":
                # pre-existing, fold-independent: fltrust's trust-score
                # sqrt/div subgraph fuses differently once the SPMD
                # partitioner splits the program (1 ULP even with the
                # fold forced sequential on the mesh) — tight tolerance
                assert np.allclose(p_mesh, p_ref, rtol=1e-6,
                                   atol=1e-8), (agg, S)
            else:
                assert np.array_equal(p_mesh, p_ref), (agg, S)
            if "mask" in lg_mesh:
                assert np.array_equal(np.asarray(lg_mesh["mask"]),
                                      np.asarray(lg_ref["mask"])), (agg, S)
    print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["TF_CPP_MIN_LOG_LEVEL"] = "2"
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert p.returncode == 0, \
        f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-4000:]}"
    assert "OK" in p.stdout
