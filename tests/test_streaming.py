"""Streaming aggregation subsystem (ISSUE 3, DESIGN.md §6).

Three contracts:

  * **streaming == dense, bitwise** — for the masked-mean family
    (``diversefl``, ``oracle``, ``mean``) the streaming fold reproduces
    the dense (N, D) path bit for bit: delta, params trajectory and the
    per-client criterion logs, at N=256, at any chunk size (divisible or
    not), with full and partial participation.  Non-associative rules
    fall back to the dense path (bitwise trivially) with the reason
    exposed on the engine.
  * **AggState is a monoid** — ``merge`` is associative, ``init`` is its
    identity, and folding the same clients in a different chunk order
    merges to the same state (exact on integer-valued floats, fp
    tolerance on generic ones) for every registered streaming rule.
  * **chunked_vmap edge cases** — N < chunk and N not divisible by chunk
    take the padded-block path and still equal plain vmap exactly.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core.attacks import AttackConfig
from repro.core.diversefl import masked_mean_flat
from repro.data import FederatedData, make_classification
from repro.data.partition import partition_sorted_shards
from repro.fl import (FLConfig, Federation, RoundEngine, chunked_vmap,
                      fallback_reason, get_streaming,
                      run_federated_training, softmax_regression,
                      streaming_rules, telemetry)
from repro.kernels import ops as kops
from repro.kernels.masked_agg import fold_layout
from repro.fl.server import KERNEL_AGG_RULES, AggregationContext, aggregate
from repro.fl.streaming import NON_STREAMING, stream_aggregate
from repro.optim import inv_sqrt_lr

N_CLIENTS, DIM, N_CLASSES = 256, 8, 4


@pytest.fixture(scope="module")
def fed_data():
    x, y = make_classification(jax.random.PRNGKey(0), N_CLIENTS * 8,
                               N_CLASSES, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N_CLIENTS), N_CLASSES)
    tx, ty = make_classification(jax.random.PRNGKey(9), 64, N_CLASSES, DIM)
    return data, tx, ty


def _cfg(**kw):
    kw.setdefault("n_clients", N_CLIENTS)
    kw.setdefault("f", N_CLIENTS // 5)
    kw.setdefault("rounds", 2)
    kw.setdefault("batch_size", 2)
    kw.setdefault("eval_every", 2)
    kw.setdefault("l2", 0.0)
    kw.setdefault("attack", AttackConfig(kind="sign_flip"))
    return FLConfig(**kw)


def _train(fed_data, cfg):
    data, tx, ty = fed_data
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    return run_federated_training(model, fed, cfg, inv_sqrt_lr(0.05))


def _flat(params):
    return np.concatenate(
        [np.asarray(v).ravel() for v in jax.tree.leaves(params)])


# ----------------------------------------------------------------------
# streaming == dense: bitwise for the masked-mean family at N=256
# ----------------------------------------------------------------------

@pytest.mark.parametrize("aggregator", ["diversefl", "oracle", "mean"])
def test_streaming_matches_dense_bitwise(fed_data, aggregator):
    """The acceptance contract: same chunking, streaming=True folds the
    AggState to the exact bits the dense (N, D) path produces."""
    h_dense = _train(fed_data, _cfg(aggregator=aggregator, client_chunk=64))
    h_strm = _train(fed_data, _cfg(aggregator=aggregator, client_chunk=64,
                                   streaming=True))
    assert np.array_equal(_flat(h_dense["params"]), _flat(h_strm["params"]))
    assert h_dense["acc"] == h_strm["acc"]
    assert h_dense["mask_tpr"] == h_strm["mask_tpr"]
    assert h_dense["mask_fpr"] == h_strm["mask_fpr"]
    if h_dense["c1c2"]:                       # criterion logs, bit for bit
        np.testing.assert_array_equal(h_dense["c1c2"][-1], h_strm["c1c2"][-1])


def test_streaming_partial_participation_bitwise(fed_data):
    """C = ceil(0.5·N) selected ids, non-divisible chunk (pad + valid
    masking): still bitwise."""
    kw = dict(aggregator="diversefl", participation=0.5, client_chunk=48,
              attack=AttackConfig(kind="gaussian"))
    h_dense = _train(fed_data, _cfg(**kw))
    h_strm = _train(fed_data, _cfg(streaming=True, **kw))
    assert np.array_equal(_flat(h_dense["params"]), _flat(h_strm["params"]))
    assert h_dense["mask_tpr"] == h_strm["mask_tpr"]


def test_streaming_unchunked_single_block_bitwise(fed_data):
    """client_chunk=None folds one C-sized block — same bits again."""
    h_dense = _train(fed_data, _cfg(aggregator="oracle"))
    h_strm = _train(fed_data, _cfg(aggregator="oracle", streaming=True))
    assert np.array_equal(_flat(h_dense["params"]), _flat(h_strm["params"]))


def test_streaming_fltrust_weighted_mean(fed_data):
    """fltrust streams as a weighted mean (dense uses matvec cosine —
    different association, so fp tolerance, not bitwise)."""
    h_dense = _train(fed_data, _cfg(aggregator="fltrust", client_chunk=64))
    h_strm = _train(fed_data, _cfg(aggregator="fltrust", client_chunk=64,
                                   streaming=True))
    np.testing.assert_allclose(_flat(h_strm["params"]),
                               _flat(h_dense["params"]),
                               rtol=1e-5, atol=1e-6)


def test_streaming_kernel_path(fed_data):
    """use_kernel_agg accumulates per block through the streaming Pallas
    kernel (interpret mode on CPU) — block association, fp tolerance."""
    kw = dict(aggregator="diversefl", client_chunk=64)
    h_dense = _train(fed_data, _cfg(**kw))
    h_kern = _train(fed_data, _cfg(streaming=True, use_kernel_agg=True, **kw))
    np.testing.assert_allclose(_flat(h_kern["params"]),
                               _flat(h_dense["params"]),
                               rtol=1e-4, atol=1e-6)


# the kernel fold's accumulator layout (kernels/masked_agg.fold_layout):
# D a multiple of 512, of 128 only, and of neither (the (1, D) row)
FOLD_WIDTHS = {"d512": 512 * 9, "d128": 128 * 13, "row": 1000}


def _sweep_rows(d):
    """Updates and guides of 10 clients: client 4 sends a non-finite
    update and the guides of clients 1 and 7 point away."""
    rng = np.random.default_rng(d)
    U = rng.normal(size=(10, d)).astype(np.float32)
    G = U + 0.3 * rng.normal(size=U.shape).astype(np.float32)
    G[[1, 7]] *= -1
    U[4, 5] = np.inf
    return U, G


def _diversefl_sweep(d, chunk, merge, kernel):
    """The streaming diversefl rule over ``_sweep_rows`` in blocks of
    ``chunk``.  ``merge``: one sweep (``plain``), two shard groups
    (``shards``), or two landed async rows merged before finalize
    (``async``).  Returns (delta, client logs, fold_layout events)."""
    U, G = map(jnp.asarray, _sweep_rows(d))
    byz = jnp.zeros((10,), bool)
    rule = get_streaming("diversefl").bind(AggregationContext(
        byz_mask=byz, use_kernel_agg=kernel))

    def block_fn(blk, valid):
        u, g, b = blk
        return u, {"guide": g, "byz": b}

    with telemetry.recording() as rec:
        extra = None
        if merge == "async":
            ctx = {"guide": G[:2], "byz": byz[:2],
                   "valid": jnp.ones((2,), jnp.float32)}
            extra, _ = jax.lax.scan(
                lambda st, r: rule.update(st, r[0], r[1]), rule.init(d),
                (U[:2] * 0.5, ctx))
        delta, _, logs = stream_aggregate(
            rule, block_fn, (U, G, byz), chunk, d=d, prefer_block=kernel,
            shards=2 if merge == "shards" else 1, extra_state=extra)
    events = [{k: r[k] for k in ("rows", "lanes", "view")}
              for r in rec.records if r.get("kind") == "fold_layout"]
    return delta, logs, events


def _todays_kernel_fold(d):
    """The chunk-1 sweep as the flat (D,) accumulator folded it through
    ``ops.masked_agg_update`` one client at a time."""
    U, G = _sweep_rows(d)
    rule = get_streaming("diversefl").bind(AggregationContext())
    s, n = jnp.zeros((d,), jnp.float32), jnp.float32(0.0)
    for i in range(10):
        a, b, _ = rule.weights(jnp.asarray(U[i:i + 1]), {
            "guide": jnp.asarray(G[i:i + 1]), "byz": jnp.zeros((1,), bool),
            "valid": jnp.ones((1,), jnp.float32)})
        u = np.where(np.isfinite(U[i]).all(), U[i], 0.0)[None]
        s = kops.masked_agg_update(jnp.asarray(u), a, s)
        n = n + jnp.sum(b)
    return s / jnp.maximum(n, 1.0)


@pytest.mark.parametrize("merge", ["plain", "shards", "async"])
@pytest.mark.parametrize("chunk", [1, 3])
@pytest.mark.parametrize("width", sorted(FOLD_WIDTHS))
def test_kernel_fold_layout_matches_row_fold(width, chunk, merge):
    """With ``use_kernel_agg`` the numerator lives in the Pallas fold's
    own 2-D layout for the whole sweep (init, update_block, update,
    merge, finalize) and the fold matches the row fold: bit for bit at
    client_chunk 1 (each element is still acc + w·u, as the flat
    accumulator's kernel folded it), to the kernel path's tolerance in
    blocks of 3 with padding rows.  Keep mask and C1/C2 logs are the
    row fold's bits, and the non-finite client gets weight 0 and leaves
    the delta finite."""
    d = FOLD_WIDTHS[width]
    delta, logs, events = _diversefl_sweep(d, chunk, merge, kernel=True)
    want, want_logs, row_events = _diversefl_sweep(d, chunk, merge,
                                                   kernel=False)
    rows, lanes = fold_layout(d)
    assert (lanes % 128 == 0) == (width != "row")
    assert events and all(e == {"rows": rows, "lanes": lanes,
                                "view": width != "row"} for e in events)
    assert not row_events
    assert delta.shape == want.shape == (d,)
    assert np.isfinite(np.asarray(delta)).all()
    assert set(logs) == set(want_logs)
    for k in logs:
        np.testing.assert_array_equal(np.asarray(logs[k]),
                                      np.asarray(want_logs[k]), err_msg=k)
    assert bool(logs["nonfinite"][4]) and not bool(logs["mask"][4])
    assert not bool(logs["mask"][1]) and not bool(logs["mask"][7])
    if chunk == 1:
        np.testing.assert_array_equal(np.asarray(delta), np.asarray(want))
        if merge == "plain":
            np.testing.assert_array_equal(np.asarray(delta),
                                          np.asarray(_todays_kernel_fold(d)))
    else:
        np.testing.assert_allclose(np.asarray(delta), np.asarray(want),
                                   rtol=1e-4, atol=1e-6)


# ----------------------------------------------------------------------
# fallback: non-associative rules stay dense, with the reason exposed
# ----------------------------------------------------------------------

def test_streaming_fallback_is_dense_and_logged(fed_data, caplog):
    data, tx, ty = fed_data
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    cfg = _cfg(aggregator="median", streaming=True, client_chunk=64)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    with caplog.at_level(logging.WARNING, logger="repro.fl.engine"):
        engine = RoundEngine(model, fed, cfg)
    assert not engine.streaming
    assert "median" in engine.streaming_fallback
    assert any("falling back" in r.message for r in caplog.records)
    # and the fallback path is numerically the dense path, trivially
    h_strm = _train(fed_data, cfg)
    h_dense = _train(fed_data, _cfg(aggregator="median", client_chunk=64))
    assert np.array_equal(_flat(h_strm["params"]), _flat(h_dense["params"]))


def test_server_streaming_aggregator_accessor():
    """SecureServer stays the aggregation choke point: the engine binds
    streaming rules through it, and dense-only names return None."""
    from repro.fl import SecureServer
    ctx = AggregationContext(byz_mask=jnp.zeros((3,), bool))
    rule = SecureServer.streaming_aggregator("oracle", ctx)
    assert rule is not None and callable(rule.update)
    assert SecureServer.streaming_aggregator("median", ctx) is None


def test_fallback_reasons_cover_non_associative_rules():
    for name in ("median", "trimmed_mean", "krum", "bulyan", "resampling"):
        assert get_streaming(name) is None
        assert fallback_reason(name) == NON_STREAMING[name]
    for name in ("mean", "oracle", "diversefl", "fltrust"):
        assert get_streaming(name) is not None
        assert fallback_reason(name) is None
    assert set(streaming_rules()) == {"mean", "oracle", "diversefl",
                                      "fltrust"}


def test_use_kernel_agg_outside_family_raises():
    for name in ("median", "krum", "bulyan", "resampling", "trimmed_mean"):
        with pytest.raises(ValueError, match="weighted-mean"):
            FLConfig(aggregator=name, use_kernel_agg=True)
    for name in KERNEL_AGG_RULES:
        FLConfig(aggregator=name, use_kernel_agg=True)   # must not raise
    # every streaming rule is in the kernel family and vice versa: the
    # two capability lists cannot disagree
    assert set(KERNEL_AGG_RULES) == set(streaming_rules())


def test_dense_fltrust_kernel_path_matches_xla():
    """The dense fltrust kernel leg (weighted-mean form through the
    streaming Pallas kernel) agrees with aggregators.fltrust."""
    rng = np.random.default_rng(5)
    U = jnp.asarray(rng.normal(size=(9, 120)).astype(np.float32))
    root = jnp.asarray(rng.normal(size=(120,)).astype(np.float32))
    base, _ = aggregate("fltrust", U, AggregationContext(root_update=root))
    kern, _ = aggregate("fltrust", U, AggregationContext(
        root_update=root, use_kernel_agg=True))
    np.testing.assert_allclose(np.asarray(kern), np.asarray(base),
                               rtol=1e-5, atol=1e-6)


def test_streaming_kernel_stats_without_kernel_agg_raises():
    """use_kernel_stats is unreachable on the streaming row-fold path —
    rejected instead of silently ignored (same class of fix as above)."""
    with pytest.raises(ValueError, match="use_kernel_stats"):
        FLConfig(aggregator="diversefl", streaming=True,
                 use_kernel_stats=True)
    # reachable combinations must not raise
    FLConfig(aggregator="diversefl", streaming=True, use_kernel_stats=True,
             use_kernel_agg=True)
    FLConfig(aggregator="diversefl", use_kernel_stats=True)


# ----------------------------------------------------------------------
# AggState monoid laws: associativity + chunk-order insensitivity
# ----------------------------------------------------------------------

def _bound_rule(name, n, d, rng):
    """A bound streaming rule plus per-client (u, ctx) rows for it."""
    U = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    G = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    byz = jnp.asarray(rng.random(n) < 0.3)
    root = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    ctx = AggregationContext(byz_mask=byz, guides=G, root_update=root)
    rule = get_streaming(name).bind(ctx)
    rows = [(U[i], {"guide": G[i], "byz": byz[i],
                    "valid": jnp.asarray(True)}) for i in range(n)]
    return rule, rows


def _fold(rule, rows, d):
    state = rule.init(d)
    for u, ci in rows:
        state, _ = rule.update(state, u, ci)
    return state


def _assert_states_close(a, b, **tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **tol)


@given(st.sampled_from(["mean", "oracle", "diversefl", "fltrust"]),
       st.integers(min_value=0, max_value=3))
@settings(max_examples=16, deadline=None)
def test_merge_is_associative(name, seed):
    """merge(merge(a,b),c) == merge(a,merge(b,c)) — exact: merge is a
    componentwise add of two states, no data-dependent order."""
    rng = np.random.default_rng(seed)
    d = 17
    rule, rows = _bound_rule(name, 9, d, rng)
    a = _fold(rule, rows[:3], d)
    b = _fold(rule, rows[3:6], d)
    c = _fold(rule, rows[6:], d)
    left = rule.merge(rule.merge(a, b), c)
    right = rule.merge(a, rule.merge(b, c))
    # one fp add each side, same operands -> tight tolerance
    _assert_states_close(left, right, rtol=1e-6, atol=1e-7)


@given(st.sampled_from(["mean", "oracle", "diversefl"]))
@settings(max_examples=8, deadline=None)
def test_merge_identity_and_exact_associativity(name):
    """With integer-valued updates and 0/1 weights the fp adds are exact:
    the monoid laws hold bitwise, and init is the identity.  (fltrust's
    trust-score weights are irrational — it is covered by the
    fp-tolerance associativity tests above.)"""
    rng = np.random.default_rng(0)
    d = 11
    rule, _ = _bound_rule(name, 3, d, rng)
    U = jnp.asarray(rng.integers(-8, 8, size=(6, d)).astype(np.float32))
    G = jnp.asarray(np.sign(np.asarray(U)) * 1.0)   # keeps diversefl masks on
    rows = [(U[i], {"guide": G[i], "byz": jnp.asarray(False),
                    "valid": jnp.asarray(True)}) for i in range(6)]
    a = _fold(rule, rows[:2], d)
    b = _fold(rule, rows[2:4], d)
    c = _fold(rule, rows[4:], d)
    for x, y in zip(jax.tree.leaves(rule.merge(rule.merge(a, b), c)),
                    jax.tree.leaves(rule.merge(a, rule.merge(b, c)))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(rule.merge(rule.init(d), a)),
                    jax.tree.leaves(a)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@given(st.sampled_from(["mean", "oracle", "diversefl", "fltrust"]),
       st.integers(min_value=1, max_value=4))
@settings(max_examples=16, deadline=None)
def test_chunk_order_insensitive(name, n_chunks):
    """Folding disjoint chunks and merging in any order finalizes to the
    same delta (fp tolerance; + is commutative in value)."""
    rng = np.random.default_rng(n_chunks)
    d, n = 13, 12
    rule, rows = _bound_rule(name, n, d, rng)
    bounds = np.linspace(0, n, n_chunks + 1).astype(int)
    partials = [_fold(rule, rows[lo:hi], d)
                for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo]
    fwd = partials[0]
    for p in partials[1:]:
        fwd = rule.merge(fwd, p)
    rev = partials[-1]
    for p in reversed(partials[:-1]):
        rev = rule.merge(p, rev)
    d_fwd, _ = rule.finalize(fwd)
    d_rev, _ = rule.finalize(rev)
    np.testing.assert_allclose(np.asarray(d_fwd), np.asarray(d_rev),
                               rtol=1e-5, atol=1e-6)


def test_update_matches_merge_of_singleton():
    """update(s, u, c) == merge(s, update(init, u, c)) up to fp rounding —
    the associativity contract between update and merge."""
    rng = np.random.default_rng(3)
    d = 19
    rule, rows = _bound_rule("diversefl", 5, d, rng)
    state = _fold(rule, rows[:4], d)
    via_update, _ = rule.update(state, *rows[4])
    singleton, _ = rule.update(rule.init(d), *rows[4])
    via_merge = rule.merge(state, singleton)
    _assert_states_close(via_update, via_merge, rtol=1e-6, atol=1e-7)


def test_stream_aggregate_matches_dense_masked_mean():
    """The sweep itself (pad + valid + fold) against the canonical dense
    reduction, bitwise, at a non-divisible chunk."""
    rng = np.random.default_rng(1)
    n, d = 37, 29
    U = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    byz = jnp.asarray(rng.random(n) < 0.3)
    rule = get_streaming("oracle").bind(AggregationContext(byz_mask=byz))

    def block_fn(blk, valid):
        u_blk, byz_b = blk
        return u_blk, {"byz": byz_b}

    delta, _, clogs = stream_aggregate(rule, block_fn, (U, byz), 8, d=d)
    want = masked_mean_flat(U, ~byz)
    np.testing.assert_array_equal(np.asarray(delta), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(clogs["mask"]),
                                  np.asarray(~byz))


# ----------------------------------------------------------------------
# chunked_vmap edge cases (satellite): N < chunk, N % chunk != 0
# ----------------------------------------------------------------------

def test_chunked_vmap_n_smaller_than_chunk():
    """chunk >= N must be *exactly* the vmap path (same traced graph)."""
    xs = jnp.arange(15.0).reshape(5, 3)
    fn = lambda row: (row * row, jnp.sum(row))
    want = jax.vmap(fn)(xs)
    for chunk in (5, 6, 100):
        got = chunked_vmap(fn, (xs,), chunk)
        for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_pad_to_blocks_rejects_chunk_over_c():
    """The shared partition helper fails loudly instead of with an opaque
    reshape error when a new consumer forgets the chunk >= C clamp."""
    from repro.fl.chunking import pad_to_blocks
    with pytest.raises(ValueError, match="exceeds the leading axis"):
        pad_to_blocks((jnp.ones((3, 2)),), 8)


@pytest.mark.parametrize("n,chunk", [(7, 3), (7, 4), (7, 6), (5, 2), (1, 3)])
def test_chunked_vmap_non_divisible_pytree(n, chunk):
    """Padded blocks with pytree args and multi-output fn: padding rows
    never reach the output, rows stay aligned."""
    xs = {"a": jnp.arange(float(n * 3)).reshape(n, 3),
          "b": jnp.arange(float(n)) * 0.5}
    fn = lambda t: {"s": jnp.sum(t["a"]) + t["b"], "v": t["a"] * 2.0}
    want = jax.vmap(fn)(xs)
    got = chunked_vmap(fn, (xs,), chunk)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
