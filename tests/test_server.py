"""SecureServer + aggregator registry: completeness, equivalence to the
pre-refactor dispatch, and the enclave trust boundary (guides must be
computed from unsealed bytes only)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregators as agg
from repro.core.diversefl import (DiverseFLConfig, diversefl_mask,
                                  guiding_update, masked_mean_flat,
                                  similarity_stats_matrix)
from repro.fl.server import (AggregationContext, SecureServer, aggregate,
                             available_aggregators, get_aggregator)

# the dispatch names the seed's if/elif chain supported
LEGACY_AGGREGATORS = ("diversefl", "oracle", "mean", "median", "trimmed_mean",
                      "krum", "bulyan", "resampling", "fltrust")


def _fixtures(n=9, d=40, f=2, seed=0):
    rng = np.random.default_rng(seed)
    U = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    G = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    root = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    byz = jnp.zeros((n,), bool).at[:f].set(True)
    key = jax.random.PRNGKey(7)
    ctx = AggregationContext(key=key, f=f, byz_mask=byz, guides=G,
                             root_update=root, resample_s=2)
    return U, G, root, byz, key, ctx


# ----------------------------------------------------------------------
# registry completeness + equivalence with the pre-refactor code paths
# ----------------------------------------------------------------------

def test_registry_resolves_every_legacy_name():
    for name in LEGACY_AGGREGATORS:
        entry = get_aggregator(name)
        assert entry.name == name and callable(entry.fn)
    assert set(LEGACY_AGGREGATORS) <= set(available_aggregators())


def test_registry_unknown_name_raises():
    with pytest.raises(ValueError, match="unknown aggregator"):
        get_aggregator("nope")


def test_registry_matches_legacy_dispatch():
    """Each rule must produce the exact delta the seed's if/elif chain
    computed from the same inputs (fixed seed, same rng key)."""
    U, G, root, byz, key, ctx = _fixtures()
    dot, zz, gg = similarity_stats_matrix(U, G)
    mask = diversefl_mask(dot, zz, gg, ctx.dfl)
    expected = {
        "diversefl": agg.oracle_sgd(U, mask),
        "oracle": agg.oracle_sgd(U, ~byz),
        "mean": U.mean(0),
        "median": agg.median(U),
        "trimmed_mean": agg.trimmed_mean(U, ctx.f),
        "krum": agg.krum(U, ctx.f),
        "bulyan": agg.bulyan(U, ctx.f),
        "resampling": agg.resampling(U, key, ctx.resample_s),
        "fltrust": agg.fltrust(U, root),
    }
    for name, want in expected.items():
        delta, logs = aggregate(name, U, ctx)
        np.testing.assert_allclose(np.asarray(delta), np.asarray(want),
                                   rtol=1e-6, atol=1e-7, err_msg=name)
    # diversefl logs carry the criterion diagnostics
    _, logs = aggregate("diversefl", U, ctx)
    assert set(logs) >= {"mask", "c1", "c2", "c1c2"}
    np.testing.assert_array_equal(np.asarray(logs["mask"]), np.asarray(mask))


def test_diversefl_kernel_paths_agree_with_xla_path():
    """use_kernel_stats / use_kernel_agg route through Pallas (interpret
    mode on CPU) and must agree with the plain XLA path."""
    U, G, root, byz, key, ctx = _fixtures(n=5, d=300)
    base_delta, base_logs = aggregate("diversefl", U, ctx)
    for kw in ({"use_kernel_stats": True}, {"use_kernel_agg": True}):
        ctx_k = AggregationContext(key=key, f=ctx.f, byz_mask=byz, guides=G,
                                   root_update=root, **kw)
        delta, logs = aggregate("diversefl", U, ctx_k)
        np.testing.assert_allclose(np.asarray(delta), np.asarray(base_delta),
                                   rtol=1e-5, atol=1e-6, err_msg=str(kw))
        np.testing.assert_array_equal(np.asarray(logs["mask"]),
                                      np.asarray(base_logs["mask"]))


# ----------------------------------------------------------------------
# SecureServer trust boundary
# ----------------------------------------------------------------------

def _ingest(server, n_clients=3, s=4, d=6, seed=0):
    rng = np.random.default_rng(seed)
    for j in range(n_clients):
        server.ingest_samples(j, rng.normal(size=(s, d)).astype(np.float32),
                              rng.integers(0, 5, size=s).astype(np.int32))


def test_attestation_rejects_wrong_enclave_identity():
    from repro.core.tee import Enclave
    with pytest.raises(RuntimeError, match="attestation failed"):
        SecureServer(enclave=Enclave("evil-enclave"))


def test_guides_come_from_unsealed_bytes():
    """Tampering with the sealed blob must change the guide batch and the
    guiding update — proving the guide path reads through the enclave's
    sealed store, not a raw-sample side channel."""
    server = SecureServer()
    _ingest(server)
    gx1, gy1 = server.guide_batches()

    # flip the sealed *label* region of client 1's blob (stays valid int32)
    meta = server.enclave._meta[1]
    nx = 4 * int(np.prod(meta["x_shape"]))
    blob = bytearray(server.enclave._store[1])
    blob[nx:] = bytes(b ^ 0xFF for b in blob[nx:])
    server.enclave._store[1] = bytes(blob)

    gx2, gy2 = server.guide_batches(refresh=True)
    np.testing.assert_allclose(gx2[1], gx1[1])           # x region untouched
    assert np.asarray(gy2[1]).tobytes() != np.asarray(gy1[1]).tobytes()

    # the guiding update computed inside the enclave changes with it
    params = {"w": jnp.ones((6, 1))}

    def grad_fn(p, batch):
        x, y = batch
        tgt = y.astype(jnp.float32)[:, None]
        return jax.grad(lambda q: jnp.mean((x @ q["w"] - tgt) ** 2))(p)

    d1 = guiding_update(params, (gx1[1], gy1[1]), grad_fn, lr=0.1, E=1)
    d2 = guiding_update(params, (gx2[1], gy2[1]), grad_fn, lr=0.1, E=1)
    assert not np.allclose(np.asarray(d1["w"]), np.asarray(d2["w"]))


def test_guide_cache_invalidated_by_reseal():
    """Re-sealing through the enclave (as the sample-poisoning tests do)
    must be visible on the next guide_batches() call without an explicit
    refresh — the cache is keyed on the enclave's seal version."""
    server = SecureServer()
    _ingest(server)
    _, gy1 = server.guide_batches()
    x, y = server.enclave.unseal_samples(0)
    server.enclave.seal_samples(0, x, 4 - y)
    _, gy2 = server.guide_batches()
    np.testing.assert_array_equal(np.asarray(gy2[0]), 4 - np.asarray(gy1[0]))


def test_guide_batches_stay_id_aligned_after_drop():
    """Sec. IV-C: dropping a screened-out client must not shift the rows
    of other clients' guide batches, and the dropped id's zero guide can
    never pass the C1/C2 criterion."""
    server = SecureServer()
    _ingest(server, n_clients=5)
    gx_before, _ = server.guide_batches()
    server.drop_client(2)
    gx_after, _ = server.guide_batches()
    assert gx_after.shape == gx_before.shape
    for j in (0, 1, 3, 4):
        np.testing.assert_allclose(gx_after[j], gx_before[j], err_msg=str(j))
    np.testing.assert_array_equal(np.asarray(gx_after[2]), 0.0)
    # zero guide -> dot=0, ||g||²=0 -> rejected by the criterion
    assert not bool(diversefl_mask(jnp.float32(0.0), jnp.float32(1.0),
                                   jnp.float32(0.0), DiverseFLConfig()))


def test_guide_batches_rebuild_under_jit_raises():
    """A stale cache must not be rebuilt inside a trace: the stacked
    arrays would be tracers cached past the trace.  The eager cache stays
    usable, and the next eager call rebuilds it."""
    server = SecureServer()
    _ingest(server)
    gx0, _ = server.guide_batches()
    x, y = server.enclave.unseal_samples(0)
    server.enclave.seal_samples(0, x, 4 - y)       # stale: version moved

    @jax.jit
    def traced(z):
        gx, _ = server.guide_batches()
        return z + gx.sum()

    with pytest.raises(RuntimeError, match="under an active JAX trace"):
        traced(jnp.float32(0.0))
    assert server._guide_cache[1] is gx0           # no tracer was cached
    _, gy = server.guide_batches()
    np.testing.assert_array_equal(np.asarray(gy[0]), 4 - np.asarray(y))


def test_guide_batches_empty_store_raises():
    server = SecureServer()
    with pytest.raises(RuntimeError, match="no sealed samples"):
        server.guide_batches()


def test_compute_guides_matches_direct_guiding_update():
    server = SecureServer()
    _ingest(server)
    gx, gy = server.guide_batches()
    params = {"w": jnp.full((6, 1), 0.5)}

    def grad_fn(p, batch):
        x, y = batch
        tgt = y.astype(jnp.float32)[:, None]
        return jax.grad(lambda q: jnp.mean((x @ q["w"] - tgt) ** 2))(p)

    guides = server.compute_guides(params, grad_fn, lr=0.05, E=2)
    for j in range(3):
        want = guiding_update(params, (gx[j], gy[j]), grad_fn, lr=0.05, E=2)
        np.testing.assert_allclose(guides["w"][j], want["w"], rtol=1e-6)


def test_oracle_and_diversefl_share_masked_mean():
    """One source of truth for Eq. 6: the registry's masked aggregation is
    core.diversefl.masked_mean_flat."""
    U, G, root, byz, key, ctx = _fixtures()
    delta, _ = aggregate("oracle", U, ctx)
    np.testing.assert_allclose(np.asarray(delta),
                               np.asarray(masked_mean_flat(U, ~byz)),
                               rtol=1e-6)
