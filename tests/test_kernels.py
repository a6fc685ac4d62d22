"""Per-kernel correctness: shape/dtype sweeps against the pure-jnp oracles
(interpret mode executes the same kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref


# ----------------------------------------------------------------------
# similarity
# ----------------------------------------------------------------------

@pytest.mark.parametrize("n,d,chunk,dtype", [
    (1, 128, 128, jnp.float32),
    (5, 1000, 256, jnp.float32),      # pad path
    (8, 4096, 1024, jnp.bfloat16),
    (3, 70, 512, jnp.float32),        # d < chunk
])
def test_similarity_shapes(n, d, chunk, dtype):
    rng = np.random.default_rng(d)
    z = jnp.asarray(rng.normal(size=(n, d))).astype(dtype)
    g = jnp.asarray(rng.normal(size=(n, d))).astype(dtype)
    got = ops.similarity_stats(z, g, chunk=chunk)
    want = ref.similarity_ref(z, g)
    np.testing.assert_allclose(got, want, rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 9), st.integers(1, 600))
def test_similarity_property(n, d):
    rng = np.random.default_rng(n * 1000 + d)
    z = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    got = ops.similarity_stats(z, g, chunk=128)
    want = ref.similarity_ref(z, g)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # norms are non-negative; Cauchy-Schwarz holds
    assert (np.asarray(got[:, 1]) >= 0).all()
    assert (got[:, 0] ** 2 <= got[:, 1] * got[:, 2] * (1 + 1e-4) + 1e-5).all()


# ----------------------------------------------------------------------
# masked aggregation (fused Step 5 / Eq. 6)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("n,d,chunk,dtype", [
    (1, 128, 128, jnp.float32),
    (5, 1000, 256, jnp.float32),      # pad path
    (8, 4096, 1024, jnp.bfloat16),
    (3, 70, 512, jnp.float32),        # d < chunk
    (23, 2048, 512, jnp.float32),     # paper-scale client count
])
def test_masked_agg_matches_oracle_sgd(n, d, chunk, dtype):
    """Kernel parity with the aggregators.oracle_sgd reference (the same
    masked mean DiverseFL applies to the surviving updates)."""
    from repro.core import aggregators as agg
    rng = np.random.default_rng(d + n)
    u = jnp.asarray(rng.normal(size=(n, d))).astype(dtype)
    mask = jnp.asarray(rng.integers(0, 2, size=n).astype(bool))
    got = ops.masked_aggregate(u, mask, chunk=chunk)
    want = agg.oracle_sgd(u.astype(jnp.float32), mask)
    np.testing.assert_allclose(got, want,
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-6)


def test_masked_agg_empty_mask_yields_zero():
    u = jnp.ones((4, 300))
    got = ops.masked_aggregate(u, jnp.zeros((4,), bool))
    np.testing.assert_allclose(got, np.zeros(300))


def test_diversefl_step45_fused_matches_reference():
    """The two-HBM-pass fused path (similarity kernel -> mask -> masked-agg
    kernel) must reproduce the unfused XLA Step 4+5 exactly."""
    from repro.core.diversefl import DiverseFLConfig, diversefl_mask
    rng = np.random.default_rng(0)
    n, d = 9, 700
    g = rng.normal(size=(n, d)).astype(np.float32)
    z = g.copy()
    z[2] = -z[2]              # sign flip -> fails C1
    z[5] = z[5] * 10.0        # huge scale -> fails C2
    z, g = jnp.asarray(z), jnp.asarray(g)
    cfg = DiverseFLConfig()
    delta, mask, (dot, zz, gg) = ops.diversefl_step45(z, g, cfg, chunk=256)
    s = ref.similarity_ref(z, g)
    want_mask = diversefl_mask(s[:, 0], s[:, 1], s[:, 2], cfg)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(want_mask))
    np.testing.assert_allclose(delta, ref.masked_agg_ref(z, want_mask),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jnp.stack([dot, zz, gg], -1), s, rtol=1e-5)


# ----------------------------------------------------------------------
# leaf forms: Step 4+5 on one stacked leaf viewed as (N, R, L)
# ----------------------------------------------------------------------

# R not a multiple of 8; L below 128 or not a multiple of it
LEAF_SHAPES = [(23, 40, 256), (23, 27, 64), (23, 4608 // 64, 512),
               (1, 16, 128), (5, 3, 10), (3, 20, 300)]
# the default blocks, and blocks small enough that row and column edge
# blocks are masked in-kernel
LEAF_BLOCKS = [None, 4096]


def _leaf_operands(shape, seed=0):
    rng = np.random.default_rng(seed + sum(shape))
    z = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    g = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    mask = jnp.asarray(rng.integers(0, 2, size=shape[0]).astype(bool))
    return z, g, mask


def _blocks(block_bytes):
    return {} if block_bytes is None else {"block_bytes": block_bytes}


@pytest.mark.parametrize("block_bytes", LEAF_BLOCKS)
@pytest.mark.parametrize("shape", LEAF_SHAPES)
def test_similarity_leaf_matches_row_kernel(shape, block_bytes):
    from repro.core.diversefl import similarity_stats_matrix
    from repro.kernels import similarity
    z, g, _ = _leaf_operands(shape)
    got = similarity.similarity_leaf_kernel(z, g, interpret=True,
                                            **_blocks(block_bytes))
    zf, gf = z.reshape(shape[0], -1), g.reshape(shape[0], -1)
    np.testing.assert_allclose(got, ops.similarity_stats(zf, gf),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        got, jnp.stack(similarity_stats_matrix(zf, gf), -1),
        rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("block_bytes", LEAF_BLOCKS)
@pytest.mark.parametrize("shape", LEAF_SHAPES)
def test_masked_agg_leaf_matches_row_kernel(shape, block_bytes):
    from repro.core.diversefl import masked_mean_flat
    from repro.kernels import masked_agg
    u, _, mask = _leaf_operands(shape)
    m = mask.astype(jnp.float32)
    got = masked_agg.masked_agg_leaf_kernel(
        u, m / jnp.maximum(m.sum(), 1.0), interpret=True,
        **_blocks(block_bytes))
    assert got.shape == shape[1:] and got.dtype == jnp.float32
    uf = u.reshape(shape[0], -1)
    np.testing.assert_allclose(got.reshape(-1), ops.masked_aggregate(uf, mask),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got.reshape(-1), masked_mean_flat(uf, mask),
                               rtol=1e-5, atol=1e-6)


# the (D/128, 128) view and the (1, D) row of fold_layout; blocks of 1,
# 3 and 40 clients (two client tiles, the second part full)
@pytest.mark.parametrize("block_bytes", LEAF_BLOCKS)
@pytest.mark.parametrize("c,d,dtype", [
    (1, 128 * 37, jnp.float32), (3, 128 * 37, jnp.float32),
    (40, 128 * 37, jnp.float32), (3, 128 * 37, jnp.bfloat16),
    (3, 1000, jnp.float32)])
def test_masked_agg_fold_matches_flat_fold(c, d, dtype, block_bytes):
    """The streaming fold into its own 2-D accumulator equals the fold
    into a flat (D,) one, bit for bit: each element is acc + w·u for a
    single client, and the same sum over each client tile otherwise."""
    from repro.kernels import masked_agg
    rng = np.random.default_rng(c + d)
    u = jnp.asarray(rng.normal(size=(c, d))).astype(dtype)
    w = jnp.asarray(rng.uniform(size=c).astype(np.float32))
    acc = jnp.asarray(rng.normal(size=d).astype(np.float32))
    want = masked_agg.masked_agg_update_kernel(u, w, acc, interpret=True)
    got = masked_agg.masked_agg_fold_kernel(
        u, w, acc.reshape(masked_agg.fold_layout(d)), interpret=True,
        **_blocks(block_bytes))
    assert got.shape == masked_agg.fold_layout(d)
    np.testing.assert_array_equal(np.asarray(got).reshape(-1),
                                  np.asarray(want))


def test_masked_agg_leaf_empty_mask_yields_zero():
    u = jnp.ones((4, 27, 64))
    got = ops.masked_aggregate_leaves({"w": u, "b": u[:, 0]},
                                      jnp.zeros((4,), bool))
    np.testing.assert_array_equal(np.asarray(got["w"]), np.zeros((27, 64)))
    np.testing.assert_array_equal(np.asarray(got["b"]), np.zeros(64))


def _tree(n, seed):
    """A model-like stack: conv, dense and narrow leaves, and leaves that
    are rows already (biases, a scalar)."""
    rng = np.random.default_rng(seed)
    shapes = {"c": (3, 3, 3, 16), "w": (40, 256), "h": (64, 10),
              "b": (256,), "s": ()}
    return {k: jnp.asarray(rng.normal(size=(n,) + s).astype(np.float32))
            for k, s in shapes.items()}


@pytest.mark.parametrize("n", [1, 9])
def test_diversefl_step45_leaves_matches_rows(n):
    from repro.core.aggregators import flatten_updates
    from repro.core.diversefl import DiverseFLConfig
    g = _tree(n, 0)
    z = jax.tree.map(lambda a: a + 0.3 * a[::-1], g)
    z["w"] = z["w"].at[0].multiply(-1.0)
    cfg = DiverseFLConfig()
    delta, mask, stats = ops.diversefl_step45_leaves(z, g, cfg)
    zf, unravel = flatten_updates(z)
    gf, _ = flatten_updates(g)
    want_delta, want_mask, want_stats = ops.diversefl_step45(zf, gf, cfg)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(want_mask))
    np.testing.assert_allclose(jnp.stack(stats, -1),
                               jnp.stack(want_stats, -1), rtol=1e-5,
                               atol=1e-4)
    for k, leaf in unravel(want_delta).items():
        assert delta[k].shape == leaf.shape, k
        np.testing.assert_allclose(delta[k], leaf, rtol=1e-5, atol=1e-6,
                                   err_msg=k)
    np.testing.assert_allclose(
        jnp.stack(ops.similarity_stats_leaves(z, g), -1),
        jnp.stack(stats, -1), rtol=1e-6)


# ----------------------------------------------------------------------
# robust aggregation
# ----------------------------------------------------------------------

@pytest.mark.parametrize("n,d,f", [(3, 256, 0), (9, 1000, 2), (23, 4096, 5),
                                   (8, 100, 3)])
def test_robust_agg_shapes(n, d, f):
    rng = np.random.default_rng(n + d)
    u = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    med, trim = ops.robust_aggregate(u, f=f, chunk=512)
    np.testing.assert_allclose(med, ref.median_ref(u), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(trim, ref.trimmed_ref(u, f), rtol=1e-5, atol=1e-6)


def test_robust_agg_tolerates_outliers():
    """Median ignores f huge rows (the Byzantine resilience property)."""
    rng = np.random.default_rng(0)
    u = rng.normal(size=(9, 300)).astype(np.float32)
    u[0] = 1e8
    u[5] = -1e8
    med, trim = ops.robust_aggregate(jnp.asarray(u), f=2)
    clean_med = np.median(u[[1, 2, 3, 4, 6, 7, 8]], axis=0)
    assert np.abs(np.asarray(med)).max() < 1e3
    assert np.abs(np.asarray(trim)).max() < 1e3


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

@pytest.mark.parametrize("B,H,K,S,dh,window,bq,bk", [
    (1, 2, 2, 128, 32, None, 64, 64),
    (2, 4, 2, 192, 64, None, 64, 64),      # GQA + pad (192 % 64 == 0)
    (1, 4, 1, 256, 64, None, 128, 128),    # MQA
    (2, 2, 2, 256, 32, 64, 64, 64),        # sliding window
    (1, 2, 2, 100, 32, 32, 32, 32),        # pad path with window
])
def test_flash_attention(B, H, K, S, dh, window, bq, bk):
    rng = np.random.default_rng(S)
    q = jnp.asarray(rng.normal(size=(B, H, S, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, K, S, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, K, S, dh)).astype(np.float32))
    got = ops.flash_attention_bhsd(q, k, v, window=window, bq=bq, bk=bk)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64))).astype(jnp.bfloat16)
    got = ops.flash_attention_bhsd(q, k, v, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@settings(max_examples=8, deadline=None)
@given(st.integers(33, 160), st.sampled_from([None, 16, 48]))
def test_flash_attention_property(S, window):
    rng = np.random.default_rng(S)
    q = jnp.asarray(rng.normal(size=(1, 2, S, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 1, S, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 1, S, 32)).astype(np.float32))
    got = ops.flash_attention_bhsd(q, k, v, window=window, bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5)


# ----------------------------------------------------------------------
# mamba scan
# ----------------------------------------------------------------------

@pytest.mark.parametrize("B,S,di,n,bs,bd", [
    (1, 64, 32, 8, 32, 32),
    (2, 256, 64, 16, 64, 32),
    (1, 128, 128, 4, 128, 128),
])
def test_mamba_scan(B, S, di, n, bs, bd):
    rng = np.random.default_rng(S + di)
    da = jnp.asarray(np.exp(-np.abs(rng.normal(size=(B, S, di, n)))).astype(np.float32))
    dbx = jnp.asarray(rng.normal(size=(B, S, di, n)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(B, S, n)).astype(np.float32))
    got = ops.mamba_scan_raw(da, dbx, c, bs=bs, bd=bd)
    want = ref.mamba_scan_ref(da, dbx, c)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_mamba_scan_state_carries_across_chunks():
    """A single impulse at t=0 must decay across chunk boundaries."""
    B, S, di, n = 1, 128, 8, 4
    da = jnp.full((B, S, di, n), 0.9, jnp.float32)
    dbx = jnp.zeros((B, S, di, n)).at[:, 0].set(1.0)
    c = jnp.ones((B, S, n), jnp.float32)
    y = ops.mamba_scan_raw(da, dbx, c, bs=32, bd=8)
    want = n * 0.9 ** np.arange(S)  # h decays geometrically, y = sum over n
    np.testing.assert_allclose(y[0, :, 0], want, rtol=1e-3)
