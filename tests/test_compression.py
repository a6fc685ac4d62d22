"""Compressed update streams (ISSUE 7): codec contract, error feedback,
the fused dequantize-and-fold kernel, and the end-to-end guarantees.

Pinned here, per DESIGN.md §10:

* codec roundtrip error bounds — f32 bitwise, bf16 half-ULP relative,
  int8 absmax_block/254 per block — and the measured wire sizes;
* ``dequant_fold_update`` (Pallas, interpret on CPU) bitwise against
  ``kernels/ref.dequant_fold_ref``, the one decode definition;
* error feedback: the residual is exactly the compression error, and
  the accumulated transmitted signal tracks the true signal with error
  bounded by one round's quantization error (EF-SGD's telescoping);
* ``compression="f32"`` training is bitwise-equal to the dense
  uncompressed fold at every (chunk, shards, pods) combination;
* lossy codecs: streaming == dense bitwise (same encoded bits folded
  either way), sweep == solo bitwise with a structural compression
  axis, and diversefl accuracy within a point of uncompressed on the
  paper-style N=256 grid;
* the launch-side knobs route through the same registry:
  ``resolve_update_dtype`` and the pinned XLA:CPU AllReducePromotion
  workaround (``update_psum_dtype``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attacks import AttackConfig
from repro.data import FederatedData, make_classification
from repro.data.partition import partition_sorted_shards
from repro.fl import (FLConfig, Federation, SweepSpec, run_federated_sweep,
                      run_federated_training, structural_key)
from repro.fl.compression import (QBLOCK, available_codecs,
                                  encode_with_feedback, get_codec,
                                  quantize_tree, wire_bytes)
from repro.fl.small_models import softmax_regression
from repro.kernels import ops
from repro.kernels.ref import dequant_fold_ref, dequant_int8_ref
from repro.launch.train import resolve_update_dtype, update_psum_dtype
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
    kw.setdefault("attack", AttackConfig(kind="sign_flip"))
    return FLConfig(**kw)


def _train(fed_data, cfg):
    model, data, tx, ty = fed_data
    fed = Federation.create(model, data, tx, ty, cfg, FED_KEY)
    return run_federated_training(model, fed, cfg, inv_sqrt_lr(0.05))


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


# ----------------------------------------------------------------------
# codec registry + roundtrip error bounds
# ----------------------------------------------------------------------

def test_registry_names_and_unknown():
    assert {"f32", "bf16", "int8"} <= set(available_codecs())
    with pytest.raises(ValueError, match="unknown compression codec"):
        get_codec("zstd")
    with pytest.raises(ValueError, match="f32"):
        get_codec("zstd")        # the error lists what IS available


def test_f32_roundtrip_bitwise():
    codec = get_codec("f32")
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(5, 37)).astype(np.float32))
    assert codec.lossless
    assert np.array_equal(np.asarray(codec.decode(codec.encode(x))),
                          np.asarray(x))


def test_bf16_half_ulp_bound():
    codec = get_codec("bf16")
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(4, 301)).astype(np.float32))
    err = np.abs(np.asarray(codec.decode(codec.encode(x)) - x))
    # round-to-nearest-even bf16: relative error <= 2^-8 (half ULP)
    assert np.all(err <= 2.0 ** -8 * np.abs(np.asarray(x)) + 1e-30)


def test_int8_per_block_bound_and_shapes():
    codec = get_codec("int8")
    d = 2 * QBLOCK + 10                      # exercises the padded tail
    x = np.random.default_rng(2).normal(size=(3, d)).astype(np.float32)
    x[1, :QBLOCK] = 0.0                      # an all-zero block
    enc = codec.encode(jnp.asarray(x))
    assert enc["q"].dtype == jnp.int8 and enc["q"].shape == x.shape
    assert enc["scale"].shape == (3, -(-d // QBLOCK))
    dec = np.asarray(codec.decode(enc))
    assert np.array_equal(dec[1, :QBLOCK], np.zeros(QBLOCK))
    err = np.abs(dec - x)
    for b in range(-(-d // QBLOCK)):
        blk = slice(b * QBLOCK, min((b + 1) * QBLOCK, d))
        bound = np.abs(x[:, blk]).max(axis=1) / 254.0
        assert np.all(err[:, blk] <= bound[:, None] * (1 + 1e-6) + 1e-12)


def test_int8_decode_is_the_shared_ref():
    codec = get_codec("int8")
    x = jnp.asarray(np.random.default_rng(3).normal(
        size=(2, 70)).astype(np.float32))
    enc = codec.encode(x)
    assert np.array_equal(
        np.asarray(codec.decode(enc)),
        np.asarray(dequant_int8_ref(enc["q"], enc["scale"], QBLOCK)))


def test_wire_bytes_measured():
    d = 333
    assert wire_bytes(get_codec("f32"), d) == 4 * d
    assert wire_bytes(get_codec("bf16"), d) == 2 * d
    assert wire_bytes(get_codec("int8"), d) == d + 4 * (-(-d // QBLOCK))
    # the headline number: int8 at mlp scale is >= 3.5x under dense f32
    assert 4 * 50698 / wire_bytes(get_codec("int8"), 50698) > 3.5


# ----------------------------------------------------------------------
# error feedback
# ----------------------------------------------------------------------

@pytest.mark.parametrize("name", ["bf16", "int8"])
def test_encode_with_feedback_residual_is_the_error(name):
    codec = get_codec(name)
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(size=(6, 2 * QBLOCK)).astype(np.float32))
    r = jnp.asarray(rng.normal(size=(6, 2 * QBLOCK)).astype(np.float32))
    enc, dec, new_r = encode_with_feedback(codec, u, r)
    v = np.asarray(u) + np.asarray(r)
    assert np.array_equal(np.asarray(dec), np.asarray(codec.decode(enc)))
    assert np.allclose(np.asarray(dec) + np.asarray(new_r), v,
                       rtol=0, atol=1e-6)


def test_f32_feedback_is_identity():
    codec = get_codec("f32")
    u = jnp.asarray(np.random.default_rng(5).normal(
        size=(3, 50)).astype(np.float32))
    enc, dec, new_r = encode_with_feedback(codec, u, jnp.zeros_like(u))
    assert np.array_equal(np.asarray(dec), np.asarray(u))
    assert not np.asarray(new_r).any()


def test_error_feedback_telescopes():
    """EF-SGD's point: sum_t dec_t = sum_t u_t − resid_T, so the
    accumulated transmitted signal is off by ONE round's compression
    error, not T of them.  Without feedback the bias grows with T."""
    codec = get_codec("int8")
    rng = np.random.default_rng(6)
    u = jnp.asarray(rng.normal(size=(QBLOCK,)).astype(np.float32))
    T = 20
    resid = jnp.zeros_like(u)
    acc_ef = np.zeros(u.shape, np.float64)
    acc_no = np.zeros(u.shape, np.float64)
    for _ in range(T):
        _, dec, resid = encode_with_feedback(codec, u, resid)
        acc_ef += np.asarray(dec)
        acc_no += np.asarray(codec.decode(codec.encode(u)))
    true = T * np.asarray(u, np.float64)
    one_round = np.abs(np.asarray(u)).max() / 254.0
    assert np.abs(acc_ef - true).max() <= one_round * (1 + 1e-4) + 1e-6
    # the no-feedback bias is the same deterministic error T times over
    assert np.abs(acc_no - true).max() >= np.abs(acc_ef - true).max()


def test_quantize_tree_lossless_is_identity_lossy_rounds():
    tree = {"w": jnp.asarray(np.random.default_rng(7).normal(
        size=(4, 3, 5)).astype(np.float32))}
    assert quantize_tree(get_codec("f32"), tree) is tree
    out = quantize_tree(get_codec("int8"), tree)
    assert out["w"].shape == tree["w"].shape
    assert not np.array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    ref = get_codec("int8")
    flat = tree["w"].reshape((4, -1))
    assert np.array_equal(
        np.asarray(out["w"]),
        np.asarray(ref.decode(ref.encode(flat)).reshape(tree["w"].shape)))


# ----------------------------------------------------------------------
# the fused dequantize-and-fold kernel vs the reference decoder
# ----------------------------------------------------------------------

# compiled like the kernel: XLA:CPU may reduce an op-by-op (eager) sum in
# another order than the same sum inside a compiled program
_dequant_fold_ref_jit = jax.jit(dequant_fold_ref, static_argnames="qblock")


@pytest.mark.parametrize("n,d,qblock", [(5, 40, 16), (7, 300, 128),
                                        (16, 2 * QBLOCK, QBLOCK)])
def test_dequant_fold_kernel_matches_ref_bitwise(n, d, qblock):
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.integers(-127, 128, size=(n, d)).astype(np.int8))
    scale = jnp.asarray(
        rng.uniform(0, 0.1, size=(n, -(-d // qblock))).astype(np.float32))
    w = jnp.asarray((rng.random(n) < 0.7).astype(np.float32)
                    * rng.uniform(0, 2, n).astype(np.float32))
    acc = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    got = ops.dequant_fold_update(q, scale, w, acc, qblock=qblock)
    want = _dequant_fold_ref_jit(q, scale, w, acc, qblock=qblock)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_dequant_fold_kernel_chunked_matches_ref():
    n, d, qblock = 4, 5 * 64, 64
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.integers(-127, 128, size=(n, d)).astype(np.int8))
    scale = jnp.asarray(
        rng.uniform(0, 0.1, size=(n, d // qblock)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0, 1, n).astype(np.float32))
    acc = jnp.zeros((d,), jnp.float32)
    got = ops.dequant_fold_update(q, scale, w, acc, qblock=qblock,
                                  chunk=2 * qblock)       # multi-tile grid
    want = _dequant_fold_ref_jit(q, scale, w, acc, qblock=qblock)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------------------
# FLConfig validation + launch-side dtype routing
# ----------------------------------------------------------------------

def test_config_unknown_codec_raises():
    with pytest.raises(ValueError, match="not a registered codec"):
        _cfg(compression="gzip")


def test_config_lossy_kernel_agg_requires_streaming():
    with pytest.raises(ValueError, match="requires streaming=True"):
        _cfg(compression="int8", use_kernel_agg=True, streaming=False)
    _cfg(compression="int8", use_kernel_agg=True, streaming=True)
    _cfg(compression="f32", use_kernel_agg=True, streaming=False)


def test_update_psum_dtype_cpu_promotion_pin():
    """XLA:CPU AllReducePromotion CHECK-fails on a bf16 all-reduce; the
    workaround (psum in f32 on the cpu backend) must stay until the
    backend fixes it.  If this test fails because jax started accepting
    bf16 psums on CPU, the gate in launch/train.py can go."""
    assert jax.default_backend() == "cpu"
    assert update_psum_dtype(jnp.bfloat16) == jnp.float32
    assert update_psum_dtype(jnp.float32) == jnp.float32


def test_resolve_update_dtype_routes_through_registry():
    assert resolve_update_dtype("f32") == jnp.float32
    assert resolve_update_dtype("bf16") == jnp.bfloat16
    # legacy knob still honored when compression is defaulted
    assert resolve_update_dtype("f32", jnp.bfloat16) == jnp.bfloat16
    with pytest.raises(ValueError, match="no dense wire dtype"):
        resolve_update_dtype("int8")
    with pytest.raises(ValueError, match="conflicts"):
        resolve_update_dtype("bf16", jnp.float32)


# ----------------------------------------------------------------------
# end-to-end: f32 bitwise at (chunk, shards, pods); lossy contracts
# ----------------------------------------------------------------------

@pytest.mark.parametrize("chunk,shards,pods", [
    (8, None, None), (4, None, None), (8, 1, 1)])
def test_f32_streaming_bitwise_vs_dense_grid(fed_data, chunk, shards, pods):
    """The lossless passthrough must reproduce the pre-compression fold
    bit for bit at every sequential fold partition — compression="f32"
    skips the error-feedback carry structurally, so the jaxpr is the
    PR-6 one (chunking and S=1/P=1 never reassociate)."""
    dense = _train(fed_data, _cfg(streaming=False))
    strm = _train(fed_data, _cfg(streaming=True, compression="f32",
                                 client_chunk=chunk, stream_shards=shards,
                                 pods=pods))
    _assert_hist_bitwise(strm, dense, f"chunk={chunk},shards={shards},"
                                      f"pods={pods}")


@pytest.mark.parametrize("chunk,shards,pods", [(8, 3, None), (4, 2, 2)])
def test_f32_streaming_sharded_grid_close(fed_data, chunk, shards, pods):
    """Sharded/two-tier partitions reassociate the merge (the PR-6
    contract: per-client criterion stats bitwise, delta to tight fp
    tolerance) — the f32 codec must inherit exactly that, no worse."""
    dense = _train(fed_data, _cfg(streaming=False))
    strm = _train(fed_data, _cfg(streaming=True, compression="f32",
                                 client_chunk=chunk, stream_shards=shards,
                                 pods=pods))
    assert np.array_equal(np.asarray(strm["mask_tpr"]),
                          np.asarray(dense["mask_tpr"]))
    assert np.array_equal(np.asarray(strm["mask_fpr"]),
                          np.asarray(dense["mask_fpr"]))
    np.testing.assert_allclose(_flat(strm["params"]),
                               _flat(dense["params"]),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("name", ["bf16", "int8"])
def test_lossy_streaming_matches_dense_bitwise(fed_data, name):
    """Same encoded bits folded streaming or dense must agree exactly:
    both sides decode through the one reference decoder."""
    dense = _train(fed_data, _cfg(compression=name, streaming=False))
    strm = _train(fed_data, _cfg(compression=name, streaming=True,
                                 client_chunk=8))
    _assert_hist_bitwise(strm, dense, f"{name} streaming-vs-dense")


def test_lossy_kernel_agg_matches_jnp_fold(fed_data):
    """use_kernel_agg routes int8 blocks through the Pallas
    dequantize-and-fold kernel; the fold must agree with the jnp path
    to fp tolerance (the kernel reassociates the row sum)."""
    plain = _train(fed_data, _cfg(compression="int8", streaming=True,
                                  client_chunk=8))
    kern = _train(fed_data, _cfg(compression="int8", streaming=True,
                                 client_chunk=8, use_kernel_agg=True))
    assert np.allclose(_flat(kern["params"]), _flat(plain["params"]),
                       rtol=1e-5, atol=1e-6)


def test_comm_stats_in_history(fed_data):
    hist = _train(fed_data, _cfg(compression="int8"))
    d = _flat(hist["params"]).size
    assert hist["uplink_bytes_per_client"] == \
        d + 4 * (-(-d // QBLOCK))
    assert hist["dense_uplink_bytes_per_round"] == \
        hist["downlink_bytes_per_round"] == 4 * d * N
    assert hist["uplink_reduction"] > 3.5
    f32 = _train(fed_data, _cfg())
    assert f32["uplink_reduction"] == 1.0
    assert f32["uplink_bytes_per_round"] == 4 * d * N


# ----------------------------------------------------------------------
# sweep: structural compression axis, sweep == solo, accuracy grid
# ----------------------------------------------------------------------

def test_compression_axis_is_structural():
    a = _cfg(compression="f32")
    b = _cfg(compression="int8")
    assert structural_key(a) != structural_key(b)


def test_sweep_compressions_axis_bitwise_vs_solo(fed_data):
    model, data, tx, ty = fed_data
    base = _cfg(rounds=2, eval_every=2)
    spec = SweepSpec(base=base, seeds=(0, 1),
                     compressions=("f32", "int8"))
    cells = spec.cells()
    assert sorted({c.cfg.compression for c in cells}) == ["f32", "int8"]
    fed = Federation.create(model, data, tx, ty, base, FED_KEY)
    hists = run_federated_sweep(model, fed, spec, inv_sqrt_lr(0.05))
    for cell, hist in zip(cells, hists):
        solo = _train(fed_data, cell.cfg)
        _assert_hist_bitwise(hist, solo,
                             f"compression={cell.cfg.compression},"
                             f"seed={cell.cfg.seed}")


def test_accuracy_within_a_point_n256():
    """The paper-style N=256 diversefl grid under sign_flip: bf16 and
    int8 with error feedback must land within one accuracy point of
    the uncompressed run (the EF convergence guarantee, measured)."""
    n, per_client = 256, 8
    x, y = make_classification(jax.random.PRNGKey(0), n * per_client,
                               NC, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, n), NC)
    tx, ty = make_classification(jax.random.PRNGKey(9), 256, NC, DIM)
    model = softmax_regression(input_dim=DIM, n_classes=NC)
    base = FLConfig(n_clients=n, f=n // 5, rounds=16, eval_every=16,
                    batch_size=2, l2=0.0, aggregator="diversefl",
                    attack=AttackConfig(kind="sign_flip"))
    fed = Federation.create(model, data, tx, ty, base, FED_KEY)
    spec = SweepSpec(base=base, compressions=("f32", "bf16", "int8"))
    hists = run_federated_sweep(model, fed, spec, inv_sqrt_lr(0.05))
    acc = {cell.cfg.compression: float(np.asarray(h["acc"])[-1])
           for cell, h in zip(spec.cells(), hists)}
    assert acc["f32"] > 0.5, f"uncompressed baseline failed: {acc}"
    for name in ("bf16", "int8"):
        assert abs(acc[name] - acc["f32"]) <= 0.01 + 1e-9, \
            f"{name} accuracy {acc[name]:.4f} vs f32 {acc['f32']:.4f}"
