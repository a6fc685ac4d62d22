"""Run DiverseFL training on a TPU chip, end to end, and check the result.

The quickest proof that the system still starts on the chip.  One
process, seeded synthetic data, nothing downloaded.  It drives Algorithm
1 (sealed-sample ingestion, client local SGD, enclave guiding updates,
the Eq. 6 per-client filter and the masked fold) through the library's
own entry points, at the full width of models the repo defines:

* paper phase — VGG-11 (paper Table I, 28.1M params) on 32x32x3 inputs,
  N=23 clients of which f=5 sign-flip their updates, 3 DiverseFL rounds
  through ``run_federated_training``: once with the Pallas similarity
  and masked-mean kernels, once as plain XLA (the reference);
* decoder phase — the 100,369,280-param ``fl-llm-100m`` decoder, N=4
  clients with f=1 sign-flipper, streaming fold with client_chunk=1, 3
  rounds through ``RoundEngine.run_training``: with the Pallas fold
  kernel, and as plain XLA.

Each kernel run must carry ``tpu_custom_call`` in its compiled program
(the kernels really compiled: interpret mode would leave none), and must
agree with its XLA reference: the same keep-masks every round and
params within the tolerances below.  In the paper phase every
sign-flipper must be tagged in every round (TPR 1.0).  Times
printed are smoke timings of one run, not benchmark numbers.

``--four-chips`` runs only the decoder phase on a (data=2, model=2) mesh
of four chips, compared with the same training on one chip.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --four-chips  # a host with four

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU the script exits 1 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ROUNDS = 3
PAPER_N, PAPER_F = 23, 5
PAPER_LR = 0.05
PER_CLIENT, N_TEST = 64, 256     # synthetic CIFAR-like images
DECODER_N, DECODER_F = 4, 1
DECODER_LR = 3e-2
# Kernel vs XLA Step 4+5 on one identical (23, 28.1M) input pair, as
# |ddelta| / |delta|.  On a v5e the kernel reads 1.05e-7, a reversed
# XLA fold 1.16e-7, and a kernel that drops one kept client 0.236: the
# limit is ten times an f32 reordering.
FOLD_REL_TOL = 1e-6
# Kernel training run vs XLA training run, as |dparams| / |trained
# displacement|.  Rounds 2 and 3 feed round 1's f32 rounding
# differences through convolutions whose inputs the MXU rounds to bf16,
# which amplifies them.  Readings on a v5e at two seeds: a reversed XLA
# fold 1.7e-3-3.2e-3; the kernels 1.6e-2-3.4e-2 (2.7e-5-1.0e-4 at
# precision=highest, where no bf16 rounding amplifies); one kept
# client's weight dropped 0.54-0.99.  The limit sits between the
# kernels' highest and the fault's lowest; the keep masks compare
# exactly.
PAPER_REL_TOL = 0.13
# bf16 params (decoder): an update below half a bf16 ulp of its weight
# leaves the weight as it was, so the run must move this share of them
# for the comparison below to see the fold at all
DECODER_MIN_MOVED = 0.01
# one-chip vs four-chip decoder accuracy: tests/test_model_fl.py's
# client x model mesh tolerance
MESH_ACC_ATOL = 0.08


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def kept_masks(c1c2, dfl):
    """Per-round keep masks read back from the C1·C2 criterion logs:
    C1·C2 is C2 when C1 = +1 and <= 0 otherwise, so with eps1 = 0 a
    client is kept iff eps2 < C1·C2 < eps3 (core/diversefl.py)."""
    check(dfl.eps1 == 0.0, "keep masks are read back for eps1 = 0 only")
    c = np.asarray(c1c2)
    return (c > dfl.eps2) & (c < dfl.eps3)


def flat(params):
    import jax
    return np.concatenate([np.asarray(p, np.float32).ravel()
                           for p in jax.tree.leaves(params)])


def peak_bytes(device) -> int:
    """The device's peak: live buffers, or the memory the runtime reserved
    for them and for the programs' temporaries, whichever is larger."""
    stats = device.memory_stats() or {}
    return max(int(stats.get("peak_bytes_in_use", -1)),
               int(stats.get("peak_bytes_reserved", -1)))


def memory_line(device) -> str:
    stats = device.memory_stats() or {}
    return ", ".join(f"{k} {v}" for k, v in sorted(stats.items())
                     if "peak" in k or k in ("bytes_in_use", "bytes_limit"))


def compile_training(engine, params, lrs):
    """AOT-compile the engine's one-dispatch training program as
    ``run_training`` calls it; returns (seconds, optimized HLO text)."""
    import jax
    t0 = time.perf_counter()
    compiled = engine.lower_training(
        params, jax.random.PRNGKey(engine.cfg.seed), lrs).compile()
    return time.perf_counter() - t0, compiled.as_text()


def check_kernels(text: str, kernels: bool, label: str) -> None:
    has = "tpu_custom_call" in text
    check(has == kernels,
          f"{label}: tpu_custom_call {'missing from' if kernels else 'in'} "
          f"the compiled training program")


def report(label, res, device) -> None:
    log(f"[{label}] smoke timings: compile {res['compile_s']:.2f} s, first "
        f"call {res['first_s']:.2f} s, warm call {res['warm_s']:.3f} s "
        f"({res['warm_s'] / ROUNDS:.3f} s/round)")
    log(f"[{label}] acc per round {np.round(res['acc'], 4).tolist()}, "
        f"final test loss {res['loss']:.6f}")
    log(f"[{label}] TPR {res['tpr'].tolist()}, FPR {res['fpr'].tolist()}")
    log(f"[{label}] memory so far: {memory_line(device)}")


# ----------------------------------------------------------------------
# paper phase: VGG-11, N=23, f=5 sign-flip, run_federated_training
# ----------------------------------------------------------------------

def paper_run(model, data, tx, ty, kernels: bool, label: str):
    import jax
    from repro.core.attacks import AttackConfig
    from repro.fl import (FLConfig, Federation, RoundEngine,
                          run_federated_training)
    cfg = FLConfig(n_clients=PAPER_N, f=PAPER_F, rounds=ROUNDS,
                   batch_size=30, sample_frac=0.05, aggregator="diversefl",
                   eval_every=1,
                   attack=AttackConfig(kind="sign_flip"),
                   use_kernel_stats=kernels, use_kernel_agg=kernels)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    engine = RoundEngine(model, fed, cfg)
    params0 = model.init(jax.random.PRNGKey(cfg.seed + 1))
    compile_s, text = compile_training(
        engine, params0, np.full(ROUNDS, PAPER_LR, np.float32))
    check_kernels(text, kernels, label)

    def lr(r):
        return PAPER_LR

    t0 = time.perf_counter()
    hist = run_federated_training(model, fed, cfg, lr, engine=engine)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_federated_training(model, fed, cfg, lr, engine=engine)
    warm_s = time.perf_counter() - t0
    loss = float(jax.jit(model.loss)(hist["params"], tx, ty))
    res = {"compile_s": compile_s, "first_s": first_s, "warm_s": warm_s,
           "acc": np.asarray(hist["acc"]), "loss": loss,
           "tpr": np.asarray(hist["mask_tpr"]),
           "fpr": np.asarray(hist["mask_fpr"]),
           "kept": kept_masks(hist["c1c2"], cfg.dfl),
           "params": flat(hist["params"]), "params0": flat(params0)}
    del fed, engine, hist, params0
    gc.collect()
    return res


def fold_check(d: int) -> None:
    """Step 4+5 once on one random (N, d) update/guide pair, Pallas
    (``ops.diversefl_step45``) against XLA: training amplifies any
    difference, a single step does not, so this limit is tight.  The
    first f clients point against their guides and must be dropped."""
    import jax
    import jax.numpy as jnp
    from repro.core.diversefl import (DiverseFLConfig, diversefl_mask,
                                      masked_mean_flat,
                                      similarity_stats_matrix)
    from repro.kernels import ops
    cfg = DiverseFLConfig()
    ku, kg = jax.random.split(jax.random.PRNGKey(5))
    sign = jnp.where(jnp.arange(PAPER_N) < PAPER_F, -1.0, 1.0)[:, None]
    G = jax.random.normal(kg, (PAPER_N, d), jnp.float32)
    U = sign * G + jax.random.normal(ku, (PAPER_N, d), jnp.float32)

    @jax.jit
    def xla_step45(u, g):
        dot, zz, gg = similarity_stats_matrix(u, g)
        mask = diversefl_mask(dot, zz, gg, cfg)
        return masked_mean_flat(u, mask), mask

    delta_k, mask_k, _ = ops.diversefl_step45(U, G, cfg)
    delta_r, mask_r = xla_step45(U, G)
    mask_k, mask_r = np.asarray(mask_k), np.asarray(mask_r)
    check(np.array_equal(mask_k, mask_r)
          and (mask_r == (np.arange(PAPER_N) >= PAPER_F)).all(),
          f"fold check: masks {mask_k} vs {mask_r}")
    rel = float(jnp.linalg.norm(delta_k - delta_r) / jnp.linalg.norm(delta_r))
    log(f"[paper] step 4+5 on one (N, D) pair, kernels vs xla: "
        f"|ddelta| / |delta| = {rel:.3e} (limit {FOLD_REL_TOL:g})")
    check(rel <= FOLD_REL_TOL, f"step 4+5 differs: {rel:.3e}")


def paper_phase(device):
    """VGG-11 on 64 synthetic CIFAR-like images per client (5% of them,
    3 images, sealed in the enclave).  The engine captures the
    federation's arrays into the compiled program as constants, so data
    adds its bytes to every executable and to the compile cache."""
    import jax
    from repro.data import (FederatedData, make_cifar_like,
                            partition_sorted_shards)
    from repro.fl.small_models import vgg11
    model = vgg11()
    x, y = make_cifar_like(jax.random.PRNGKey(0), PAPER_N * PER_CLIENT)
    tx, ty = make_cifar_like(jax.random.PRNGKey(9), N_TEST)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, PAPER_N), 10)
    n_params = sum(p.size for p in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    log(f"[paper] {model.name}: {n_params:,} params, N={PAPER_N}, "
        f"f={PAPER_F} sign_flip, {ROUNDS} rounds")
    fold_check(n_params)
    out = {}
    for kernels, label in ((True, "paper/kernels"), (False, "paper/xla")):
        out[kernels] = paper_run(model, data, tx, ty, kernels, label)
        report(label, out[kernels], device)
    k, r = out[True], out[False]
    compare(k, r, "paper", all_caught=True)
    rel = (np.linalg.norm(k["params"] - r["params"])
           / np.linalg.norm(r["params"] - r["params0"]))
    log(f"[paper] kernels vs xla: |dparams| / |trained displacement| = "
        f"{rel:.3e} (limit {PAPER_REL_TOL:g})")
    check(rel <= PAPER_REL_TOL, f"paper params differ: {rel:.3e}")


def compare(k, r, phase: str, all_caught: bool) -> None:
    """Kernel run vs XLA reference: finite results and identical filter
    decisions; with ``all_caught`` every sign-flipper is tagged in every
    round (TPR 1.0)."""
    for res in (k, r):
        check(np.isfinite(res["loss"]), f"{phase}: loss not finite")
        check(np.isfinite(res["params"]).all(), f"{phase}: params not finite")
        if all_caught:
            check((res["tpr"] == 1.0).all(), f"{phase}: TPR {res['tpr']}")
    check(np.array_equal(k["kept"], r["kept"]),
          f"{phase}: keep masks differ: {k['kept']} vs {r['kept']}")
    log(f"[{phase}] keep masks equal in all {ROUNDS} rounds"
        + (", TPR 1.0" if all_caught else ""))


# ----------------------------------------------------------------------
# decoder phase: fl-llm-100m, N=4, f=1 sign-flip, RoundEngine.run_training
# ----------------------------------------------------------------------

def decoder_cfg(kernels: bool):
    from repro.core.attacks import AttackConfig
    from repro.fl import FLConfig
    return FLConfig(n_clients=DECODER_N, f=DECODER_F, rounds=ROUNDS,
                    batch_size=2, l2=0.0, aggregator="diversefl",
                    streaming=True, client_chunk=1, eval_every=1,
                    compression="f32", use_kernel_agg=kernels,
                    attack=AttackConfig(kind="sign_flip"))


def decoder_run(model, cfg, label: str, mesh=None):
    import jax
    import jax.numpy as jnp
    from repro.fl import RoundEngine, make_zoo_federation
    fed = make_zoo_federation(model, cfg, per_client=4, n_test=16)
    engine = RoundEngine(model, fed, cfg, mesh=mesh)
    check(engine.donate, f"{label}: buffer donation is off on the chip")
    lrs = jnp.full((ROUNDS,), DECODER_LR, jnp.float32)

    def params():          # donation consumes each run's params
        return model.init(jax.random.PRNGKey(1))

    compile_s, text = compile_training(engine, params(), lrs)
    check_kernels(text, cfg.use_kernel_agg, label)
    p0 = jax.block_until_ready(params())
    params0 = flat(p0)
    t0 = time.perf_counter()
    p, _, metrics, _ = engine.run_training(p0, jax.random.PRNGKey(0), lrs)
    metrics = jax.device_get(metrics)
    first_s = time.perf_counter() - t0
    p0 = jax.block_until_ready(params())
    t0 = time.perf_counter()
    jax.device_get(engine.run_training(p0, jax.random.PRNGKey(0), lrs)[2])
    warm_s = time.perf_counter() - t0
    loss = float(jax.jit(model.loss)(p, fed.test_x, fed.test_y))
    res = {"compile_s": compile_s, "first_s": first_s, "warm_s": warm_s,
           "acc": np.asarray(metrics["acc"]), "loss": loss,
           "tpr": np.asarray(metrics["mask_tpr"]),
           "fpr": np.asarray(metrics["mask_fpr"]),
           "kept": kept_masks(metrics["c1c2"], cfg.dfl), "params": flat(p),
           "params0": params0}
    del fed, engine, p, p0
    gc.collect()
    return res


def decoder_model():
    from benchmarks.model_fl_bench import FULL_MODEL, SEQ
    from repro.fl import zoo_model
    model = zoo_model(FULL_MODEL, seq_len=SEQ)
    log(f"[decoder] {model.name}: {model.param_count():,} params, "
        f"N={DECODER_N}, f={DECODER_F} sign_flip, streaming, "
        f"client_chunk=1, {ROUNDS} rounds")
    return model


def decoder_phase(device):
    model = decoder_model()
    out = {}
    for kernels, label in ((True, "decoder/kernels"), (False, "decoder/xla")):
        out[kernels] = decoder_run(model, decoder_cfg(kernels), label)
        report(label, out[kernels], device)
    k, r = out[True], out[False]
    compare(k, r, "decoder", all_caught=False)
    moved = r["params"] != r["params0"]
    disp = np.linalg.norm(r["params"] - r["params0"])
    log(f"[decoder] xla run moved {moved.mean():.4%} of the params, "
        f"|trained displacement| / |params0| = "
        f"{disp / np.linalg.norm(r['params0']):.3e} "
        f"(at least {DECODER_MIN_MOVED:.0%} must move)")
    check(moved.mean() >= DECODER_MIN_MOVED,
          f"decoder training moved {moved.mean():.4%} of the params")
    rel = np.linalg.norm(k["params"] - r["params"]) / disp
    log(f"[decoder] kernels vs xla: |dparams| / |trained displacement| = "
        f"{rel:.3e} (must be bitwise equal)")
    check(np.array_equal(k["params"], r["params"]),
          f"decoder params differ: {rel:.3e} of the displacement")


def four_chip_phase(devices):
    """The decoder on a (data=2, model=2) mesh of four chips vs one chip.
    The Pallas kernels are single-device programs (FLConfig refuses them
    on a model-sharded mesh), so both runs use the XLA fold."""
    from repro.launch.mesh import make_host_mesh
    model = decoder_model()
    cfg = decoder_cfg(False)
    mesh = make_host_mesh(data=2, model=2)
    check(dict(mesh.shape) == {"data": 2, "model": 2},
          f"mesh is {dict(mesh.shape)}")
    m = decoder_run(model, cfg, "decoder/mesh2x2", mesh=mesh)
    report("decoder/mesh2x2", m, devices[0])
    peaks = [peak_bytes(d) for d in devices[:4]]
    for d in devices[:4]:
        log(f"[decoder/mesh2x2] device {d.id}: {memory_line(d)}")
    check(min(peaks) > 0 and min(peaks) >= 0.5 * max(peaks),
          f"mesh run did not spread over four devices: {peaks}")
    one = decoder_run(model, cfg, "decoder/one-chip")
    report("decoder/one-chip", one, devices[0])
    compare(m, one, "decoder/mesh2x2 vs one-chip", all_caught=False)
    diff = float(np.max(np.abs(m["acc"] - one["acc"])))
    log(f"[decoder] mesh vs one chip: max |dacc| {diff:.4f} (limit "
        f"{MESH_ACC_ATOL}), loss {m['loss']:.6f} vs {one['loss']:.6f}")
    check(diff <= MESH_ACC_ATOL, f"mesh accuracy differs by {diff}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the decoder phase, on a (data=2, model=2) "
                         "mesh of four chips, against one chip")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's devices are "
              f"{devices[0].platform}); this check runs on the chip only",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: --four-chips needs 4 TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.kernels import ops
    cache = enable_compile_cache()
    log(f"device_kind {devices[0].device_kind!r}, {len(devices)} device(s), "
        f"jax {jax.__version__}, compile cache {cache}")
    check(not ops._interpret(), "Pallas would run in interpret mode")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(devices)
    else:
        paper_phase(devices[0])
        decoder_phase(devices[0])
    # launch/dryrun.py rewrites XLA_FLAGS when imported
    check("repro.launch.dryrun" not in sys.modules,
          "the chip path imported repro.launch.dryrun")
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
