"""One-dispatch training benchmark — host syncs per run, measured.

The paper's enclave performs aggregation *and* evaluation inside the
TEE; the simulation's analogue is keeping a whole training run device-
resident.  This bench measures exactly that, for a 10-segment run at
N=1024 clients, ``client_chunk=64``:

* **host_eval** — the legacy per-segment loop: one scan dispatch per
  eval segment, then the jitted eval and a host sync of its metrics —
  10 syncs for 10 segments;
* **one_dispatch** — ``RoundEngine.run_training``: the outer scan runs
  every segment *and* its eval tail on device, and the host syncs once,
  at the end, when the metric buffer is fetched.

The sync count is **counted, not asserted from the code**: every
device→host materialization in the simulator flows through the single
``repro.fl.simulator.host_sync`` choke point, which this bench wraps
with a counter — and the timed runs execute under
``jax.transfer_guard_device_to_host("disallow_explicit")``, so on
backends where device memory is distinct from host memory (GPU/TPU) a
host read that bypasses the choke point raises instead of hiding.  (On
the CPU backend arrays are host-resident and the guard never fires —
there the counter *is* the measurement; the guard is kept so the same
bench is load-bearing on accelerators.)  A multi-segment one-dispatch
run exceeding one final sync fails the acceptance (CI
``dispatch-smoke``).

The donation section closes the ROADMAP "Donation on accelerator"
measurement gap: the training program is AOT-compiled with the carry
donation forced on and off (`FLConfig.donate` → ``RoundEngine``) and
the XLA ``memory_analysis`` working-set numbers of both variants are
recorded (on CPU, where XLA cannot donate, the delta documents itself
as zero — the bench records the backend).

  PYTHONPATH=src python -m benchmarks.dispatch_bench [--smoke]
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

N_CLIENTS = 1024
CHUNK = 64
SEGMENTS = 10
DIM, N_CLASSES, PER_CLIENT, M = 8, 4, 8, 1


def _build(eval_every: int, rounds: int, **cfg_kw):
    from repro.core.attacks import AttackConfig
    from repro.data import FederatedData, make_classification
    from repro.data.partition import partition_sorted_shards
    from repro.fl import FLConfig, Federation
    from repro.fl.small_models import softmax_regression

    x, y = make_classification(jax.random.PRNGKey(0),
                               N_CLIENTS * PER_CLIENT, N_CLASSES, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N_CLIENTS), N_CLASSES)
    tx, ty = make_classification(jax.random.PRNGKey(9), 64, N_CLASSES, DIM)
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    cfg = FLConfig(n_clients=N_CLIENTS, f=N_CLIENTS // 5,
                   aggregator="diversefl",
                   attack=AttackConfig(kind="backdoor", source_class=1,
                                       target_class=2),
                   batch_size=M, rounds=rounds, eval_every=eval_every,
                   l2=0.0, client_chunk=CHUNK, **cfg_kw)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    return model, fed, cfg


def _timed_run(model, fed, cfg, *, host_eval: bool, reps: int):
    """Best-of-reps seconds for one full training run, plus the host
    sync count of the *timed* run (warmup excluded), measured at the
    simulator's host_sync choke point under a d2h transfer guard."""
    import repro.fl.simulator as sim
    from repro.fl import RoundEngine
    from repro.optim import inv_sqrt_lr

    counter = {"n": 0}
    orig = sim.host_sync

    def counting(tree):
        counter["n"] += 1
        return orig(tree)

    sched = inv_sqrt_lr(0.05)
    engine = RoundEngine(model, fed, cfg)        # compiled once, timed reps
    best, syncs, hist = np.inf, None, None
    sim.host_sync = counting
    try:
        for rep in range(reps + 1):              # rep 0 = compile warmup
            counter["n"] = 0
            t0 = time.time()
            with jax.transfer_guard_device_to_host(
                    "allow" if rep == 0 else "disallow_explicit"):
                hist = sim.run_federated_training(
                    model, fed, cfg, sched, host_eval=host_eval,
                    engine=engine)
            dt = time.time() - t0
            if rep > 0:
                best, syncs = min(best, dt), counter["n"]
    finally:
        sim.host_sync = orig
    return best, syncs, hist


def _donation_section(eval_every: int, rounds: int):
    """AOT-compile the one-dispatch program with donation forced on/off
    and record the XLA memory_analysis working-set numbers of each."""
    from repro.fl import RoundEngine

    out = {"backend": jax.default_backend(),
           "donation_supported": jax.default_backend() != "cpu"}
    model, fed, cfg = _build(eval_every, rounds)   # one federation, two
    params = model.init(jax.random.PRNGKey(cfg.seed + 1))   # compiles
    for label, donate in (("donate_on", True), ("donate_off", False)):
        engine = RoundEngine(model, fed, cfg, donate=donate)
        lowered = engine.lower_training(
            params, jax.random.PRNGKey(0), jnp.zeros((rounds,), jnp.float32))
        stats = lowered.compile().memory_analysis()
        out[label] = {
            "temp_mb": round(stats.temp_size_in_bytes / 1e6, 2),
            "argument_mb": round(stats.argument_size_in_bytes / 1e6, 2),
            "output_mb": round(stats.output_size_in_bytes / 1e6, 2),
            "alias_mb": round(stats.alias_size_in_bytes / 1e6, 2),
        }
    on, off = out["donate_on"], out["donate_off"]
    out["working_set_delta_mb"] = round(
        (off["temp_mb"] + off["argument_mb"])
        - (on["temp_mb"] + on["argument_mb"] - on["alias_mb"]), 2)
    return out


def run(smoke: bool = False):
    from .common import emit, write_report
    eval_every = 1 if smoke else 5
    rounds = SEGMENTS * eval_every
    # the smoke runs are ~15 ms each, so the wall-clock ratio is noise-
    # sensitive (idle box: 1.5-2.3x; contended: as low as ~1.3x against
    # the 1.3x gate).  Best-of-6 gives each path several chances to hit
    # an undisturbed window — the robust gates are the sync counts and
    # the bitwise history check, the ratio gate guards against gross
    # regressions.
    reps = 6 if smoke else 3

    model, fed, cfg = _build(eval_every, rounds)
    t_host, syncs_host, h_host = _timed_run(model, fed, cfg,
                                            host_eval=True, reps=reps)
    t_one, syncs_one, h_one = _timed_run(model, fed, cfg,
                                         host_eval=False, reps=reps)
    rps_host, rps_one = rounds / t_host, rounds / t_one
    speedup = rps_one / rps_host
    history_keys = ("round", "acc", "main_acc", "backdoor_acc",
                    "mask_tpr", "mask_fpr")
    # same jitted metrics on both paths -> the histories must agree
    # bitwise; a drift here means the in-scan eval rotted
    bitwise = all(h_host[k] == h_one[k] for k in history_keys)

    # the flight-recorder gate (ISSUE 8): the per-round telemetry block
    # rides the existing metric buffer, so a telemetry-enabled run must
    # still reach the host in the same single sync — and must not
    # perturb a single history bit
    cfg_tel = dataclasses.replace(cfg, telemetry=True)
    _, syncs_tel, h_tel = _timed_run(model, fed, cfg_tel,
                                     host_eval=False, reps=1)
    tel_bitwise = all(h_tel[k] == h_one[k] for k in history_keys)

    emit(f"dispatch/host_eval_n{N_CLIENTS}", 1e6 / rps_host,
         f"{rps_host:.1f}rps|syncs={syncs_host}")
    emit(f"dispatch/one_dispatch_n{N_CLIENTS}", 1e6 / rps_one,
         f"{rps_one:.1f}rps|syncs={syncs_one}|speedup={speedup:.2f}x")
    emit(f"dispatch/telemetry_n{N_CLIENTS}", 0.0,
         f"syncs={syncs_tel}|bitwise={tel_bitwise}")

    donation = _donation_section(eval_every, rounds)
    acceptance = {
        "one_dispatch_single_sync": syncs_one == 1,
        "host_eval_syncs_per_segment": syncs_host == SEGMENTS,
        "in_scan_eval_matches_host_eval": bool(bitwise),
        "speedup_ge_1_3x": speedup >= 1.3,
        "telemetry_single_sync": syncs_tel == 1,
        "telemetry_bitwise_history": bool(tel_bitwise),
    }
    return write_report(
        "dispatch", smoke=smoke, acceptance=acceptance,
        n_clients=N_CLIENTS, client_chunk=CHUNK,
        segments=SEGMENTS, eval_every=eval_every, rounds=rounds,
        host_eval={"sec_per_run": round(t_host, 3),
                   "rounds_per_sec": round(rps_host, 1),
                   "host_syncs": syncs_host},
        one_dispatch={"sec_per_run": round(t_one, 3),
                      "rounds_per_sec": round(rps_one, 1),
                      "host_syncs": syncs_one},
        telemetry={"host_syncs": syncs_tel,
                   "history_bitwise": bool(tel_bitwise)},
        speedup=round(speedup, 2),
        donation=donation)


def main():
    from .common import smoke_main
    smoke_main(run)


if __name__ == "__main__":
    main()
