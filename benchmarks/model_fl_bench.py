"""Tensor-sharded federated rounds — the model zoo on the compiled engine.

What this bench measures: a federated round over a *real* transformer
(the zoo adapter, fl/zoo.py) with the flat model dim D sharded over the
mesh's ``model`` axis (DESIGN.md §12) — the configuration that decides
whether 100M+-parameter federations fit the paper's 512 MB enclave
envelope at all.

Sections:

* **envelope** — AOT-compile the engine's multi-round segment for a
  ≥100M-param LM (full mode; a zoo smoke config under ``--smoke``) on
  client x model host meshes and record
  ``memory_analysis().temp_size_in_bytes`` against the 512 MB envelope.
  The blocked (ms, L) update layout (sharding.flatten_updates_sharded)
  keeps per-shard temps at O(D/ms): the measured matrix shows temps
  scaling *down* with the model axis — the unsharded build pins ~5 full
  D-sized f32 temps regardless of mesh.
* **throughput** — run the compiled segment (not just compile it) on
  the sharded mesh and unsharded, and record rounds/sec both ways.  On
  a single host the 8 forced devices share cores, so the ratio is a
  plumbing check, not a speedup claim — the acceptance is that the
  sharded program *completes* with finite metrics.
* **model-axis=1 gate** — the same training run on a ``model=1`` mesh
  must reproduce the meshless engine history **bitwise** (every eval
  metric): the degrade-gracefully contract that keeps every pre-zoo
  config byte-identical.

Acceptance (smoke-gated in CI):

* sharded segment compiles AND runs with temps <= the envelope;
* model-axis=1 history bitwise == meshless history;
* full mode additionally records the >=100M-param segment inside the
  envelope on the client x model mesh (the PR's headline number).

  PYTHONPATH=src python -m benchmarks.model_fl_bench [--smoke]
"""
from __future__ import annotations

import os
import sys
import time

# The client x model mesh wants 8 host devices; forcing them is only
# possible before jax initializes.  Under ``benchmarks.run`` jax may
# already be imported — the bench then degrades gracefully (mesh
# sections are skipped, the meshless gate still runs).
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.attacks import AttackConfig
from repro.fl import FLConfig, RoundEngine, make_zoo_federation, zoo_model
from repro.launch.mesh import make_host_mesh
from repro.models import ModelConfig
from repro.sharding import use_mesh

from .common import emit, smoke_main, write_report

MEM_ENVELOPE_MB = 512.0
AGGREGATOR = "diversefl"
SEQ = 32

# 13 x (640, 8H/4KV, 2560ff) + 32k vocab = 100,369,280 params — the
# smallest config of this family over the 10^8 floor the acceptance
# criterion names.
FULL_MODEL = ModelConfig(name="fl-llm-100m", n_layers=13, d_model=640,
                         n_heads=8, n_kv_heads=4, d_ff=2560,
                         vocab_size=32_000, attn_direct_max=SEQ)
# tiny gate model: layout checks are scale-free
TINY_MODEL = ModelConfig(name="fl-llm-tiny", n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=2, d_ff=128,
                         vocab_size=256, attn_direct_max=16)


def _cfg(n_clients: int, rounds: int) -> FLConfig:
    return FLConfig(
        n_clients=n_clients, f=1 if n_clients > 1 else 0, rounds=rounds,
        batch_size=2, l2=0.0, aggregator=AGGREGATOR, streaming=True,
        client_chunk=1, eval_every=rounds, compression="f32",
        attack=AttackConfig(kind="sign_flip" if n_clients > 1 else "none"))


def _engine(model, cfg, mesh=None):
    fed = make_zoo_federation(model, cfg, per_client=4, n_test=16)
    return RoundEngine(model, fed, cfg, mesh=mesh)


def _segment_temp_mb(eng, params, rounds: int) -> float:
    """Peak XLA temp of the AOT-compiled multi-round segment.  The
    lowering MUST happen under the engine's mesh — outside ``use_mesh``
    every model-axis constraint silently no-ops and the number measures
    the unsharded program."""
    carry = eng._prepare_carry(params)
    _k, subs = eng._segment_keys(jax.random.PRNGKey(0), rounds)
    lrs = jnp.zeros((rounds,), jnp.float32)
    with use_mesh(eng.mesh):
        comp = eng._segment.lower(carry, subs, lrs, False, None,
                                  eng.default_scenario).compile()
    return comp.memory_analysis().temp_size_in_bytes / 1e6


def _timed_run(eng, rounds: int):
    """(metrics dict of np arrays, rounds/sec) for a short training.
    Each run inits its own params: off the CPU the engine donates the
    carry, so params handed to one run are gone after it."""
    params = eng.model.init(jax.random.PRNGKey(1))
    lrs = jnp.full((rounds,), 3e-2, jnp.float32)
    t0 = time.time()
    _p, _k, metrics, _er = eng.run_training(
        params, jax.random.PRNGKey(0), lrs)
    metrics = {k: np.asarray(v) for k, v in metrics.items()}
    jax.block_until_ready(metrics)
    return metrics, rounds / (time.time() - t0)


def _history_bitwise(a: dict, b: dict) -> bool:
    return (set(a) == set(b)
            and all(np.array_equal(a[k], b[k], equal_nan=True) for k in a))


def run(smoke: bool = False):
    rounds = 2
    model_cfg = TINY_MODEL if smoke else FULL_MODEL
    model = zoo_model(model_cfg, seq_len=SEQ if not smoke else 16)
    params = model.init(jax.random.PRNGKey(1))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    have_mesh = jax.device_count() >= 8

    # ---- envelope: temps vs (data, model) mesh shape ----------------
    temps = {}
    if have_mesh:
        for data, mdl in ((2, 4), (1, 8)):
            eng = _engine(model, _cfg(n_clients=max(data, 2), rounds=rounds),
                          mesh=make_host_mesh(data=data, model=mdl))
            temps[f"{data}x{mdl}"] = _segment_temp_mb(eng, params, rounds)
            emit(f"model_fl/temp_mb_{data}x{mdl}",
                 0.0, f"{temps[f'{data}x{mdl}']:.1f}")
    eng_flat = _engine(model, _cfg(n_clients=2, rounds=rounds))
    temps["unsharded"] = _segment_temp_mb(eng_flat, params, rounds)
    emit("model_fl/temp_mb_unsharded", 0.0, f"{temps['unsharded']:.1f}")

    # ---- throughput: the sharded segment must RUN, not just compile -
    sharded_rps = None
    sharded_ok = True
    if have_mesh:
        eng_s = _engine(model, _cfg(n_clients=2, rounds=rounds),
                        mesh=make_host_mesh(data=2, model=4))
        m_s, sharded_rps = _timed_run(eng_s, rounds)
        sharded_ok = all(np.isfinite(v).all() for v in m_s.values())
        emit("model_fl/sharded_rounds_per_sec", 1e6 / max(sharded_rps, 1e-9),
             f"{sharded_rps:.4f}")
    m_f, flat_rps = _timed_run(eng_flat, rounds)
    flat_ok = all(np.isfinite(v).all() for v in m_f.values())
    emit("model_fl/unsharded_rounds_per_sec", 1e6 / max(flat_rps, 1e-9),
         f"{flat_rps:.4f}")

    # ---- model-axis=1 bitwise gate (scale-free: tiny model) ---------
    gate_model = zoo_model(TINY_MODEL, seq_len=16)
    gcfg = _cfg(n_clients=4, rounds=4)
    hist_meshless, _ = _timed_run(_engine(gate_model, gcfg), gcfg.rounds)
    bitwise = True
    if have_mesh:
        hist_m1, _ = _timed_run(
            _engine(gate_model, gcfg, mesh=make_host_mesh(data=4, model=1)),
            gcfg.rounds)
        bitwise = _history_bitwise(hist_meshless, hist_m1)
    emit("model_fl/model_axis1_bitwise", 0.0, bitwise)

    sharded_temp = temps.get("2x4")
    acceptance = {
        "model_axis1_bitwise_vs_meshless": bitwise,
        "sharded_run_completes_finite": sharded_ok,
        "unsharded_run_completes_finite": flat_ok,
        "sharded_under_envelope":
            sharded_temp is None or sharded_temp <= MEM_ENVELOPE_MB,
    }
    if not smoke:
        acceptance["ge_100m_params"] = n_params >= 100_000_000
        acceptance["envelope_100m_client_x_model"] = (
            sharded_temp is not None and sharded_temp <= MEM_ENVELOPE_MB
            and n_params >= 100_000_000 and sharded_ok)

    return write_report(
        "model_fl", smoke=smoke, acceptance=acceptance,
        config={"model": model_cfg.name, "n_params": int(n_params),
                "rounds": rounds, "aggregator": AGGREGATOR,
                "envelope_mb": MEM_ENVELOPE_MB,
                "devices": jax.device_count(),
                "mesh_sections": have_mesh},
        temps_mb={k: round(v, 1) for k, v in temps.items()},
        rounds_per_sec={"sharded_2x4": sharded_rps,
                        "unsharded": flat_rps})


if __name__ == "__main__":
    smoke_main(run)
