"""End-to-end driver: federated training of a ~100M-parameter LM through
the compiled round engine on a host mesh of 8 simulated devices = 4 FL
clients x 2-way tensor (model) parallelism.  One client is Byzantine
(sign flip) — watch its updates get filtered while accuracy climbs.

This is the engine path (fl/engine.RoundEngine): the SAME Steps 2-5
definition every simulator run, sweep and benchmark compiles, here with
the flattened update vector model-sharded over the mesh's ``model`` axis
(DESIGN.md §12) — params take the MODEL_AXIS partition table's placement
and each round's whole eval segment runs as one donated device program.
The bespoke per-step shard_map loop this file used to carry is gone;
``launch.train.make_fl_round_step`` remains the production-mesh lowering
reference (see launch/dryrun.py), not a driver.

    PYTHONPATH=src python examples/train_fl_llm.py --rounds 300   # full
    PYTHONPATH=src python examples/train_fl_llm.py --rounds 20    # demo
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.compile_cache import enable_compile_cache
from repro.core.attacks import AttackConfig
from repro.fl import FLConfig, RoundEngine, make_zoo_federation, zoo_model
from repro.launch.mesh import make_host_mesh
from repro.models import ModelConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", "--steps", dest="rounds", type=int,
                    default=20)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=640)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    cfg = ModelConfig(
        name="fl-llm-100m", n_layers=args.layers, d_model=args.d_model,
        n_heads=8, n_kv_heads=4, d_ff=4 * args.d_model, vocab_size=32_000,
        attn_direct_max=args.seq)
    mesh = make_host_mesh(data=4, model=2)
    print(f"model: {cfg.param_count()/1e6:.1f}M params; mesh {dict(mesh.shape)}"
          f" -> 4 FL clients x 2-way tensor parallel")

    model = zoo_model(cfg, seq_len=args.seq)
    fl = FLConfig(
        n_clients=4, f=1, rounds=args.rounds, batch_size=2, l2=0.0,
        aggregator="diversefl", streaming=True,
        eval_every=min(args.eval_every, args.rounds),
        attack=AttackConfig(kind="sign_flip"))   # client set by byz_mask
    fed = make_zoo_federation(model, fl, per_client=8, n_test=32)

    engine = RoundEngine(model, fed, fl, mesh=mesh)
    t0 = time.time()
    params, _, metrics, eval_rounds = engine.run_training(
        model.init(jax.random.PRNGKey(fl.seed + 1)),
        jax.random.PRNGKey(fl.seed),
        jnp.full((fl.rounds,), args.lr, jnp.float32))
    for r, acc, tpr in zip(np.asarray(eval_rounds),
                           np.asarray(metrics["acc"]),
                           np.asarray(metrics.get("mask_tpr", eval_rounds))):
        print(f"round {int(r):4d} acc={float(acc):.4f} "
              f"byz-detect-tpr={float(tpr):.2f}")
    print(f"{fl.rounds} rounds in {time.time()-t0:.1f}s "
          f"({engine.model_shards}-way model parallel)")
    if args.ckpt:
        save_checkpoint(args.ckpt, args.rounds, engine.carry_params(params))
        print("checkpoint saved to", args.ckpt)


if __name__ == "__main__":
    main()
