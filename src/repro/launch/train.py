"""The FL round step at pod scale — DiverseFL Steps 2–5 as ONE SPMD program.

Mesh axes ("pod","data","model"): each FL client is one (pod,data)
coordinate and owns a model-parallel slice group of 16 chips.  The round
step runs inside ``jax.shard_map`` *manual* over the client axes and
*auto* over ``model`` — tensor/expert parallelism needs no hand-written
collectives, while the FL semantics are explicit:

  1. client local SGD (E steps) on the local batch  -> update z_j
  2. (test-only) simulated Byzantine corruption of z_j
  3. guiding update Δ̃_j on the client's enclave sample (same E, same lr)
  4. per-client similarity scalars via shard-local reductions
     (GSPMD inserts the psum over ``model``)                 [C1/C2]
  5. masked mean over the client axes: one psum               [Eq. 6]

Per-client updates are never materialized N-fold: each client's update
lives only on its own mesh slice, and the criterion needs 3 scalars.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import models
from ..core.diversefl import (DiverseFLConfig, criterion_logs, diversefl_mask,
                              similarity_stats_tree)
from ..sharding import partition_pytree, use_mesh
from .mesh import client_axes, n_clients

F32 = jnp.float32

# simulated fault codes (cheap, RNG-free: part of the compiled step only
# for integration testing; 0 in production)
FAULT_NONE, FAULT_SIGN_FLIP, FAULT_SAME_VALUE, FAULT_SCALE = 0, 1, 2, 3
SAME_VALUE_SIGMA = 100.0
SCALE_FACTOR = 5.0


def update_psum_dtype(update_dtype):
    """The dtype client updates are all-reduced in.

    XLA:CPU's AllReducePromotion pass CHECK-fails cloning a bf16
    all-reduce (host dry-run only); TPU does bf16 all-reduce natively,
    so the cast up to f32 is gated on the backend.  One definition so
    the workaround is pinned by a regression test
    (tests/test_compression.py) instead of living as an inline branch a
    refactor can silently drop."""
    return F32 if jax.default_backend() == "cpu" else update_dtype


def resolve_update_dtype(compression: str, update_dtype=None):
    """Map a codec name to the wire dtype the shard_map round step
    carries client updates in.

    The pod-scale step moves updates as *native arrays* through psums —
    a dense-payload codec (``fl/compression.Codec.wire_dtype`` set:
    f32, bf16) IS a dtype choice there, so both launch knobs route
    through the one codec registry the simulator uses.  Codecs that
    need a scale sidecar (int8) have no single wire dtype and raise a
    named error rather than silently degrading.  ``update_dtype`` is
    the legacy knob: when given it must agree with ``compression``
    (or ``compression`` must be the default)."""
    from ..fl.compression import get_codec
    codec = get_codec(compression)
    if codec.wire_dtype is None:
        raise ValueError(
            f"compression={compression!r} has no dense wire dtype: the "
            f"pod-scale shard_map round step psums native update arrays "
            f"and cannot carry the {compression!r} scale sidecar — use "
            f"'f32'/'bf16' here, or run the int8 path through the "
            f"simulator's streaming fold (fl/engine.py)")
    if update_dtype is not None and update_dtype != codec.wire_dtype \
            and compression != "f32":
        raise ValueError(
            f"update_dtype={jnp.dtype(update_dtype).name!r} conflicts "
            f"with compression={compression!r} "
            f"(wire dtype {jnp.dtype(codec.wire_dtype).name!r})")
    return update_dtype if update_dtype is not None else codec.wire_dtype


def _local_batch(cfg, inputs):
    b = {"tokens": inputs["tokens"]}
    if "enc_emb" in inputs:
        b["enc_emb"] = inputs["enc_emb"]
    if "cross_emb" in inputs:
        b["cross_emb"] = inputs["cross_emb"]
    return b


def _guide_batch(cfg, inputs):
    b = {"tokens": inputs["guide_tokens"][0]}
    if "guide_enc_emb" in inputs:
        b["enc_emb"] = inputs["guide_enc_emb"][0]
    if "guide_cross_emb" in inputs:
        b["cross_emb"] = inputs["guide_cross_emb"][0]
    return b


def make_fl_round_step(cfg, mesh, dfl: DiverseFLConfig = DiverseFLConfig(),
                       lr: float = 1e-3, local_steps: int = 1,
                       donate: bool = True, update_dtype=None,
                       robust_mode: str = "diversefl",
                       compression: str = "f32"):
    """Returns a jit'd round_step(params, inputs) -> (new_params, metrics).

    ``inputs`` is the dict produced by launch.shapes.train_inputs.
    ``compression``: codec name from the fl/compression registry naming
    the dtype client updates are carried/psum'd in ("f32"/"bf16" — see
    :func:`resolve_update_dtype`).  f32 is the paper-faithful baseline;
    bf16 is the beyond-paper variant (halves update HBM traffic and
    aggregation collective volume; the C1/C2 similarity stats are still
    accumulated in fp32 — see EXPERIMENTS.md §Perf).  ``update_dtype``
    is the legacy spelling of the same knob (kept so existing callers
    and benches run unchanged); it must agree with ``compression`` when
    both are given.

    ``robust_mode``: "diversefl" (per-client criteria + masked mean — the
    paper) or "median" (coordinate-wise median across clients — the
    cross-client baseline family).  Median requires every chip to hold
    all N client update shards simultaneously (an all-gather over the
    client axes); it exists here to *quantify* the systems gap between
    cross-client statistics and DiverseFL's 3-scalars-per-client at pod
    scale (EXPERIMENTS.md §Perf, "median at scale").
    """
    assert robust_mode in ("diversefl", "median")
    caxes = client_axes(mesh)
    nc = n_clients(mesh)
    UDT = resolve_update_dtype(compression, update_dtype)

    def local_loss(params, batch):
        return models.loss_fn(params, cfg, batch)

    def client_update(params, batch):
        """Δ = θ0 - θE after E local SGD steps (E=1: just lr * grad)."""
        if local_steps == 1:
            loss, g = jax.value_and_grad(local_loss)(params, batch)
            return jax.tree.map(lambda x: (lr * x.astype(F32)).astype(UDT),
                                g), loss

        def step(theta, _):
            g = jax.grad(local_loss)(theta, batch)
            theta = jax.tree.map(
                lambda t, gg: (t.astype(F32) - lr * gg.astype(F32)).astype(t.dtype),
                theta, g)
            return theta, None
        theta, _ = jax.lax.scan(step, params, None, length=local_steps)
        delta = jax.tree.map(
            lambda a, b: (a.astype(F32) - b.astype(F32)).astype(UDT),
            params, theta)
        return delta, local_loss(params, batch)

    def round_fn(params, inputs):
        # ---- Step 2: client local training on the local shard ----
        z, loss = client_update(params, _local_batch(cfg, inputs))

        # ---- simulated Byzantine faults (integration testing) ----
        kind = inputs["byz_kind"][0]
        mult = jnp.where(kind == FAULT_SIGN_FLIP, -1.0, 1.0) * \
            jnp.where(kind == FAULT_SCALE, SCALE_FACTOR, 1.0)
        z = jax.tree.map(
            lambda u: jnp.where(kind == FAULT_SAME_VALUE,
                                jnp.asarray(SAME_VALUE_SIGMA, u.dtype),
                                u * mult.astype(u.dtype)), z)

        if robust_mode == "median":
            # cross-client baseline: gather all client updates, take the
            # coordinate-wise median.  N x update memory + collective —
            # deliberately so (see docstring).
            def med(u):
                allu = jax.lax.all_gather(u, caxes)
                allu = allu.reshape((-1,) + u.shape)
                return jnp.median(allu, axis=0)
            agg = jax.tree.map(med, z)
            new_params = jax.tree.map(
                lambda p, a: (p.astype(F32) - a.astype(F32)).astype(p.dtype),
                params, agg)
            metrics = {"loss": jax.lax.pmean(loss, caxes),
                       "kept": jnp.float32(nc),
                       "mask": jnp.ones((1,), bool),
                       "c1": jnp.ones((1,)), "c2": jnp.ones((1,))}
            return new_params, metrics

        # ---- Step 3: guiding update on the enclave sample ----
        g, _ = client_update(params, _guide_batch(cfg, inputs))

        # ---- Step 4: per-client similarity scalars (psum over model is
        #      inserted by GSPMD; client axes are manual => per-client).
        #      similarity_stats_tree reduces per-leaf elementwise products
        #      (never jnp.vdot), keeping partial sums shard-local — see
        #      core/diversefl.py (§Perf A2). ----
        dot, zz, gg = similarity_stats_tree(z, g)
        mask = diversefl_mask(dot, zz, gg, dfl)

        # ---- Step 5: masked mean over clients (Eq. 6) + model update ----
        m = mask.astype(F32)
        cnt = jax.lax.psum(m, caxes)
        denom = jnp.maximum(cnt, 1.0)
        psum_dt = update_psum_dtype(UDT)
        agg = jax.tree.map(
            lambda u: jax.lax.psum((u * m.astype(u.dtype)).astype(psum_dt),
                                   caxes).astype(F32) / denom, z)
        new_params = jax.tree.map(
            lambda p, a: (p.astype(F32) - a).astype(p.dtype), params, agg)

        crit = criterion_logs(dot, zz, gg)
        metrics = {
            "loss": jax.lax.pmean(loss, caxes),
            "kept": cnt,
            "mask": mask.reshape(1),
            "c1": crit["c1"].reshape(1),
            "c2": crit["c2"].reshape(1),
        }
        return new_params, metrics

    # in/out specs: params replicated over client axes (model handled auto);
    # batch-like inputs split over client axes on dim 0.
    def in_spec_for(name, ndim):
        if name == "rng":
            return P()
        return P(*((caxes,) + (None,) * (ndim - 1)))

    def round_step_fn(params, inputs):
        input_specs = {k: in_spec_for(k, inputs[k].ndim) for k in inputs}
        params_specs = jax.tree.map(lambda _: P(), params)
        out_metric_specs = {"loss": P(), "kept": P(), "mask": P(caxes),
                            "c1": P(caxes), "c2": P(caxes)}
        f = jax.shard_map(
            round_fn, mesh=mesh,
            in_specs=(params_specs, input_specs),
            out_specs=(params_specs, out_metric_specs),
            axis_names=set(caxes), check_vma=False)
        with use_mesh(mesh):
            return f(params, inputs)

    jit_kwargs = {}
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    return jax.jit(round_step_fn, **jit_kwargs)


def sharded_param_specs(cfg, mesh):
    """ShapeDtypeStructs (with NamedShardings) for the model params."""
    shapes = jax.eval_shape(
        functools.partial(models.init, jax.random.PRNGKey(0), cfg))
    specs = partition_pytree(shapes)
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        shapes, specs)


# ----------------------------------------------------------------------
# Launcher CLI: federated training of a real zoo arch on the host
# client x model mesh, driven by the compiled round engine — the SAME
# Steps 2-5 definition (fl/engine.make_round_body) every simulator run
# and benchmark uses, with the flat D model-sharded over ``model``
# (DESIGN.md §12).  ``make_fl_round_step`` above stays as the explicit
# shard_map lowering reference (dryrun.py compiles it against the
# production mesh; tests/test_sharded_step.py pins its semantics) but
# no driver loops over it anymore: the engine path IS the launcher.
#
#   PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --rounds 10
# ----------------------------------------------------------------------

def main(argv=None):
    import argparse

    import numpy as np
    from ..compile_cache import enable_compile_cache
    from ..core.attacks import AttackConfig
    from ..fl.engine import RoundEngine
    from ..fl.simulator import FLConfig
    from ..fl.zoo import make_zoo_federation, zoo_model
    from .mesh import make_host_mesh, n_clients as _nc

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--rounds", "--steps", dest="rounds", type=int,
                    default=10)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--byzantine", type=int, default=1,
                    help="number of sign-flipping clients")
    ap.add_argument("--eval-every", type=int, default=5)
    args = ap.parse_args(argv)
    enable_compile_cache()

    n_dev = len(jax.devices())
    mesh = make_host_mesh(data=max(1, n_dev // 2), model=2 if n_dev > 1 else 1)
    nc = _nc(mesh)
    model = zoo_model(args.arch, seq_len=args.seq, smoke=True)
    print(f"launch: {model.name} ({model.param_count():,} params) on mesh "
          f"{dict(mesh.shape)} ({nc} clients)")

    cfg = FLConfig(
        n_clients=nc, f=args.byzantine, rounds=args.rounds,
        batch_size=args.batch, l2=0.0, aggregator="diversefl",
        streaming=True, eval_every=min(args.eval_every, args.rounds),
        attack=AttackConfig(kind="sign_flip" if args.byzantine else "none"))
    fed = make_zoo_federation(model, cfg, per_client=max(args.batch, 8))

    engine = RoundEngine(model, fed, cfg, mesh=mesh)
    params, _, metrics, eval_rounds = engine.run_training(
        model.init(jax.random.PRNGKey(cfg.seed + 1)),
        jax.random.PRNGKey(cfg.seed),
        jnp.full((cfg.rounds,), args.lr, jnp.float32))
    for r, acc in zip(np.asarray(eval_rounds), np.asarray(metrics["acc"])):
        print(f"  round {int(r):3d} acc={float(acc):.4f}")
    del params


if __name__ == "__main__":
    main()
