import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")   # silence SPMD warnings

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers and compiles for the production meshes, and extract
the roofline inputs (memory_analysis, cost_analysis, collective schedule)
from the compiled artifact.  No real allocation: every input is a
ShapeDtypeStruct.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                    # all combos
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
      --shape train_4k --mesh both
  ... --out results/dryrun.json
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from .. import configs
from ..core.diversefl import DiverseFLConfig
from . import hlo as hlo_lib
from .mesh import make_production_mesh
from .serve import make_prefill, make_serve_step
from .shapes import SHAPES, applicable, serve_inputs, train_inputs
from .train import make_fl_round_step, sharded_param_specs


def _cost_dict(compiled):
    c = compiled.cost_analysis()
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    return dict(c) if c else {}


def _mem_dict(compiled):
    try:
        m = compiled.memory_analysis()
    except Exception:
        return {}
    if m is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(m, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def dryrun_one(arch_id: str, shape_name: str, multi_pod: bool,
               verbose: bool = True, opt: bool = False) -> dict:
    t0 = time.time()
    cfg = configs.get(arch_id)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "status": "ok", "opt": opt}
    if not applicable(cfg, shape):
        rec["status"] = "skip"
        rec["reason"] = ("full-attention arch: long_500k requires "
                         "sub-quadratic attention (DESIGN.md §4)")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    params = sharded_param_specs(cfg, mesh)

    if shape.kind == "train":
        specs, _ = train_inputs(cfg, shape, mesh)
        step = make_fl_round_step(
            cfg, mesh, DiverseFLConfig(),
            donate=False, compression="bf16" if opt else "f32")
        lowered = step.lower(params, specs)
    elif shape.kind == "prefill":
        prefill = make_prefill(cfg, mesh)
        from .shapes import sds
        from ..launch.mesh import client_axes
        from jax.sharding import PartitionSpec as P
        caxes = client_axes(mesh)
        tok, _ = sds((shape.batch, shape.seq), jnp.int32, mesh,
                     P(caxes, None))
        tok = jax.ShapeDtypeStruct(tok.shape, tok.dtype,
                                   sharding=_nsh(mesh, P(caxes, None)))
        kwargs = {}
        if cfg.is_enc_dec:
            kwargs["enc_emb"] = jax.ShapeDtypeStruct(
                (shape.batch, cfg.enc_seq, cfg.d_model), jnp.bfloat16,
                sharding=_nsh(mesh, P(caxes, None, None)))
        elif cfg.has_cross:
            kwargs["cross_emb"] = jax.ShapeDtypeStruct(
                (shape.batch, cfg.n_patches, cfg.d_model), jnp.bfloat16,
                sharding=_nsh(mesh, P(caxes, None, None)))
        lowered = prefill.lower(params, tok, **kwargs)
    else:  # decode
        specs, shardings = serve_inputs(cfg, shape, mesh)
        specs = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
            if sh is not None else s, specs, shardings,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        step = make_serve_step(cfg, mesh, donate_cache=False)
        lowered = step.lower(params, specs["token"], specs["cache"],
                             specs["cache_index"])

    rec["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    cost = _cost_dict(compiled)
    mem = _mem_dict(compiled)
    text = compiled.as_text()
    coll = hlo_lib.collective_stats(text)
    rec["cost"] = {k: float(v) for k, v in cost.items()
                   if isinstance(v, (int, float))}
    rec["memory"] = mem
    rec["collectives"] = coll
    rec["collective_bytes"] = hlo_lib.total_collective_bytes(text)
    rec["roofline"] = hlo_lib.roofline_terms(cost, rec["collective_bytes"])
    if verbose:
        r = rec["roofline"]
        print(f"[{rec['status']:4s}] {arch_id:22s} {shape_name:12s} "
              f"{mesh_name:8s} lower={rec['lower_s']:7.1f}s "
              f"compile={rec['compile_s']:7.1f}s "
              f"flops={r['flops']:.3e} bytes={r['bytes']:.3e} "
              f"coll={r['collective_bytes']:.3e} dom={r['dominant']}")
        print(f"       memory_analysis: {mem}")
    return rec


def _nsh(mesh, spec):
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, spec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--bf16", "--opt", dest="opt", action="store_true",
                    help="beyond-paper optimized round step: bf16 update "
                         "codec (fl/compression.py; --opt is the legacy "
                         "spelling)")
    args = ap.parse_args()

    archs = configs.all_arch_ids() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    records.append(dryrun_one(arch, shape, mp, opt=args.opt))
                except Exception as e:
                    traceback.print_exc()
                    records.append({"arch": arch, "shape": shape,
                                    "mesh": "2x16x16" if mp else "16x16",
                                    "status": "error", "error": repr(e)})
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skip" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"\ndry-run: {n_ok} ok, {n_skip} documented skips, {n_err} errors")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print("wrote", args.out)
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
