"""Read the two ends that a cell's limits are set between.

    python -m bench.control --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--out <file.json>]

In one process (the engine compiles once): the program's readings of
the three set-up rounds on every seed of ``--seeds`` (the lower
readings), then, with the program's state freed, the reference's on
the same seeds; on ``--control-seeds`` also the control (the reference
one precision down, ``reference.CONTROL_DTYPE``) and the reference with
each planted fault (``reference.FAULTS``) put in the program's place
(the upper readings).  Prints one line per reading and, with ``--out``,
writes them all as JSON.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import numpy as np

from . import correct, reference, spec, system
from .run import devices_for, log, program_on_path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    program_on_path()
    import jax
    bench = spec.benchmark()
    w = spec.workload(args.workload, bench)
    devices_for(int(w["chips"]))
    conf, traffic = spec.config(w["config"], bench), spec.traffic(w["traffic"])
    cfgmod = spec.config_module(w["config"])
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    cell = system.Cell(conf, traffic, cfgmod)
    prog = {}
    for s in seeds:
        p, _, prog[s] = cell.prime(s)
        del p
    data = cell.data
    del cell
    gc.collect()
    jax.clear_caches()

    stated = conf["param_dtype"]
    variants = [("control", reference.CONTROL_DTYPE[stated], None)] + [
        (f, None, f) for f in reference.FAULTS]
    rows = []
    refer = reference.Reference(cfgmod, conf, traffic, data)
    for s in seeds:
        ref = refer.readings(s)
        sides = [("program", prog[s])]
        if s in cseeds:
            sides += [(name, refer.readings(s, dtype=dt, fault=f))
                      for name, dt, f in variants]
        for side, r in sides:
            nums = correct.numbers(r, ref)
            nums["margin"] = correct.threshold_margin(ref["c1c2"],
                                                      traffic["eps"])
            nums["c1c2_min"] = float(np.min(np.abs(ref["c1c2"])))
            nums["c1c2_max"] = float(np.max(np.abs(ref["c1c2"])))
            nums["cos_min"] = float(np.min(np.abs(ref["cos"])))
            nums["ref_seconds"] = float(sum(ref["seconds"]))
            rows.append({"seed": s, "side": side, **nums})
            log(f"[{args.workload}] seed {s} {side}: " + ", ".join(
                f"{k} {v:.6g}" for k, v in nums.items()))
    for side in sorted({r["side"] for r in rows}):
        sel = [r for r in rows if r["side"] == side]
        log(f"[{args.workload}] {side} over {len(sel)} seeds: " + ", ".join(
            f"{k} max {max(r[k] for r in sel):.6g} min "
            f"{min(r[k] for r in sel):.6g}" for k in correct.NAMES))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f,
                      indent=1, default=float)
    print(json.dumps({"workload": args.workload, "n": len(rows),
                      "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
