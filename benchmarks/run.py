"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  python -m benchmarks.run               # everything (full rounds)
  python -m benchmarks.run --quick       # reduced rounds
  python -m benchmarks.run --only fig3   # one table/figure

A suite that raises makes the harness exit 1.

Suites are declared in the ``SUITES`` registry below: ``(name, module,
knob)`` where ``knob`` names the reduced-size keyword the module's
``run()`` accepts under ``--quick`` (``"rounds"`` for the paper-figure
benches, ``None`` for fixed-size ones) — adding a bench is one line,
not a copied block.  The system's speed benchmark is ``bench/``
(``python3 -m bench.run``), not this harness.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time

from repro.compile_cache import enable_compile_cache

QUICK_ROUNDS = 25

# (suite name, benchmarks.<module>, quick-mode knob)
SUITES = (
    ("fig2", "fig2_criteria", "rounds"),
    ("fig3", "fig3_softmax", "rounds"),
    ("fig456", "fig456_nn", "rounds"),
    ("fig7", "fig7_backdoor", "rounds"),
    ("fig8", "fig8_poisoning", None),
    ("fig9", "fig9_timing", None),
    ("tab234", "tab234_f17", "rounds"),
    ("ablation", "ablation", "rounds"),
    ("roofline", "roofline", None),
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("suite", nargs="?", default=None,
                    help="suite name substring (same filter as --only)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    only = args.only or args.suite

    errored = False
    print("name,us_per_call,derived")
    for name, module, knob in SUITES:
        if only and only not in name:
            continue
        kwargs = {}
        if knob == "rounds" and args.quick:
            kwargs["rounds"] = QUICK_ROUNDS
        t0 = time.time()
        try:  # import inside: a broken module must not abort the sweep
            mod = importlib.import_module(f".{module}", __package__)
            mod.run(**kwargs)
        except Exception as e:  # keep the harness going; surface the failure
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            errored = True
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr,
              flush=True)
    if errored:
        sys.exit(1)


if __name__ == "__main__":
    main()
