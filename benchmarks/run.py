"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  python -m benchmarks.run               # everything (full rounds)
  python -m benchmarks.run --quick       # reduced rounds (CI)
  python -m benchmarks.run --only fig3   # one table/figure
  python -m benchmarks.run async --smoke # one suite, acceptance-gated:
                                         # reduced sizes AND exit 1 when
                                         # any written acceptance fails

A suite that raises makes the harness exit 1 in every mode.

Suites are declared in the ``SUITES`` registry below: ``(name, module,
knob)`` where ``knob`` names the reduced-size keyword the module's
``run()`` accepts under ``--quick`` (``"rounds"`` for the paper-figure
benches, ``"smoke"`` for the acceptance-gated system benches, ``None``
for fixed-size ones) — adding a bench is one line, not a copied block.
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
    ("kernels", "kernel_bench", None),
    ("engine", "engine_bench", "smoke"),
    ("streaming", "streaming_bench", "smoke"),
    ("tree_agg", "tree_agg_bench", "smoke"),
    ("dispatch", "dispatch_bench", "smoke"),
    ("sweep", "sweep_bench", "smoke"),
    ("comm", "comm_bench", "smoke"),
    ("async", "async_bench", "smoke"),
    ("model_fl", "model_fl_bench", "smoke"),
    ("roofline", "roofline", None),
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("suite", nargs="?", default=None,
                    help="suite name substring (same filter as --only)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes like --quick, but exit 1 when a "
                         "suite writes a failed acceptance")
    args = ap.parse_args()
    enable_compile_cache()
    only = args.only or args.suite
    reduced = args.quick or args.smoke

    failed = errored = False
    print("name,us_per_call,derived")
    for name, module, knob in SUITES:
        if only and only not in name:
            continue
        kwargs = {}
        if knob == "rounds" and reduced:
            kwargs["rounds"] = QUICK_ROUNDS
        elif knob == "smoke":
            kwargs["smoke"] = reduced
        t0 = time.time()
        try:  # import inside: a broken module must not abort the sweep
            mod = importlib.import_module(f".{module}", __package__)
            report = mod.run(**kwargs)
            if isinstance(report, dict) and "acceptance" in report:
                print(f"# {name} acceptance: {report['acceptance']}",
                      file=sys.stderr, flush=True)
                failed |= not all(report["acceptance"].values())
        except Exception as e:  # keep the harness going; surface the failure
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            errored = True
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr,
              flush=True)
    if errored or (args.smoke and failed):
        sys.exit(1)


if __name__ == "__main__":
    main()
