"""Shared benchmark scaffolding: timed FL runs, CSV emission, reports.

Every benchmark module maps to one paper table/figure and emits rows
``name,us_per_call,derived`` where us_per_call is wall-time per FL round
(or per op call) and derived is the figure's metric (accuracy, ratio...).
Acceptance-gated suites (benchmarks/run.py) additionally write a
``BENCH_<name>.json`` report through :func:`write_report` and exit
through :func:`smoke_main` — one definition of the gating contract for
all of them.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

import jax

from repro.core.attacks import AttackConfig
from repro.data import FederatedData, make_mnist_like, partition_sorted_shards
from repro.fl import FLConfig, Federation, run_federated_training
from repro.fl.small_models import softmax_regression
from repro.optim import inv_sqrt_lr

ROWS = []

REPO_ROOT = Path(__file__).resolve().parents[1]

# bump when the report layout changes shape (readers key on this)
REPORT_SCHEMA_VERSION = 2


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO_ROOT, check=True,
            capture_output=True, text=True, timeout=10).stdout.strip()
    except Exception:
        return "unknown"


def provenance() -> dict:
    """What produced this report: the reproducibility stamp every
    BENCH_*.json carries (a snapshot without these is uncomparable —
    you cannot tell a regression from a toolchain change)."""
    return {
        "git_sha": _git_sha(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
    }


def write_report(name: str, *, smoke: bool, acceptance: dict,
                 **sections) -> dict:
    """Assemble and write one suite's ``BENCH_<name>.json`` report.

    The shared tail of every acceptance-gated bench: the report is
    ``{"schema_version", "mode", "provenance", **sections,
    "acceptance"}`` with acceptance values coerced to plain bools (numpy
    bools are not JSON), written with the repo-standard 2-space indent +
    trailing newline, and the path announced on stderr.  Every report
    stamps the schema version, git SHA, and jax/backend versions
    (:func:`provenance`).  Returns the report dict so ``run()`` can
    hand it to :func:`smoke_main` for the exit-code gate."""
    report = {"schema_version": REPORT_SCHEMA_VERSION,
              "mode": "smoke" if smoke else "full",
              "provenance": provenance(),
              **sections,
              "acceptance": {k: bool(v) for k, v in acceptance.items()}}
    path = REPO_ROOT / f"BENCH_{name}.json"
    path.write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {path}", file=sys.stderr, flush=True)
    return report


def smoke_main(run_fn) -> None:
    """The shared ``main()`` of every acceptance-gated bench (engine,
    streaming, dispatch): parse ``--smoke``, run, print the acceptance
    dict, exit non-zero when a smoke acceptance fails — one definition
    instead of a copy per module."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes; exit 1 on failed acceptance")
    args = ap.parse_args()
    report = run_fn(smoke=args.smoke)
    ok = all(report["acceptance"].values())
    print(f"acceptance: {report['acceptance']}", flush=True)
    if args.smoke and not ok:
        sys.exit(1)


def emit(name: str, us_per_call: float, derived):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def mnist_like_federation(n_clients=23, n_train=4600, n_test=800, seed=0):
    x, y = make_mnist_like(jax.random.PRNGKey(seed), n_train)
    tx, ty = make_mnist_like(jax.random.PRNGKey(seed + 9), n_test)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, n_clients), 10)
    return data, tx, ty


def timed_fl_run(model, data, tx, ty, aggregator: str, attack: AttackConfig,
                 rounds: int = 60, lr0: float = 0.05, seed: int = 2, **kw):
    cfg = FLConfig(n_clients=data.n_clients, rounds=rounds,
                   aggregator=aggregator, attack=attack, batch_size=50,
                   eval_every=rounds, **kw)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(seed))
    t0 = time.time()
    hist = run_federated_training(model, fed, cfg, inv_sqrt_lr(lr0))
    dt = time.time() - t0
    return hist, fed, dt / rounds * 1e6
