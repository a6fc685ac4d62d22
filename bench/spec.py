"""Find a cell's pieces by name.

``BENCHMARK.json`` names the cells, configurations and metrics; each
one's files sit under ``bench/`` at a path made from its name:

* ``configs/<config>.json``: the configuration as it is run;
* ``configs/<config>.py``: its model, weights, plain reference and
  FLOP count (the functions it defines: :func:`config_module`);
* ``traffic/<traffic>.json``: the federation and the job one cell runs;
* ``metrics/<metric>.py``: a per-layer metric's reader, ``read(ctx)``.

Adding a cell, a configuration or a metric adds files and an entry in
``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REPO_DIR = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(REPO_DIR / "BENCHMARK.json")


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(name: str, bench: dict | None = None) -> dict:
    return _by_name((bench or benchmark())["workloads"], name, "workload")


def config_entry(name: str, bench: dict | None = None) -> dict:
    return _by_name((bench or benchmark())["configs"], name, "config")


def config(name: str, bench: dict | None = None) -> dict:
    """The configuration file named by the ``configs`` entry."""
    return load_json(REPO_DIR / config_entry(name, bench)["file"])


def traffic(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_module(name: str):
    """``configs/<name>.py``.  It defines ``build(conf, traffic)`` (the
    program's model), ``init_params(conf, traffic, key)`` (weights in
    the program's layout, made by the benchmark), ``reference_loss(conf,
    traffic)`` (a plain ``loss(params, x, y, q)`` in float32, ``q``
    rounding every stored activation) and ``forward_flops(conf,
    traffic)`` (model FLOPs of one example's forward pass)."""
    return load_module(BENCH_DIR / "configs" / f"{name}.py",
                       "bench_config_" + name.replace("-", "_")
                       .replace(".", "_"))


def metric_module(name: str):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace("-", "_")
                       .replace(".", "_"))


def per_layer_for(cell: str, bench: dict | None = None) -> list:
    """The per-layer metrics a cell reports."""
    bench = bench or benchmark()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            moved = e2e[m["moves"]]
            cells = moved.get("workloads",
                              [w["name"] for w in bench["workloads"]])
        if cell in cells:
            out.append(m)
    return out


def end_to_end_for(cell: str, bench: dict | None = None) -> list:
    bench = bench or benchmark()
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def peaks(device_kind: str) -> dict:
    """The peak table's row for this device; a device not in the table
    is an error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json") from None
