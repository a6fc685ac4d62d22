"""BENCHMARK.json and the files under bench/ agree, and the harness finds
a new cell, configuration or metric by its files alone."""
import json
import re
import shutil
import subprocess
import sys

import pytest

from bench import correct, run, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert not any(w.startswith("/") or ".." in w for w in bench["command"])


def test_names_and_units(bench):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    names += [w[k] for w in bench["workloads"] for k in ("config",
                                                         "traffic")]
    names += [r for c in bench["configs"] for r in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for k in ("end_to_end", "per_layer"):
        assert len({m["name"] for m in bench[k]}) == len(bench[k])
        for m in bench[k]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                            "higher")


def test_cells_name_existing_pieces(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        t = spec.traffic(w["traffic"])
        assert set(t["limits"]) == set(correct.NAMES)
        conf = spec.config(w["config"], bench)
        assert conf["reduced"] == configs[w["config"]]["reduced"]
        spec.config_module(w["config"])
    assert {w["config"] for w in bench["workloads"]} == set(configs)


def test_metrics_move_what_their_cells_report(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.25
                                    for m in bench["end_to_end"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert callable(spec.metric_module(m["name"]).read)
        for cell in m.get("workloads", []):
            spec.workload(cell, bench)
            assert any(x["name"] == m["moves"]
                       for x in spec.end_to_end_for(cell, bench))
    for w in bench["workloads"]:
        assert spec.per_layer_for(w["name"], bench)
        assert len(spec.end_to_end_for(w["name"], bench)) >= 2


def test_unknown_device_kind_is_an_error(monkeypatch):
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9

    class Fake:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(run, "devices_for", lambda chips: [Fake()])
    assert run.main(["--workload", "paper-n23", "--seed", "1",
                     "--seconds", "1"]) == 2


def test_no_tpu_exits_nonzero_without_a_result():
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "paper-n23",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=spec.REPO_DIR, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_new_pieces_are_found_by_their_files(tmp_path):
    """A cell, a configuration and a per-layer metric added as new files
    plus BENCHMARK.json entries: no existing file under bench/ changes."""
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    b = spec.benchmark()
    (root / "bench/configs/new-model.json").write_text(
        json.dumps({"name": "new-model", "reduced": []}))
    (root / "bench/configs/new-model.py").write_text(
        "def forward_flops(conf, traffic):\n    return 7.0\n")
    (root / "bench/traffic/new-mix.json").write_text(
        json.dumps({"limits": {}}))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b["configs"].append({"name": "new-model", "source": "x",
                         "file": "bench/configs/new-model.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "new-cell", "config": "new-model",
                           "traffic": "new-mix", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "new_metric", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "x", "moves": "rounds_per_s",
                           "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    s = spec.load_module(root / "bench" / "spec.py", "bench_spec_copy")
    assert s.workload("new-cell")["config"] == "new-model"
    assert s.config("new-model")["name"] == "new-model"
    assert s.config_module("new-model").forward_flops({}, {}) == 7.0
    assert s.traffic("new-mix") == {"limits": {}}
    assert s.metric_module("new_metric").read(None) == 42.0
    assert [m["name"] for m in s.per_layer_for("new-cell")] == \
        ["device_idle_share", "mfu", "new_metric"]
    assert "new_metric" not in [m["name"]
                                for m in s.per_layer_for("paper-n23")]
    after = {p: p.read_bytes() for p in before}
    assert after == before
