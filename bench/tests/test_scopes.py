"""The stage split of a device trace (``bench/scopes.py``) and the seven
per-stage metrics, on the two traces a TPU v5e recorded:

* ``probe.xplane.pb``: three calls of a program with no stage scopes
  (the Pallas similarity kernel and fold on (23, 4,194,304) float32);
* ``scoped.xplane.pb``: three calls of the program's own scoped round
  (``record_probe.py``: a dense DiverseFL round with both kernels, two
  rounds and one eval a call), inside the harness's ``window``,
  ``dispatch`` and ``sync`` spans.
"""
import shutil
import types
from pathlib import Path

import jax
import pytest

from bench import run, scopes, spec
from bench import trace as tr
from bench.tests import tiny
from bench.tests.record_probe import field, varint

DATA = Path(__file__).parent / "data"
PROBE = DATA / "probe.xplane.pb"
SCOPED = DATA / "scoped.xplane.pb"
STAGE_METRICS = {"client_sgd_ms": "client_sgd", "guide_sgd_ms": "guide_sgd",
                 "attack_ms": "attack", "flatten_ms": "flatten",
                 "filter_ms": "step4_filter", "fold_ms": "step5_fold",
                 "eval_ms": "eval"}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def context(path, rounds, trace_path=True, n_params=4_194_304):
    cell = types.SimpleNamespace(
        conf=spec.config("vgg11-paper"), traffic=spec.traffic("paper-n23"),
        cfgmod=spec.config_module("vgg11-paper"), n_params=n_params)
    ctx = run.Context(tr.load(path), cell, rounds,
                      spec.peaks("TPU v5 lite"), 1)
    if trace_path:
        ctx.trace_path = str(path)
    return ctx


# ----------------------------------------------------------------------
# the attribution rule
# ----------------------------------------------------------------------

@pytest.mark.parametrize("tf_op,stage", [
    pytest.param("jit(step)/jit(similarity_stats)/pallas_call:", None,
                 id="no-stage"),
    pytest.param("jit(f)/while/body/flatten/while/body/closed_call/"
                 "vmap(client_sgd)/while/body/transpose(jvp(fwd))/mul:",
                 "client_sgd", id="wrapped-inside-flatten"),
    pytest.param("jit(f)/flatten/while/body/dynamic_update_slice:",
                 "flatten", id="flatten"),
    pytest.param("jit(f)/step5_fold/jit(diversefl_step45)/step4_filter/"
                 "pallas_call:", "step4_filter", id="step4-inside-step5"),
    pytest.param("jit(f)/step5_fold/jit(diversefl_step45)/step5_fold/"
                 "pallas_call:", "step5_fold", id="step5"),
    pytest.param("transpose(jvp(guide_sgd))/dot_general", "guide_sgd",
                 id="transposed"),
    pytest.param("jit(f)/attack_x/select_n:", None, id="near-name"),
    pytest.param("", None, id="empty"),
])
def test_innermost_stage(tf_op, stage):
    assert scopes.scope_of(tf_op) == stage


def test_stage_names_are_the_programs():
    run.program_on_path()
    from repro.fl import telemetry
    assert scopes.SCOPES == telemetry.SCOPES
    assert set(STAGE_METRICS.values()) == set(scopes.SCOPES)


# ----------------------------------------------------------------------
# the unscoped probe: what the parent commit's program gives
# ----------------------------------------------------------------------

def test_reader_matches_the_profiler_reduction():
    scoped, plain = scopes.load(PROBE), tr.load(PROBE)
    assert scopes.same_ops(scoped, plain)
    assert [o.name for o in scoped.devices["/device:TPU:0"]] == \
        [o.name for o in plain.devices["/device:TPU:0"]]
    assert scoped.spans == plain.spans


def test_reader_gives_each_ops_tf_op():
    ops = scopes.load(PROBE).devices["/device:TPU:0"]
    tf = {tr.op_name(o.name).split()[0]: o.tf_op for o in ops}
    assert tf["similarity_stats.1"] == \
        "jit(step)/jit(similarity_stats)/pallas_call:"
    assert tf["masked_agg_update.1"] == \
        "jit(step)/jit(masked_agg_update)/pallas_call:"
    assert all(o.scope is None for o in ops)


def test_accepted_metrics_read_as_before():
    """The values the parent commit's readers give on this probe."""
    ctx = context(PROBE, 3)
    want = {"device_idle_share": 83.76115732546121,
            "mfu": 52.00074388580347,
            "similarity_roofline": 88.52148768929625,
            "fold_roofline": 96.09767868022368}
    for name, value in want.items():
        assert spec.metric_module(name).read(ctx) == pytest.approx(
            value, rel=1e-12), name


def test_stage_metrics_read_nothing_without_scopes():
    ctx = context(PROBE, 3)
    for name in STAGE_METRICS:
        assert spec.metric_module(name).read(ctx) is None


# ----------------------------------------------------------------------
# the scoped probe
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def scoped():
    return scopes.load(SCOPED)


def test_scoped_probe_keeps_the_programs_spans(scoped):
    names = [n for n, _, _ in scoped.spans]
    assert names == ["window"] + ["dispatch", "fl.prepare", "fl.launch",
                                  "sync"] * 3
    # the program's spans sit inside the harness's dispatch
    for i in range(3):
        (_, d0, d1), (_, p0, p1), (_, l0, l1) = scoped.spans[1 + 4 * i:
                                                            4 + 4 * i]
        assert d0 <= p0 < p1 <= l0 < l1 <= d1


def test_idle_gaps_inside_dispatch_are_the_programs(scoped):
    ops = scoped.devices["/device:TPU:0"]
    lo, hi = scoped.window()
    labels = {tr.label(scoped.spans, (s + e) / 2)
              for s, e in tr.gaps(ops, lo, hi)}
    assert {"fl.prepare", "fl.launch", "sync"} <= labels
    assert "dispatch" not in labels


def test_kernels_are_charged_to_their_stages(scoped):
    kernels = {tr.op_name(o.name).split()[0]: o for o in
               scoped.devices["/device:TPU:0"]
               if tr.custom_call(o.name) is not None}
    sim, fold = kernels["similarity.9"], kernels["masked_agg.9"]
    # the fused Step 4+5 sits in aggregate's step5_fold; its similarity
    # half is Step 4 all the same
    assert "/step5_fold/" in sim.tf_op and sim.scope == "step4_filter"
    assert fold.scope == "step5_fold"


def test_stage_split_covers_the_busy_time(scoped):
    ops = scoped.devices["/device:TPU:0"]
    lo, hi = scoped.window()
    by = scopes.split(scoped, lo, hi)
    assert set(by) == set(scopes.SCOPES) | {None}
    assert sum(by.values()) == pytest.approx(tr.busy_ns(ops, lo, hi) * 1e-9)
    assert by["step4_filter"] == pytest.approx(5.028e-06)
    assert by["step5_fold"] == pytest.approx(4.267e-06)


def test_stage_metrics_on_the_scoped_probe():
    ctx = context(SCOPED, 6, n_params=1290)
    want = {"client_sgd_ms": 2.2683e-05, "guide_sgd_ms": 5.1861e-05,
            "attack_ms": 8.21e-07, "flatten_ms": 2.7811e-05,
            "filter_ms": 5.028e-06, "fold_ms": 4.267e-06,
            "eval_ms": 4.339e-06}
    for name, secs in want.items():
        assert scopes.scope_seconds(ctx, STAGE_METRICS[name]) == \
            pytest.approx(secs, rel=1e-9)
        assert spec.metric_module(name).read(ctx) == pytest.approx(
            1e3 * secs / 6, rel=1e-9), name
    # the fold and similarity kernels sit inside their stages' time
    fold = spec.metric_module("fold_roofline")
    sim = spec.metric_module("similarity_roofline")
    assert 0 < ctx.kernel_seconds(lambda s: fold._is_kernel(s, 1290)) \
        <= scopes.scope_seconds(ctx, "step5_fold")
    assert 0 < ctx.kernel_seconds(lambda s: sim._is_kernel(s, 1290)) \
        <= scopes.scope_seconds(ctx, "step4_filter")


# ----------------------------------------------------------------------
# ops XLA made without metadata, charged through the HLO graph
# ----------------------------------------------------------------------

def _int(f, x):
    return varint(f << 3) + varint(x)


def _instr(iid, name, opcode, op_name="", operands=(), calls=()):
    b = field(1, name.encode()) + field(2, opcode.encode()) + _int(35, iid)
    if op_name:
        b += field(7, field(2, op_name.encode()))
    b += field(36, b"".join(varint(o) for o in operands)) if operands else b""
    return b + b"".join(_int(38, c) for c in calls)


def _hlo(comps):
    mod = b"".join(field(3, _int(5, cid) + b"".join(field(2, i) for i in ins))
                   for cid, ins in comps)
    return memoryview(field(1, mod))


def test_graph_charges_ops_xla_made():
    """The shape of the dense round's flatten on the chip: a client
    update is padded and laid out anew for the (n, D) reshape, and the
    concatenate becomes dynamic-update-slices into the (n, D) buffer;
    none of these ops has metadata.  A loop fusion without metadata
    holds ops of the client SGD."""
    sgd = "jit(f)/while/body/flatten/vmap(client_sgd)/dot_general"
    ins = [
        _instr(1, "fusion.1", "fusion", sgd),
        _instr(2, "pad.1", "pad", "", [1]),
        _instr(3, "copy.1", "copy", "", [2]),
        _instr(4, "bitcast.1", "bitcast", "jit(f)/flatten/reshape", [3]),
        _instr(5, "broadcast.1", "broadcast", ""),
        _instr(6, "dynamic-update-slice.1", "dynamic-update-slice", "",
               [5, 4]),
        _instr(7, "copy.2", "copy", "", [1]),
        _instr(8, "bitcast.2", "bitcast", "", [7]),
        _instr(9, "dynamic-update-slice.2", "dynamic-update-slice", "",
               [6, 8]),
        _instr(10, "negate_select_fusion", "fusion",
               "jit(f)/attack/select_n", [9]),
        _instr(11, "fusion.2", "fusion", "", [1], calls=[2]),
        _instr(12, "rng.1", "rng-bit-generator", "jit(f)/threefry"),
        _instr(13, "copy.3", "copy", "", [12]),
    ]
    fused = [_instr(20, "mul.1", "multiply", sgd),
             _instr(21, "convert.1", "convert", "")]
    instrs, comps = scopes.hlo_graph(_hlo([(1, ins), (2, fused)]))
    assert instrs[6].operands == (5, 4) and instrs[11].calls == (2,)
    got = scopes.graph_stages(instrs, comps)
    assert got["dynamic-update-slice.1"] == "flatten"     # its update
    assert got["pad.1"] == got["copy.1"] == "flatten"     # their reader
    # an update laid out without metadata: the buffer it writes into
    assert got["dynamic-update-slice.2"] == "flatten"
    assert got["copy.2"] == got["bitcast.2"] == "flatten"
    assert got["fusion.2"] == "client_sgd"                # fused ops
    assert got["copy.3"] is None and got["convert.1"] is None
    assert "rng.1" not in got and "fusion.1" not in got


def _plane(name, lines=b"", meta=(), stat_names=()):
    b = field(2, name.encode()) + lines
    for key, value in meta:
        b += field(4, _int(1, key) + field(2, value))
    for key, sname in stat_names:
        b += field(5, _int(1, key) + field(2, _int(1, key)
                                         + field(2, sname.encode())))
    return field(1, b)


def test_load_charges_an_op_without_metadata(tmp_path):
    """A trace whose one device op has no stage on its path (XLA made
    it) and whose metadata plane holds the program's HLO."""
    hlo = bytes(_hlo([(1, [
        _instr(1, "bitcast.1", "bitcast", "jit(f)/flatten/reshape"),
        _instr(2, "broadcast.1", "broadcast"),
        _instr(3, "dynamic-update-slice.1", "dynamic-update-slice", "",
               [2, 1])])]))
    op = (_int(1, 1) + field(2, b"%dynamic-update-slice.1 = f32[2,4]{1,0} "
                            b"dynamic-update-slice(f32[2,4]{1,0} %b)")
          + field(5, _int(1, 10) + field(5, b"jit(f)/while:"))
          + field(5, _int(1, 11) + _int(4, 77)))
    device = _plane(
        "/device:TPU:0",
        field(3, field(2, b"XLA Ops") + _int(3, 1000)
             + field(4, _int(1, 1) + _int(2, 0) + _int(3, 5000))),
        meta=[(1, op)], stat_names=[(10, "tf_op"), (11, "program_id")])
    metadata = _plane(
        scopes.METADATA_PLANE,
        meta=[(77, _int(1, 77) + field(2, b"jit_f(77)")
               + field(5, _int(1, 12) + field(6, hlo)))],
        stat_names=[(12, scopes.HLO_STAT)])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device + metadata)
    (o,) = scopes.load(path).devices["/device:TPU:0"]
    assert (o.start, o.dur, o.tf_op) == (1000.0, 5.0, "jit(f)/while:")
    assert o.scope == "flatten"


# ----------------------------------------------------------------------
# finding the run's trace file
# ----------------------------------------------------------------------

def test_finds_the_runs_file_among_the_harness_dirs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes.tempfile, "tempdir", str(tmp_path))
    mine = tmp_path / "bench-trace-a" / "plugins" / "profile" / "t"
    other = tmp_path / "bench-trace-b" / "plugins" / "profile" / "t"
    mine.mkdir(parents=True)
    other.mkdir(parents=True)
    shutil.copy(SCOPED, mine / "h.xplane.pb")
    shutil.copy(PROBE, other / "h.xplane.pb")      # newer, other ops
    (tmp_path / "bench-trace-c").mkdir()
    (tmp_path / "bench-trace-c" / "h.xplane.pb").write_bytes(b"\xff\xff")
    ctx = context(mine / "h.xplane.pb", 6, trace_path=False)
    found = scopes.for_context(ctx)
    assert found is not None and scopes.same_ops(found, ctx.trace)
    assert any(o.scope for o in found.devices["/device:TPU:0"])
    assert ctx.scoped_trace is found                 # read once per run


def test_harness_run_reads_the_stage_metrics_without_raising(monkeypatch):
    """A whole traced run on the CPU: no device plane, so every stage
    metric reads nothing, and the result line leaves it out."""
    import repro.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    per_layer = [{"name": n, "unit": "ms"} for n in STAGE_METRICS]
    out = run.run_cell("tiny", tiny.conf(), tiny.traffic(), tiny.cfgmod(),
                       11, 0.2, True, jax.devices(), PEAKS,
                       per_layer=per_layer,
                       end_to_end=[{"name": "rounds_per_s"}])
    assert out["metrics"] == {}
    assert out["attempted"] > 0 and out["failed"] == 0
