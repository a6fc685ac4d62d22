"""The trace reducer, on hand-made intervals and on a small trace that a
TPU v5e recorded: three calls of one program holding the Pallas
similarity kernel and the Pallas fold on (23, 4,194,304) float32
inputs, with the harness's ``dispatch``/``sync`` spans."""
from pathlib import Path

import pytest

from bench import spec
from bench import trace as T

PROBE = Path(__file__).parent / "data" / "probe.xplane.pb"
D = 4_194_304


def ops(*iv):
    return [T.Op("x", s, e - s) for s, e in iv]


def test_union_merges_overlaps_and_clips():
    o = ops((0, 10), (5, 20), (30, 40), (35, 38), (50, 70))
    assert T.merged(o, 0, 100) == [(0, 20), (30, 40), (50, 70)]
    assert T.busy_ns(o, 0, 100) == 50
    assert T.busy_ns(o, 8, 60) == 12 + 10 + 10
    assert T.gaps(o, 0, 100) == [(20, 30), (40, 50), (70, 100)]
    assert T.gaps(o, 8, 60) == [(20, 30), (40, 50)]


def test_label_is_the_innermost_harness_span():
    spans = [("window", 0, 100), ("dispatch", 10, 20), ("sync", 20, 90)]
    assert T.label(spans, 15) == "dispatch"
    assert T.label(spans, 50) == "sync"
    assert T.label(spans, 95) == "host"


@pytest.fixture(scope="module")
def probe():
    return T.load(PROBE)


def test_probe_planes_and_spans(probe):
    assert list(probe.devices) == ["/device:TPU:0"]
    assert len(probe.devices["/device:TPU:0"]) == 21
    assert [n for n, _, _ in probe.spans] == ["dispatch", "sync"] * 3


def test_probe_busy_and_idle(probe):
    o = probe.devices["/device:TPU:0"]
    lo, hi = probe.window()
    busy = T.busy_ns(o, lo, hi)
    # the ops of one chip's "XLA Ops" line run one at a time: their
    # union is the plain sum of their durations
    assert busy == pytest.approx(4_944_301.0)
    assert busy == pytest.approx(sum(x.dur for x in o))
    idle = sum(e - s for s, e in T.gaps(o, lo, hi))
    assert busy + idle == pytest.approx(hi - lo)
    assert 0.8 < idle / (hi - lo) < 0.9


def test_probe_kernel_sums(probe):
    o = probe.devices["/device:TPU:0"]
    sigs = [(x, T.custom_call(x.name)) for x in o]
    kernels = [(x, s) for x, s in sigs if s is not None]
    assert len(kernels) == 6
    sim = spec.metric_module("similarity_roofline")
    fold = spec.metric_module("fold_roofline")
    assert sum(x.dur for x, s in kernels if sim._is_kernel(s, D)) == \
        pytest.approx(3_193_496.0)
    assert sum(x.dur for x, s in kernels if fold._is_kernel(s, D)) == \
        pytest.approx(1_598_764.0)
    assert not any(sim._is_kernel(s, D) and fold._is_kernel(s, D)
                   for _, s in kernels)


def test_op_names(probe):
    names = {T.op_name(x.name) for x in probe.devices["/device:TPU:0"]}
    assert "similarity_stats.1 f32[23,128] custom-call" in names
    assert "fusion.3 f32[23,1] fusion" in names
    assert "copy-start copy-start" in names
