"""Unit tests for launch/hlo.py — the compiled-HLO collective census.

The dryrun harness and benchmarks/roofline.py both trust this parser
to turn compiled module text into collective byte counts; these tests
pin it against a hand-written HLO fixture (every dtype, tuple-result
async starts, metadata lines that must NOT match) so a regex regression
shows up here instead of as silently-wrong roofline numbers.
"""
import math

import pytest

from repro.launch import hlo


# ----------------------------------------------------------------------
# _shape_bytes: the full dtype table
# ----------------------------------------------------------------------

@pytest.mark.parametrize("dtype,nbytes", sorted(hlo.DTYPE_BYTES.items()))
def test_shape_bytes_dtype_table(dtype, nbytes):
    assert hlo._shape_bytes(dtype, "8,4") == 32 * nbytes


def test_shape_bytes_scalar():
    # "f32[]" — empty dims is one element, not zero
    assert hlo._shape_bytes("f32", "") == 4
    assert hlo._shape_bytes("pred", "") == 1


def test_shape_bytes_1d():
    assert hlo._shape_bytes("bf16", "1000") == 2000


# ----------------------------------------------------------------------
# collective_stats on a hand-written HLO fixture
# ----------------------------------------------------------------------

FIXTURE = """\
HloModule jit_step, entry_computation_layout={...}

ENTRY %main (p0: f32[128,256]) -> f32[128,256] {
  %p0 = f32[128,256] parameter(0)
  %ar = f32[128,256] all-reduce(%p0), replica_groups={}, to_apply=%add
  %ag.s = (bf16[64], bf16[256]) all-gather-start(%x), dimensions={0}
  %ag.d = bf16[256] all-gather-done(%ag.s)
  %rs = f32[32] reduce-scatter(%y), dimensions={0}, to_apply=%add
  %cp = u8[16] collective-permute(%z), source_target_pairs={{0,1}}
  ROOT %out = f32[128,256] add(%ar, %ar)
}
// a bare mention of all-reduce or all-gather in a comment is ignored
"""


def test_collective_stats_counts():
    stats = hlo.collective_stats(FIXTURE)
    assert stats["all-reduce"]["count"] == 1
    assert stats["all-gather"]["count"] == 1        # the -start form
    assert stats["reduce-scatter"]["count"] == 1
    assert stats["collective-permute"]["count"] == 1
    assert stats["all-to-all"]["count"] == 0


def test_collective_stats_result_bytes():
    stats = hlo.collective_stats(FIXTURE)
    assert stats["all-reduce"]["result_bytes"] == 128 * 256 * 4
    # tuple-result async start: both tuple elements sum
    assert stats["all-gather"]["result_bytes"] == (64 + 256) * 2
    assert stats["reduce-scatter"]["result_bytes"] == 32 * 4
    assert stats["collective-permute"]["result_bytes"] == 16


def test_collective_stats_moved_bytes_factors():
    stats = hlo.collective_stats(FIXTURE)
    # all-reduce counts twice (reduce + broadcast phases)
    assert stats["all-reduce"]["moved_bytes"] == \
        pytest.approx(2.0 * 128 * 256 * 4)
    assert stats["all-gather"]["moved_bytes"] == pytest.approx((64 + 256) * 2)


def test_collective_stats_done_lines_do_not_double_count():
    # the all-gather-done line must not add a second all-gather
    stats = hlo.collective_stats(FIXTURE)
    total = sum(v["count"] for v in stats.values())
    assert total == 4


def test_total_collective_bytes_sums_moved():
    stats = hlo.collective_stats(FIXTURE)
    assert hlo.total_collective_bytes(FIXTURE) == pytest.approx(
        sum(v["moved_bytes"] for v in stats.values()))
    expected = (2.0 * 128 * 256 * 4) + (64 + 256) * 2 + 32 * 4 + 16
    assert hlo.total_collective_bytes(FIXTURE) == pytest.approx(expected)


def test_empty_module_is_all_zero():
    stats = hlo.collective_stats("HloModule empty\n")
    assert all(v["count"] == 0 and v["moved_bytes"] == 0.0
               for v in stats.values())
    assert hlo.total_collective_bytes("") == 0.0


def test_op_census_counts_collectives_and_fusions():
    text = FIXTURE + "  %f = f32[8] fusion(%p0), kind=kLoop\n"
    census = hlo.op_census(text)
    assert census["all-reduce"] == 1
    assert census["all-gather"] == 1
    assert census["fusion"] == 1


def test_roofline_dominant_term():
    r = hlo.roofline_terms({"flops": 1e15, "bytes accessed": 1.0}, 1.0)
    assert r["dominant"] == "compute"
    r = hlo.roofline_terms({"flops": 1.0, "bytes accessed": 1e14}, 1.0)
    assert r["dominant"] == "memory"
    r = hlo.roofline_terms({}, 1e13)
    assert r["dominant"] == "collective"
    assert math.isfinite(r["t_collective"])
