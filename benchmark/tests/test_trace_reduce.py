"""The trace reduction against a small hand-built trace: overlapping ops,
a gap, two modules, two chips."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import trace_reduce  # noqa: E402

# (name, start_ns, duration_ns)
OPS = [
    ("fusion.1", 1_000, 4_000),      # 1,000 .. 5,000
    ("fusion.2", 3_000, 4_000),      # overlaps the first: busy to 7,000
    ("copy.3", 7_000, 1_000),        # touches: busy to 8,000
    ("fusion.1", 20_000, 5_000),     # after a gap of 12,000
    ("inner", 21_000, 1_000),        # nested inside the previous one
]
MODULES = [
    ("jit_run_program_batch", 1_000, 7_000),
    ("jit_pack_flat", 20_000, 5_000),
    ("jit_run_program_batch", 30_000, 2_000),
]


def test_union_counts_overlap_once_and_skips_gaps():
    assert trace_reduce.union_seconds(OPS) == pytest.approx(12_000 / 1e9)
    assert trace_reduce.union_seconds([]) == 0.0


def test_traced_seconds_run_from_the_first_operation_to_the_last():
    assert trace_reduce.traced_seconds(OPS) == pytest.approx(24_000 / 1e9)
    assert trace_reduce.traced_seconds([]) == 0.0


def test_seconds_by_name_sums_and_ranks():
    assert trace_reduce.seconds_by_name(MODULES) == [
        ["jit_run_program_batch", pytest.approx(9_000 / 1e9)],
        ["jit_pack_flat", pytest.approx(5_000 / 1e9)]]
    assert len(trace_reduce.seconds_by_name(OPS, top=2)) == 2


def test_reduce_averages_busy_time_over_chips():
    planes = {
        "/device:TPU:0": {"XLA Ops": OPS, "XLA Modules": MODULES},
        "/device:TPU:1": {"XLA Ops": OPS[:1], "XLA Modules": MODULES[:1]},
    }
    out = trace_reduce.reduce_planes(planes)
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx((12_000 + 4_000) / 2 / 1e9)
    assert out["traced_s"] == pytest.approx((24_000 + 4_000) / 2 / 1e9)
    assert out["device_modules"][0][0] == "jit_run_program_batch"


def test_a_trace_without_device_ops_is_an_error_not_a_zero():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes({})
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes({"/device:TPU:0": {"Steps": []}})
