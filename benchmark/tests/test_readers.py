"""Each per-layer reader on a hand-built run: two requests, one traced
slice, a reduction with known busy time."""

import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "readers")]
import run as harness  # noqa: E402
import traffic  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "ssb-flat-sf10-16seg.json")
                    .read_text())
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def record(cls, params, start, end, spans, n_rows, n_cols):
    r = harness.Record.__new__(harness.Record)
    r.cls, r.start, r.end = cls, start, end
    r.sql = WORKLOAD.render(cls, params)
    r.trace = [{"operator": k, "durationMs": v} for k, v in spans]
    r.dispatches, r.n_rows, r.n_cols = 1, n_rows, n_cols
    return r


WORKLOAD = traffic.Workload(traffic.load("traffic", "flight12-streams3"),
                            "lineorder16", 7)


@pytest.fixture()
def run():
    records = [
        # wholly inside the slice [10, 14]
        record("ssb_q1_1", {"Y": 1993, "D": 1, "D2": 3, "Q": 25, "L": 10},
               10.0, 10.1,
               [("BROKER_SCATTER", 90.0), ("BROKER_REDUCE", 1.0),
                ("QUERY_PROCESSING", 80.0), ("SCHEDULER_WAIT", 1.0),
                ("family_dispatch", 20.0), ("DEVICE_FETCH", 30.0),
                ("SERVER_COMBINE", 2.0)], 1, 1),
        # half inside: 13.9 .. 14.1
        record("ssb_q2_1", {"CAT": "MFGR#12", "R": "ASIA", "L": 1000},
               13.9, 14.1,
               [("BROKER_SCATTER", 150.0), ("BROKER_REDUCE", 30.0),
                ("QUERY_PROCESSING", 140.0),
                ("family_dispatch", 40.0), ("family_dispatch", 10.0),
                ("DEVICE_FETCH", 25.0), ("SERVER_COMBINE", 60.0)], 280, 3),
    ]
    # the device's tracer covered 3.0 s of the host's 4 s slice
    return types.SimpleNamespace(
        records=records, slice=(10.0, 14.0),
        trace={"busy_s": 0.03, "traced_s": 3.0}, config=CONFIG,
        total_rows=67_108_864, setup={"build_s": 20.5}, workload=WORKLOAD,
        peak=PEAK, t0=9.0, gc_pauses=[(0, 0.01), (2, 0.5)])


def read(name, run):
    spec = traffic.load("metrics", name)
    reader = harness.module_at(BENCH / "readers" / f"{spec['reader']}.py")
    return reader.read(run, spec["params"])


def test_span_metrics_are_means_of_sums_and_differences(run):
    assert read("broker_self_ms", run) == pytest.approx(
        ((100 - 90 - 1) + (200 - 150 - 30)) / 2)
    assert read("server_self_ms", run) == pytest.approx(
        ((80 - 1 - 20 - 30 - 2) + (140 - 50 - 25 - 60)) / 2)
    assert read("host_combine_ms", run) == pytest.approx((3 + 90) / 2)


def test_counts_classes_setup_and_gc(run):
    assert read("dispatches_per_query", run) == 1.0
    assert read("flight12.q1_ms", run) == pytest.approx(100.0)
    assert read("flight12.q2_ms", run) == pytest.approx(200.0)
    assert read("build_s", run) == 20.5
    assert read("first_touch_s", run) is None  # nothing to read: left out
    assert read("host_gc_pct", run) == pytest.approx(100 * 0.51 / 5.1)


def test_device_metrics_weigh_requests_by_their_share_of_the_slice(run):
    # idle over what the device's tracer covered, not over the host's slice
    assert read("device_idle_pct", run) == pytest.approx(99.0)
    assert read("device_ms_per_query", run) == pytest.approx(30.0 / 1.5)
    q1 = 67_108_864 * 10 + 8
    q2 = 67_108_864 * 9 + 280 * 3 * 8
    least = (q1 + 0.5 * q2) / 819e9
    assert read("scan_hbm_roofline", run) == pytest.approx(
        100 * least / 0.03)


def test_an_answer_from_a_cache_moves_no_bytes(run):
    run.records[1].dispatches = 0
    least = (67_108_864 * 10 + 8) / 819e9
    assert read("scan_hbm_roofline", run) == pytest.approx(
        100 * least / 0.03)


def test_without_a_trace_the_device_readers_return_nothing(run):
    run.trace = run.slice = None
    for name in ("device_idle_pct", "device_ms_per_query",
                 "scan_hbm_roofline"):
        assert read(name, run) is None
