"""Device-phase tracing, histogram metrics, and /metrics exposition tests.

Covers the observability layer end to end: hierarchical span trees with
deterministic ordering, the compile/execute/transfer attribution on
family-dispatch spans, the previously-dead server timers, MetricsRegistry
edge cases, and the Prometheus /metrics + slow-query /debug/queries REST
routes.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.engine.scheduler import PriorityQueryScheduler, QueryScheduler
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.metrics import (
    SERVER_METRICS,
    MetricsRegistry,
    ServerTimer,
    render_prometheus,
)
from pinot_tpu.spi.trace import TRACING, Trace, phase_breakdown

# -- span tree / ordering ----------------------------------------------------


def test_trace_to_json_sorted_by_start_ms():
    """Satellite: combine workers append from multiple threads, so raw
    record order is interleave-dependent — to_json must sort by startMs."""
    tr = Trace("t")
    base = tr._t0
    tr.record("late", base + 0.010, base + 0.011)
    tr.record("early", base + 0.001, base + 0.002)
    tr.record("mid", base + 0.005, base + 0.006)
    assert [s["operator"] for s in tr.to_json()] == ["early", "mid", "late"]


def test_trace_to_json_ties_break_by_record_order():
    tr = Trace("t")
    base = tr._t0
    tr.record("first", base + 0.001, base + 0.002)
    tr.record("second", base + 0.001, base + 0.003)
    tr.record("third", base + 0.001, base + 0.004)
    assert [s["operator"] for s in tr.to_json()] == \
        ["first", "second", "third"]


def test_trace_ordering_deterministic_across_adopting_threads():
    tr = TRACING.start_trace("t")
    TRACING.end_trace()
    base = tr._t0
    # two workers adopt the trace and record with interleaved start times
    offsets = {0: [0.002, 0.006, 0.010], 1: [0.004, 0.008, 0.012]}

    def worker(wid):
        TRACING.adopt(tr)
        try:
            for off in offsets[wid]:
                tr.record(f"s{off:.3f}", base + off, base + off + 0.001)
        finally:
            TRACING.adopt(None)

    threads = [threading.Thread(target=worker, args=(w,)) for w in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    starts = [s["startMs"] for s in tr.to_json()]
    assert starts == sorted(starts)
    assert [s["operator"] for s in tr.to_json()] == \
        [f"s{o:.3f}" for o in sorted(offsets[0] + offsets[1])]


def test_span_hierarchy_and_attributes():
    TRACING.start_trace("q")
    with TRACING.scope("outer") as outer:
        outer.set_attribute("k", 1)
        with TRACING.scope("inner") as inner:
            inner.set_attribute("deep", True)
    tr = TRACING.end_trace()
    spans = {s["operator"]: s for s in tr.to_json()}
    assert spans["inner"]["parentId"] == spans["outer"]["spanId"]
    # a root span carries the trace's query id
    assert spans["outer"]["attributes"] == {"k": 1, "queryId": "q"}
    assert spans["inner"]["attributes"] == {"deep": True}
    # startNs: the epoch clock, ordered like startMs
    assert spans["outer"]["startNs"] <= spans["inner"]["startNs"]
    assert abs(spans["outer"]["startNs"] - time.time_ns()) < 60e9
    tree = tr.to_tree()
    assert len(tree) == 1 and tree[0]["operator"] == "outer"
    assert tree[0]["children"][0]["operator"] == "inner"


def test_adopt_with_parent_nests_worker_spans():
    TRACING.start_trace("q")
    with TRACING.scope("parent") as parent:
        # thread-locals don't propagate: hand the worker trace + span
        caller_trace = TRACING.active_trace()

        def worker():
            TRACING.adopt(caller_trace, parent)
            try:
                with TRACING.scope("child"):
                    pass
            finally:
                TRACING.adopt(None)

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    tr = TRACING.end_trace()
    spans = {s["operator"]: s for s in tr.to_json()}
    assert spans["child"]["parentId"] == spans["parent"]["spanId"]


def test_scope_off_yields_none_and_records_nothing():
    assert TRACING.active_trace() is None
    with TRACING.scope("noop") as span:
        assert span is None


def test_phase_breakdown_rollup():
    trace_json = [
        {"operator": "family_dispatch", "startMs": 0, "durationMs": 10,
         "attributes": {"compileMs": 6.0, "transferBytes": 100}},
        {"operator": "DEVICE_FETCH", "startMs": 10, "durationMs": 2.0,
         "attributes": {"hostFetches": 1}},
        {"operator": "family_dispatch", "startMs": 12, "durationMs": 1,
         "attributes": {"compileMs": 0.0, "transferBytes": 50}},
        {"operator": "DEVICE_FETCH", "startMs": 13, "durationMs": 1.5,
         "attributes": {"hostFetches": 1}},
        {"operator": "SERVER_COMBINE", "startMs": 15, "durationMs": 4.0},
        {"operator": "BROKER_REDUCE", "startMs": 20, "durationMs": 1.0},
    ]
    out = phase_breakdown(trace_json)
    assert out == {"compileMs": 6.0, "deviceWaitMs": 3.5,
                   "hostCombineMs": 5.0, "transferBytes": 150}


# -- device-path acceptance: 16-segment batched GROUP BY ---------------------


@pytest.fixture(scope="module")
def batched_engine(tmp_path_factory):
    d = tmp_path_factory.mktemp("obs16")
    # unique column names → a fresh Program → a compile-guard miss on the
    # first dispatch even when other tests compiled similar shapes
    schema = Schema.build("obs16", dimensions=[("obk16", "INT")],
                          metrics=[("obv16", "INT")])
    rng = np.random.default_rng(11)
    segs = []
    for i in range(16):
        cols = {"obk16": rng.integers(0, 50, 4000).astype(np.int32),
                "obv16": rng.integers(0, 100, 4000).astype(np.int32)}
        SegmentBuilder(schema, segment_name=f"ob16_{i}").build(
            cols, d / f"s{i}")
        segs.append(load_segment(d / f"s{i}"))
    qe = QueryExecutor()
    qe.add_table(schema, segs)
    return qe


def test_batched_family_dispatch_span_attributes(batched_engine):
    sql = "SET trace = true; SELECT obk16, SUM(obv16) FROM obs16 GROUP BY obk16"
    r = batched_engine.execute_sql(sql)
    assert not r.exceptions, r.exceptions
    fam = [s for s in r.trace_info if s["operator"] == "family_dispatch"]
    # 16 equal-bucket segments → ONE batched family dispatch
    assert len(fam) == 1
    attrs = fam[0]["attributes"]
    assert attrs["numSegments"] == 16
    # compile/execute/transfer attribution, first dispatch compiles
    assert attrs["compileMs"] > 0
    assert attrs["program"] == "gby_by0_sum_d1"
    assert attrs["transferBytes"] > 0
    # the wait for the device is its own span, where the untraced path
    # waits too: one fetch for the whole family
    fetch = [s for s in r.trace_info if s["operator"] == "DEVICE_FETCH"]
    assert len(fetch) == 1
    assert fetch[0]["attributes"]["hostFetches"] == 1
    assert fetch[0]["attributes"]["fetchBytes"] > 0
    assert fetch[0]["startMs"] >= fam[0]["startMs"] + fam[0]["durationMs"]
    assert "obk16:ids" in attrs["transfers"]
    # the cache's residency is the /metrics gauges' and /debug's, not a
    # dispatch's: no HBM snapshot rides on the span
    assert not any(k.startswith("hbm") for k in attrs)
    # family-dispatch spans nest under the plan-execution phase
    by_id = {s["spanId"]: s for s in r.trace_info}
    assert by_id[fam[0]["parentId"]]["operator"] == "QUERY_PLAN_EXECUTION"
    # repeat dispatch of the same family: compile = 0, planes cached (a
    # traced repeat would be answered by the segment cache like any other,
    # so a test that wants device work says so)
    r2 = batched_engine.execute_sql("SET segmentCache = false; " + sql)
    fam2 = [s for s in r2.trace_info if s["operator"] == "family_dispatch"]
    assert len(fam2) == 1
    assert fam2[0]["attributes"]["compileMs"] == 0.0
    assert fam2[0]["attributes"]["transferBytes"] == 0
    assert fam2[0]["attributes"]["stackHits"] > 0


def test_trace_span_ids_unique_and_sorted(batched_engine):
    r = batched_engine.execute_sql(
        "SET trace = true; SELECT COUNT(*) FROM obs16")
    assert not r.exceptions
    ids = [s["spanId"] for s in r.trace_info]
    assert len(ids) == len(set(ids))
    starts = [s["startMs"] for s in r.trace_info]
    assert starts == sorted(starts)


# -- dead timers wired (satellite) -------------------------------------------


def test_query_processing_timer_recorded(batched_engine):
    before = SERVER_METRICS.timer_stats(
        ServerTimer.QUERY_PROCESSING_TIME_MS)[0]
    r = batched_engine.execute_sql("SELECT COUNT(*) FROM obs16")
    assert not r.exceptions
    n, total = SERVER_METRICS.timer_stats(
        ServerTimer.QUERY_PROCESSING_TIME_MS)
    assert n == before + 1
    assert total > 0


def test_scheduler_wait_timer_recorded():
    before = SERVER_METRICS.timer_stats(ServerTimer.SCHEDULER_WAIT_MS)[0]
    sched = QueryScheduler(max_concurrent=1)
    sched.submit(lambda tracker: None)
    assert SERVER_METRICS.timer_stats(
        ServerTimer.SCHEDULER_WAIT_MS)[0] == before + 1
    psched = PriorityQueryScheduler(max_concurrent=1)
    psched.submit(lambda tracker: None)
    assert SERVER_METRICS.timer_stats(
        ServerTimer.SCHEDULER_WAIT_MS)[0] == before + 2


def test_processing_timer_has_quantiles_in_snapshot(batched_engine):
    batched_engine.execute_sql("SELECT COUNT(*) FROM obs16")
    snap = SERVER_METRICS.snapshot()
    t = snap["timers"][ServerTimer.QUERY_PROCESSING_TIME_MS]
    assert t["count"] >= 1
    assert t["p50Ms"] > 0 and t["p95Ms"] >= t["p50Ms"] \
        and t["p99Ms"] >= t["p95Ms"]


# -- MetricsRegistry edge cases (satellite) ----------------------------------


def test_snapshot_skips_raising_gauge():
    reg = MetricsRegistry()
    reg.set_gauge("good", lambda: 42.0)

    def bad():
        raise RuntimeError("supplier died")

    reg.set_gauge("bad", bad)
    reg.add_meter("m", 3)
    snap = reg.snapshot()
    assert snap["gauges"]["good"] == 42.0
    assert "bad" not in snap["gauges"]
    assert snap["meters"]["m"] == 3


def test_snapshot_evaluates_slow_gauge_outside_lock():
    reg = MetricsRegistry()
    entered = threading.Event()
    release = threading.Event()

    def slow():
        entered.set()
        release.wait(10)
        return 1.0

    reg.set_gauge("slow", slow)
    snap_holder = {}
    t = threading.Thread(
        target=lambda: snap_holder.update(snap=reg.snapshot()))
    t.start()
    assert entered.wait(5)
    # supplier is blocked mid-snapshot — the registry lock must be free
    t0 = time.perf_counter()
    reg.add_meter("during", 1)
    reg.update_timer("t", 5.0)
    assert (time.perf_counter() - t0) < 1.0
    release.set()
    t.join(10)
    assert snap_holder["snap"]["gauges"]["slow"] == 1.0


def test_remove_gauge_with_supplier_keeps_replacement():
    reg = MetricsRegistry()
    old = lambda: 1.0  # noqa: E731
    new = lambda: 2.0  # noqa: E731
    reg.set_gauge("g", old)
    reg.set_gauge("g", new)  # replacement registered
    reg.remove_gauge("g", old)  # old component's shutdown
    assert reg.gauge_value("g") == 2.0
    reg.remove_gauge("g", new)
    assert reg.gauge_value("g") is None


def test_concurrent_add_meter_and_update_timer():
    reg = MetricsRegistry()
    n_threads, n_iter = 8, 500

    def work():
        for _ in range(n_iter):
            reg.add_meter("m")
            reg.update_timer("t", 1.0)
            reg.add_table_meter("tbl", "m")

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iter
    assert reg.meter_count("m") == total
    assert reg.table_meter_count("tbl", "m") == total
    n, total_ms = reg.timer_stats("t")
    assert n == total and total_ms == pytest.approx(total)


def test_timer_histogram_quantiles():
    reg = MetricsRegistry()
    for v in range(1, 101):  # 1..100 ms
        reg.update_timer("lat", float(v))
    snap = reg.snapshot()["timers"]["lat"]
    assert snap["count"] == 100
    assert snap["minMs"] == 1.0 and snap["maxMs"] == 100.0
    # log-bucketed estimate: within one bucket (~19%) of the true quantile
    assert 40 <= snap["p50Ms"] <= 64
    assert 80 <= snap["p95Ms"] <= 100
    assert 90 <= snap["p99Ms"] <= 100


def test_table_meters_in_snapshot_and_prometheus():
    reg = MetricsRegistry()
    reg.add_table_meter("orders", "queries", 5)
    reg.add_table_meter("users", "queries", 2)
    snap = reg.snapshot()
    assert snap["tableMeters"]["queries.orders"] == 5
    text = render_prometheus(reg, role="server")
    assert 'pinot_queries_total{role="server",table="orders"} 5' in text
    assert 'pinot_queries_total{role="server",table="users"} 2' in text


def test_render_prometheus_summary_quantiles():
    reg = MetricsRegistry()
    reg.add_meter("queries", 7)
    reg.set_gauge("documentCount", lambda: 123.0)
    for v in (5.0, 10.0, 20.0):
        reg.update_timer("queryProcessingTimeMs", v)
    text = render_prometheus(reg, role="broker")
    assert '# TYPE pinot_queries_total counter' in text
    assert 'pinot_queries_total{role="broker"} 7' in text
    assert 'pinot_documentCount{role="broker"} 123.0' in text
    assert '# TYPE pinot_queryProcessingTimeMs summary' in text
    assert 'pinot_queryProcessingTimeMs{role="broker",quantile="0.95"}' \
        in text
    assert 'pinot_queryProcessingTimeMs_count{role="broker"} 3' in text


# -- REST exposition ---------------------------------------------------------


SCHEMA = Schema.build(
    "obsweb", dimensions=[("path", "STRING")], metrics=[("hits", "INT")])


@pytest.fixture()
def cluster_stack(tmp_path):
    from pinot_tpu.cluster import (
        Broker,
        ClusterController,
        PropertyStore,
        ServerInstance,
    )
    from pinot_tpu.cluster.rest import (
        BrokerRestServer,
        ControllerRestServer,
        ServerRestServer,
    )

    store = PropertyStore()
    controller = ClusterController(store)
    server = ServerInstance(store, "Server_Obs", backend="host")
    server.start()
    broker = Broker(store)
    controller.add_schema(SCHEMA.to_json())
    table = controller.create_table({"tableName": "obsweb", "replication": 1})
    cols = {"path": np.asarray(["/a", "/b", "/a", "/c"], dtype=object),
            "hits": np.asarray([1, 2, 3, 4], dtype=np.int32)}
    SegmentBuilder(SCHEMA, segment_name="ow0").build(cols, tmp_path / "ow0")
    controller.add_segment(table, "ow0", {"location": str(tmp_path / "ow0"),
                                          "numDocs": 4})
    brest = BrokerRestServer(broker)
    crest = ControllerRestServer(controller)
    srest = ServerRestServer(server)
    yield brest, crest, srest, broker
    brest.close()
    crest.close()
    srest.close()
    server.stop()


def _get(url):
    with urllib.request.urlopen(url) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read().decode()


def _post_query(brest, sql):
    req = urllib.request.Request(
        brest.url + "/query/sql",
        data=json.dumps({"sql": sql}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_metrics_endpoint_live_broker(cluster_stack):
    brest, crest, srest, _broker = cluster_stack
    out = _post_query(
        brest, "SELECT path, SUM(hits) FROM obsweb GROUP BY path")
    assert not out.get("exceptions")
    st, ctype, text = _get(brest.url + "/metrics")
    assert st == 200
    assert ctype.startswith("text/plain")
    # acceptance: Prometheus text including a p95 for queryProcessingTimeMs
    assert 'pinot_queryProcessingTimeMs{role="broker",quantile="0.95"}' \
        in text
    assert 'pinot_queryProcessingTimeMs_count{role="broker"}' in text
    # controller + server roles expose their own registries
    st, ctype, _text = _get(crest.url + "/metrics")
    assert st == 200 and ctype.startswith("text/plain")
    st, _ctype, text = _get(srest.url + "/metrics")
    assert st == 200
    assert 'role="server"' in text


def test_slow_query_ring_buffer_via_debug_queries(cluster_stack):
    brest, _crest, _srest, broker = cluster_stack
    broker.query_logger.slow_threshold_ms = 0.0  # every query is "slow"
    out = _post_query(
        brest,
        "SET trace = true; SELECT path, SUM(hits) FROM obsweb GROUP BY path")
    assert not out.get("exceptions")
    st, _ctype, body = _get(brest.url + "/debug/queries")
    assert st == 200
    dq = json.loads(body)
    assert dq["slowThresholdMs"] == 0.0
    assert dq["slowQueries"], "slow ring should have captured the query"
    entry = dq["slowQueries"][0]
    assert "obsweb" in entry["sql"]
    assert entry["timeMs"] >= 0
    # traced queries carry the full phase breakdown
    assert "phases" in entry
    assert set(entry["phases"]) == {"compileMs", "deviceWaitMs",
                                    "hostCombineMs", "transferBytes"}
    # worst-first ordering
    times = [e["timeMs"] for e in dq["slowQueries"]]
    assert times == sorted(times, reverse=True)
