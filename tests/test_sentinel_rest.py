"""End-to-end regression sentinel over a live broker REST surface.

The acceptance path for the continuous regression sentinel: a seeded
``device.dispatch`` delay fault slows a live cluster's dispatches; the
sentinel classifies the shift as ``latency-drift`` within its hysteresis
budget; the alert shows at GET /debug/alerts with at least one pinned
exemplar trace retrievable (chrome format included) by alert id; the
alert auto-clears once clean windows accumulate; and the persisted
ledger survives a WAL-store restart.

Companions: test_perf_ledger.py (unit), test_tracing_perf_guard.py
(warm-path zero-cost), soak.py --suite sentinel (the same loop
time-boxed for long runs).
"""

from __future__ import annotations

import json
import tempfile
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from pinot_tpu.cluster import (Broker, ClusterController, PropertyStore,
                               ServerInstance)
from pinot_tpu.cluster.sentinel import (SENTINEL_REPORT_PATH,
                                        PerfRegressionSentinel)
from pinot_tpu.engine.perf_ledger import (ALERTS, LEDGER_PATH, PERF_LEDGER,
                                          PerfLedger)
from pinot_tpu.spi import faults
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.spi.data_types import Schema

SCHEMA = Schema.build("sentab", dimensions=[("sk", "STRING")],
                      metrics=[("sv", "INT")])
# both caches off: a cached repeat performs zero device dispatches, so
# neither the delay fault nor the drift it should cause would exist
SQL = ("SET resultCache = false; SET segmentCache = false; "
       "SELECT sk, SUM(sv) FROM sentab GROUP BY sk")


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    d = tmp_path_factory.mktemp("sentinel_rest")
    PERF_LEDGER.clear()
    ALERTS.clear()
    store = PropertyStore(data_dir=str(d / "store"), fsync="off")
    controller = ClusterController(store)
    # backend="auto": the fault point sits on the device dispatch path
    server = ServerInstance(store, "Server_0", backend="auto")
    server.start()
    controller.add_schema(SCHEMA.to_json())
    controller.create_table({"tableName": "sentab", "replication": 1})
    rng = np.random.default_rng(19)
    for i in range(2):
        n = 200
        cols = {"sk": np.asarray(["a", "b", "c", "d"], dtype=object)[
                    rng.integers(0, 4, n)],
                "sv": rng.integers(0, 100, n).astype(np.int32)}
        name = f"sentab_{i}"
        SegmentBuilder(SCHEMA, segment_name=name).build(cols, d / name)
        controller.add_segment("sentab_OFFLINE", name,
                               {"location": str(d / name), "numDocs": n})
    broker = Broker(store)
    # the cold query (trace + compile, over the 1 s latency objective on a
    # loaded host) belongs to no window: the tests are about drift in a
    # warm plan
    warm = broker.execute_sql(SQL)
    assert not warm.exceptions, warm.exceptions
    PERF_LEDGER.clear()
    yield store, controller, server, broker, d
    faults.FAULTS.reset()
    PERF_LEDGER.clear()
    ALERTS.clear()
    server.stop()
    store.close()


def _burst(broker, n):
    for _ in range(n):
        resp = broker.execute_sql(SQL)
        assert not resp.exceptions, resp.exceptions


def _get(rs, path):
    with urllib.request.urlopen(rs.url + path) as r:
        return r.status, json.loads(r.read())


def test_sentinel_detects_pins_and_clears_over_rest(cluster):
    from pinot_tpu.cluster.rest import BrokerRestServer

    store, controller, _server, broker, _d = cluster
    _burst(broker, 8)
    PERF_LEDGER.rotate_now()
    # the drift floor stands between what six busy test workers do to a
    # 10 ms query (a few ms at the median) and the injected 100 ms a
    # dispatch: the alert fires on the fault and clears without it, whatever
    # else the machine is doing
    sentinel = PerfRegressionSentinel(store, controller, min_queries=3,
                                      breaches=2, clears=2, min_abs_ms=40.0)
    report = sentinel.evaluate()
    assert report["anomalies"] == [], report["anomalies"]

    rs = BrokerRestServer(broker)
    try:
        # ledger endpoint serves the baseline plan
        code, ledger = _get(rs, "/debug/ledger")
        assert code == 200 and ledger["numPlans"] >= 1
        assert ledger["plans"][0]["totals"]["queries"] >= 8

        # -- inject: every dispatch +100ms ------------------------------
        alert = None
        with faults.injected("device.dispatch", kind="delay",
                             delay_s=0.1, times=None):
            for _ in range(12):
                _burst(broker, 6)
                sentinel.evaluate()
                if ALERTS.active_count:
                    alert = ALERTS.active()[0]
                    break
            assert alert is not None, \
                "injected dispatch delay never raised an alert"
            assert alert["type"] == "latency-drift"
            # exemplar arming: next matching queries are force-traced
            _burst(broker, 4)

        code, alerts = _get(rs, "/debug/alerts")
        assert code == 200 and alerts["active"] >= 1
        assert any(a["id"] == alert["id"] for a in alerts["alerts"])

        code, rec = _get(rs, f"/debug/alerts/{alert['id']}")
        assert code == 200 and rec["type"] == "latency-drift"
        exemplars = rec.get("exemplarTraceIds") or []
        assert exemplars, "alert fired but pinned no exemplar traces"

        # the pinned exemplar is a real retained trace, chrome-exportable,
        # cross-linked back to its alert
        tid = exemplars[0]
        code, trace = _get(rs, f"/debug/traces/{tid}")
        assert code == 200 and alert["id"] in trace.get("alertIds", [])
        code, chrome = _get(rs, f"/debug/traces/{tid}?format=chrome")
        assert code == 200 and chrome["traceEvents"], \
            "exemplar must export as a chrome trace"

        # slow-log cross-link: entries during the incident name the alert
        slow = broker.query_logger.slow_queries()
        linked = [e for e in slow if alert["id"] in e.get("alertIds", [])]
        # (only present if any query crossed the slow threshold — the
        # 100ms delay is under the 500ms default, so don't require it;
        # active_ids_for is covered by unit tests)
        for e in linked:
            assert e["table"] == "sentab"

        try:
            _get(rs, "/debug/alerts/no-such-alert")
            assert False, "404 expected"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        faults.FAULTS.reset()
        rs.close()

    # -- recovery: clean rounds resolve the alert (it clears once the
    # short window's median is a clean query's again and two evaluations
    # have seen it so; the count of rounds only bounds a hang) -----------
    for _ in range(60):
        _burst(broker, 6)
        sentinel.evaluate()
        if not ALERTS.active_count:
            break
    assert ALERTS.active_count == 0, "alert never cleared after recovery"
    rec = ALERTS.get(alert["id"])
    assert rec["state"] == "cleared" and rec["clearReason"] == "recovered"

    # a full scrape pass lands the ledger and report in the store
    sentinel()
    assert store.get(LEDGER_PATH) is not None
    assert store.get(SENTINEL_REPORT_PATH) is not None


def test_ledger_survives_store_restart(cluster, tmp_path):
    """Persist into a durable WAL store, close it, reopen from disk: the
    reference windows come back."""
    assert len(PERF_LEDGER) >= 1, "e2e test must have populated the ledger"
    wal = PropertyStore(data_dir=str(tmp_path / "wal"), fsync="off")
    PERF_LEDGER.persist(wal)
    payload = wal.get(LEDGER_PATH)
    assert payload and payload["plans"], "persist wrote no plans"
    wal.close()
    reopened = PropertyStore(data_dir=str(tmp_path / "wal"), fsync="off")
    try:
        fresh = PerfLedger()
        assert fresh.restore(reopened) >= 1, \
            "restored zero plans after store restart"
        key = next(iter(payload["plans"]))
        _cur, _ref, w, table = fresh.plan_windows(key)
        assert w > 0 and table == "sentab"
    finally:
        reopened.close()
