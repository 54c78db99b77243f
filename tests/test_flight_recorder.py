"""Flight recorder: sampled trace retention, Perfetto export, compile
telemetry.

Covers the always-on observability loop end to end: deterministic head
sampling (broker and servers agree on a queryId hash, no option on the
wire), tail-based pinning of slow/partial/failed traces, the
byte-budgeted broker TraceStore behind GET /debug/traces, the Chrome
Trace Event export (schema-valid, matched B/E pairs, connected flows),
and the compile registry (cold compile counted once, warm dispatches
free of fingerprint work).
"""

from __future__ import annotations

import json
import tempfile
import urllib.request
from collections import defaultdict
from pathlib import Path

import numpy as np
import pytest

from pinot_tpu.cluster import (Broker, ClusterController, PropertyStore,
                               ServerInstance)
from pinot_tpu.cluster.tracestore import TraceStore
from pinot_tpu.engine.compile_registry import COMPILE_REGISTRY, CompileRegistry
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.trace import sample_decision, trace_sample_rate
from pinot_tpu.spi.traceexport import to_chrome_trace

SAMPLE_ENV = "PINOT_TPU_TRACE_SAMPLE"


# -- sampling decision --------------------------------------------------------


def test_sample_decision_deterministic():
    for qid in ("a1b2c3", "deadbeef0123", ""):
        assert sample_decision(qid, 0.5) == sample_decision(qid, 0.5)
    assert sample_decision("anything", 0.0) is False
    assert sample_decision("anything", 1.0) is True


def test_sample_decision_rate_is_roughly_honored():
    hits = sum(sample_decision(f"q{i:06d}", 0.3) for i in range(4000))
    assert 0.2 < hits / 4000 < 0.4


def test_shard_suffix_strips_to_same_decision():
    # the broker hashes the root id; servers receive "<id>:<n>" shard ids
    root = "0123456789ab"
    for n in range(4):
        shard = f"{root}:{n}"
        assert sample_decision(shard.split(":", 1)[0], 0.37) == \
            sample_decision(root, 0.37)


def test_trace_sample_rate_env(monkeypatch):
    monkeypatch.delenv(SAMPLE_ENV, raising=False)
    assert trace_sample_rate() == 0.0
    monkeypatch.setenv(SAMPLE_ENV, "0.25")
    assert trace_sample_rate() == 0.25
    monkeypatch.setenv(SAMPLE_ENV, "7")  # clamps
    assert trace_sample_rate() == 1.0
    monkeypatch.setenv(SAMPLE_ENV, "not-a-number")
    assert trace_sample_rate() == 0.0


# -- TraceStore ---------------------------------------------------------------


def _spans(n=3, pad=0):
    out = [{"operator": f"OP_{i}", "startMs": float(i), "durationMs": 1.0,
            "spanId": i} for i in range(n)]
    if pad:
        out[0]["attributes"] = {"pad": "x" * pad}
    return out


def test_tracestore_offer_get_summaries():
    ts = TraceStore(budget_bytes=1 << 20, max_traces=8)
    tid = ts.offer("q1", _spans(), reason="sampled", table="t",
                   time_ms=12.5)
    assert tid == "q1"
    ent = ts.get("q1")
    assert ent["reason"] == "sampled" and ent["numSpans"] == 3
    assert ent["timeMs"] == 12.5 and not ent["pinned"]
    summ = ts.summaries()
    assert len(summ) == 1 and "spans" not in summ[0]
    assert ts.get("nope") is None
    assert ts.stats()["traces"] == 1


def test_tracestore_same_id_replaces():
    ts = TraceStore(budget_bytes=1 << 20, max_traces=8)
    ts.offer("q1", _spans(2))
    ts.offer("q1", _spans(5))
    assert ts.stats()["traces"] == 1
    assert ts.get("q1")["numSpans"] == 5


def test_tracestore_evicts_unpinned_before_pinned():
    ts = TraceStore(budget_bytes=4000, max_traces=100)
    ts.offer("pinned1", _spans(pad=1000), reason="slow", pinned=True)
    ts.offer("sample1", _spans(pad=1000), reason="sampled")
    ts.offer("sample2", _spans(pad=1000), reason="sampled")
    # over budget: the healthy samples go first, oldest first
    ts.offer("sample3", _spans(pad=1000), reason="sampled")
    assert ts.get("pinned1") is not None, "pinned trace evicted first"
    assert ts.get("sample1") is None
    assert ts.stats()["evictions"] >= 1


def test_tracestore_count_cap_and_newest_survives():
    ts = TraceStore(budget_bytes=1 << 20, max_traces=2)
    ts.offer("a", _spans(), pinned=True)
    ts.offer("b", _spans(), pinned=True)
    ts.offer("c", _spans())  # newest must survive even under pressure
    assert ts.get("c") is not None
    assert ts.stats()["traces"] == 2


# -- CompileRegistry ----------------------------------------------------------


def test_compile_registry_cold_then_warm():
    reg = CompileRegistry(max_entries=16)
    reg.note_compile(("k1",), 12.0, "fp-1", {"mode": "GROUP_BY"})
    reg.note_dispatch(("k1",))
    reg.note_dispatch(("k1",))
    snap = reg.snapshot()
    assert snap["families"] == 1
    assert snap["totalCompiles"] == 1
    assert snap["totalDispatches"] == 3  # compile counts as a dispatch
    ent = snap["compiles"][0]
    assert ent["fingerprint"] == "fp-1"
    assert ent["compileMsTotal"] == 12.0 and ent["compileMsLast"] == 12.0


def test_compile_registry_unknown_key_dispatch_is_noop():
    reg = CompileRegistry(max_entries=16)
    reg.note_dispatch(("never-compiled",))
    assert reg.snapshot()["totalDispatches"] == 0


def test_compile_registry_ranks_by_compile_cost():
    reg = CompileRegistry(max_entries=16)
    reg.note_compile(("cheap",), 1.0, "fp-cheap", {})
    reg.note_compile(("dear",), 100.0, "fp-dear", {})
    assert [e["fingerprint"] for e in reg.snapshot()["compiles"]] == \
        ["fp-dear", "fp-cheap"]


def test_compile_registry_lru_eviction_purges_key_map():
    reg = CompileRegistry(max_entries=2)
    reg.note_compile(("a",), 1.0, "fp-a", {})
    reg.note_compile(("b",), 1.0, "fp-b", {})
    reg.note_compile(("c",), 1.0, "fp-c", {})
    snap = reg.snapshot()
    assert snap["families"] == 2
    assert "fp-a" not in {e["fingerprint"] for e in snap["compiles"]}
    reg.note_dispatch(("a",))  # stale key: silent no-op, no resurrection
    assert reg.snapshot()["families"] == 2


def test_unfingerprintable_family_still_counted():
    reg = CompileRegistry(max_entries=16)
    reg.note_compile(("k",), 5.0, None, {})
    snap = reg.snapshot()
    assert snap["totalCompiles"] == 1
    assert snap["compiles"][0]["fingerprint"].startswith("unfingerprintable:")


def test_compile_registry_ranking_decays_with_traffic(monkeypatch):
    """AOT-persist priority must track CURRENT traffic: a family whose
    dispatches all happened windows ago decays to bare compile cost,
    so a cheaper-but-hot family overtakes it in the ranking."""
    import pinot_tpu.engine.compile_registry as crmod
    clock = {"t": 1000.0}
    monkeypatch.setattr(crmod.time, "time", lambda: clock["t"])
    reg = CompileRegistry(max_entries=16)
    # expensive family, heavily dispatched... then traffic stops
    reg.note_compile(("old",), 100.0, "fp-old", {})
    for _ in range(50):
        reg.note_dispatch(("old",))
    # >2 windows later a cheap family starts taking steady traffic
    clock["t"] += 3 * crmod._RECENT_WINDOW_S
    reg.note_compile(("hot",), 10.0, "fp-hot", {})
    for _ in range(30):
        reg.note_dispatch(("hot",))
    pri = reg.aot_priority()
    assert [fp for fp, _, _ in pri] == ["fp-hot", "fp-old"], pri
    # the stale family's recency term is fully decayed: bare compile cost
    assert dict((fp, s) for fp, s, _ in pri)["fp-old"] == 100.0
    # snapshot ranks by the same decayed score and exposes it
    snap = reg.snapshot()
    assert snap["compiles"][0]["fingerprint"] == "fp-hot"
    assert snap["compiles"][0]["aotScore"] > 100.0
    # unfingerprintable families never make the AOT list
    reg.note_compile(("anon",), 999.0, None, {})
    assert all(not fp.startswith("unfingerprintable:")
               for fp, _, _ in reg.aot_priority())


# -- Chrome Trace Event export: schema + flow validators ----------------------


def _validate_chrome(ct):
    """Required keys, monotonic ts per lane, matched B/E pairs, flow
    s/f id pairing. Returns (duration_events, flow_events, processes)."""
    assert set(ct) >= {"traceEvents", "displayTimeUnit"}
    ev = ct["traceEvents"]
    json.dumps(ct)  # JSON-serializable end to end
    procs = {}
    stacks = defaultdict(list)
    last_ts = defaultdict(lambda: -1.0)
    flows = defaultdict(list)
    dur = []
    for e in ev:
        assert {"name", "ph", "pid"} <= set(e), e
        if e["ph"] == "M":
            if e["name"] == "process_name":
                procs[e["pid"]] = e["args"]["name"]
            continue
        assert "ts" in e and e["ts"] >= 0, e
        key = (e["pid"], e.get("tid", 0))
        if e["ph"] in ("B", "E"):
            dur.append(e)
            # emit order within a lane must be replayable: ts monotonic
            assert e["ts"] >= last_ts[key] - 1e-9, (e, last_ts[key])
            last_ts[key] = e["ts"]
            if e["ph"] == "B":
                stacks[key].append(e["name"])
            else:
                assert stacks[key], f"E without open B on lane {key}: {e}"
                assert stacks[key].pop() == e["name"], e
        elif e["ph"] in ("s", "f"):
            flows[e["id"]].append(e)
    assert all(not s for s in stacks.values()), (
        f"unbalanced B/E: {dict(stacks)}")
    for fid, pair in flows.items():
        # file order of s/f is irrelevant to the format; the binding is
        # by id, and the start must not be later than the finish
        assert sorted(p["ph"] for p in pair) == ["f", "s"], (fid, pair)
        start = next(p for p in pair if p["ph"] == "s")
        finish = next(p for p in pair if p["ph"] == "f")
        assert start["ts"] <= finish["ts"] + 1e-6, (fid, pair)
    return dur, flows, procs


def test_chrome_export_synthetic_two_process():
    spans = [
        {"operator": "BROKER_SCATTER", "startMs": 1.0, "durationMs": 10.0,
         "spanId": 1},
        {"operator": "BROKER_REDUCE", "startMs": 11.0, "durationMs": 2.0,
         "spanId": 2},
        {"operator": "SERVER_QUERY", "startMs": 0.0, "durationMs": 8.0,
         "spanId": "Server_0:1", "server": "Server_0"},
        {"operator": "segment:seg_0", "startMs": 1.0, "durationMs": 3.0,
         "spanId": "Server_0:2", "parentId": "Server_0:1"},
        # overlapping sibling: must land on its own lane, not corrupt B/E
        {"operator": "segment:seg_1", "startMs": 2.0, "durationMs": 3.0,
         "spanId": "Server_0:3", "parentId": "Server_0:1"},
    ]
    # every span says where it lies on the epoch clock: the server's trace
    # began 1.5 ms into the broker's, half a millisecond into the scatter
    base = 1_790_000_000_000_000_000
    for s in spans:
        shard = isinstance(s["spanId"], str)
        s["startNs"] = base + int((s["startMs"] + (1.5 if shard else 0.0))
                                  * 1e6)
    ct = to_chrome_trace(spans, query_id="qtest")
    dur, flows, procs = _validate_chrome(ct)
    assert ct["otherData"]["queryId"] == "qtest"
    # placed by startNs, not re-based onto the scatter span: ts 0 is the
    # request's earliest span (BROKER_SCATTER), the shard's root 500 us on
    root = next(e for e in dur if e["name"] == "SERVER_QUERY"
                and e["ph"] == "B")
    assert root["ts"] == pytest.approx(500.0)
    assert set(procs.values()) == {"broker", "Server_0"}
    assert len(dur) == 2 * len(spans)
    names = {f[0]["name"] for f in flows.values()}
    assert "scatter" in names and "gather" in names


def test_chrome_export_flows_connect_every_shard():
    spans = [
        {"operator": "BROKER_SCATTER", "startMs": 0.0, "durationMs": 5.0,
         "spanId": 1},
        {"operator": "SERVER_QUERY", "startMs": 0.0, "durationMs": 2.0,
         "spanId": "Server_0:1", "server": "Server_0"},
        {"operator": "SERVER_QUERY", "startMs": 0.0, "durationMs": 2.0,
         "spanId": "Server_1#1:1", "server": "Server_1"},
    ]
    ct = to_chrome_trace(spans)
    _dur, flows, procs = _validate_chrome(ct)
    shard_pids = {pid for pid, name in procs.items() if name != "broker"}
    # every shard process is the destination of at least one flow
    reached = {p[1]["pid"] for p in flows.values()
               if p[0]["name"] == "scatter"}
    assert reached == shard_pids


def test_chrome_export_empty_trace():
    ct = to_chrome_trace([])
    assert ct["traceEvents"] == []


# -- cluster end-to-end -------------------------------------------------------


FR = Schema.build("frtab", dimensions=[("frk", "INT")],
                  metrics=[("frv", "INT")])


@pytest.fixture(scope="module")
def cluster():
    d = Path(tempfile.mkdtemp(prefix="fr_"))
    store = PropertyStore()
    controller = ClusterController(store)
    servers = [ServerInstance(store, f"Server_{i}", backend="auto")
               for i in range(2)]
    for s in servers:
        s.start()
    controller.add_schema(FR.to_json())
    t = controller.create_table({"tableName": "frtab", "replication": 2})
    rng = np.random.default_rng(11)
    for i in range(3):
        cols = {"frk": rng.integers(0, 16, 400).astype(np.int32),
                "frv": rng.integers(0, 100, 400).astype(np.int32)}
        name = f"frtab_{i}"
        SegmentBuilder(FR, segment_name=name).build(cols, d / name)
        controller.add_segment(t, name, {"location": str(d / name),
                                         "numDocs": 400})
    broker = Broker(store)
    broker.backoff_base_s = 0.001
    yield store, broker, servers
    for s in servers:
        s.stop()


SQL = "SELECT frk, SUM(frv) FROM frtab GROUP BY frk LIMIT 20"


def test_sampled_production_query_retained(cluster, monkeypatch):
    """The acceptance path: sampling armed, NO explain analyze, a plain
    production query — retrievable afterwards at /debug/traces/{queryId}
    with a schema-valid chrome export whose flows connect the processes."""
    _store, broker, _servers = cluster
    # warm the statement first: a first run compiles, crosses the slow
    # threshold and would be retained as `slow`, not `sampled`
    warm = broker.execute_sql("SET resultCache = false; " + SQL)
    assert not warm.exceptions, warm.exceptions
    monkeypatch.setenv(SAMPLE_ENV, "1.0")
    resp = broker.execute_sql("SET resultCache = false; " + SQL)
    assert not resp.exceptions, resp.exceptions
    qid = resp.query_id
    assert qid and resp.trace_id == qid
    # the client never asked for a trace: the response ships plain
    assert resp.trace_info is None
    ent = broker.trace_store.get(qid)
    assert ent is not None and ent["reason"] == "sampled"
    ops = [s["operator"] for s in ent["spans"]]
    assert "BROKER_SCATTER" in ops and "BROKER_REDUCE" in ops
    assert any(s.get("server") for s in ent["spans"]), (
        "server shard spans must merge into the retained trace")
    ct = to_chrome_trace(ent["spans"], query_id=qid)
    dur, flows, procs = _validate_chrome(ct)
    assert "broker" in procs.values() and len(set(procs.values())) >= 2
    assert len(dur) == 2 * len(ent["spans"])
    assert any(p[0]["name"] == "scatter" for p in flows.values())
    assert any(p[0]["name"] == "gather" for p in flows.values())


def test_sampling_off_retains_nothing(cluster, monkeypatch):
    _store, broker, _servers = cluster
    monkeypatch.setenv(SAMPLE_ENV, "0.0")
    before = broker.trace_store.stats()["traces"]
    resp = broker.execute_sql("SET resultCache = false; " + SQL)
    assert not resp.exceptions, resp.exceptions
    assert getattr(resp, "trace_id", None) is None
    assert broker.trace_store.stats()["traces"] == before


def test_slow_sampled_query_is_pinned_and_linked(cluster, monkeypatch):
    """Tail-based capture: a traced query over the slow threshold retains
    PINNED, and the slow-query log references the retained id instead of
    embedding a second copy of the spans."""
    _store, broker, _servers = cluster
    monkeypatch.setenv(SAMPLE_ENV, "1.0")
    monkeypatch.setattr(broker.query_logger, "slow_threshold_ms", 0.0)
    resp = broker.execute_sql("SET resultCache = false; " + SQL)
    assert not resp.exceptions, resp.exceptions
    ent = broker.trace_store.get(resp.query_id)
    assert ent is not None and ent["pinned"] and ent["reason"] == "slow"
    slow = broker.query_logger.slow_queries()
    linked = [e for e in slow if e.get("traceId") == resp.query_id]
    assert linked, "slow entry must link the retained trace id"
    assert "trace" not in linked[0], "linked entry must not embed spans"


def test_explicit_trace_still_ships_to_client(cluster, monkeypatch):
    _store, broker, _servers = cluster
    monkeypatch.delenv(SAMPLE_ENV, raising=False)
    resp = broker.execute_sql("SET trace = true; SET resultCache = false; "
                              + SQL)
    assert not resp.exceptions, resp.exceptions
    assert resp.trace_info, "explicit SET trace keeps the client copy"
    assert broker.trace_store.get(resp.query_id) is not None


def test_sampled_result_cache_entry_is_plain(cluster, monkeypatch):
    _store, broker, _servers = cluster
    monkeypatch.setenv(SAMPLE_ENV, "1.0")
    sql = "SELECT frk, SUM(frv) FROM frtab GROUP BY frk LIMIT 19"
    r1 = broker.execute_sql(sql)
    assert not r1.exceptions and r1.cache_outcome in ("miss", "bypass")
    r2 = broker.execute_sql(sql)
    assert r2.cache_outcome == "hit"
    assert getattr(r2, "trace_info", None) is None, (
        "a cache hit must never replay a stale sampled trace")


def test_compile_registry_cold_vs_warm_end_to_end(cluster, monkeypatch):
    """Acceptance: a cold family shows >= 1 compile; re-running the same
    query adds dispatches WITHOUT adding compiles."""
    _store, broker, _servers = cluster
    monkeypatch.delenv(SAMPLE_ENV, raising=False)
    # segmentCache off too: a warm partial-cache hit would serve the
    # result without any device dispatch, hiding the counter this test
    # exists to watch
    sql = "SET resultCache = false; SET segmentCache = false; " \
          "SELECT frk, MAX(frv) FROM frtab GROUP BY frk LIMIT 21"
    t0 = COMPILE_REGISTRY.totals()
    r = broker.execute_sql(sql)
    assert not r.exceptions, r.exceptions
    t1 = COMPILE_REGISTRY.totals()
    assert t1["compiles"] >= t0["compiles"] + 1, (t0, t1)
    d1 = COMPILE_REGISTRY.snapshot()["totalDispatches"]
    r = broker.execute_sql(sql)
    assert not r.exceptions, r.exceptions
    t2 = COMPILE_REGISTRY.totals()
    d2 = COMPILE_REGISTRY.snapshot()["totalDispatches"]
    assert t2["compiles"] == t1["compiles"], "warm run must not recompile"
    assert d2 > d1, "warm run must count its dispatches"


def test_debug_endpoints(cluster, monkeypatch):
    """GET /debug/traces, /debug/traces/{id}?format=chrome, and
    /debug/compiles all serve; /metrics carries the new gauges."""
    from pinot_tpu.cluster.rest import BrokerRestServer

    _store, broker, _servers = cluster
    monkeypatch.setenv(SAMPLE_ENV, "1.0")
    resp = broker.execute_sql("SET resultCache = false; " + SQL)
    assert not resp.exceptions
    qid = resp.query_id
    rs = BrokerRestServer(broker)
    try:
        def get(path):
            with urllib.request.urlopen(rs.url + path) as r:
                return r.status, r.read()

        code, body = get("/debug/traces")
        listing = json.loads(body)
        assert code == 200 and listing["stats"]["traces"] >= 1
        assert any(t["queryId"] == qid for t in listing["traces"])
        code, body = get(f"/debug/traces/{qid}")
        assert code == 200 and json.loads(body)["queryId"] == qid
        code, body = get(f"/debug/traces/{qid}?format=chrome")
        assert code == 200
        _validate_chrome(json.loads(body))
        code, body = get("/debug/compiles")
        comp = json.loads(body)
        assert code == 200 and comp["totalCompiles"] >= 1
        assert "hbm" in comp and "highWater" in comp["hbm"]
        code, body = get("/metrics")
        text = body.decode()
        assert "pinot_traceStoreTraces" in text
        try:
            get("/debug/traces/not-a-query-id")
            assert False, "404 expected"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        rs.close()


def test_server_debug_compiles_endpoint(cluster):
    from pinot_tpu.cluster.rest import ServerRestServer

    _store, _broker, servers = cluster
    rs = ServerRestServer(servers[0])
    try:
        with urllib.request.urlopen(rs.url + "/debug/compiles") as r:
            comp = json.loads(r.read())
            assert r.status == 200 and "hbm" in comp
        with urllib.request.urlopen(rs.url + "/metrics") as r:
            text = r.read().decode()
            assert "pinot_compileFamilies" in text
            assert "pinot_hbmBytesHighWater" in text
    finally:
        rs.close()
