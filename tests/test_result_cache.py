"""Multi-tier result cache (ISSUE 5): fingerprints, partial reuse,
broker cache, lineage invalidation.

Tier 1 — cache/keys.py: process-stable program fingerprints (two fresh
planners → byte-identical keys; any literal change → different keys; no
repr()/id() fallback by construction).

Tier 2 — cache/partial.py + device-resident tabs: a warm repeat of a
multi-segment query must return bit-identical rows with ZERO device
dispatches, respect its byte budget, survive in-place combine mutation,
and never serve a replaced segment's stale partial (crc in the key).

Tier 3 — cache/results.py + broker wiring: full-response reuse keyed on
(query_fp, lineage epoch); segment replace and realtime commit bump the
epoch and the post-replace answer matches a cold broker bit-for-bit.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

from pinot_tpu.cache.keys import (UnfingerprintableError, canonical_bytes,
                                  program_fingerprint, query_fingerprint,
                                  segment_token)
from pinot_tpu.cache.partial import GLOBAL_PARTIAL_CACHE, SegmentPartialCache
from pinot_tpu.cache.results import (BrokerResultCache, bump_lineage_epoch,
                                     lineage_epoch)
from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.query.parser.sql import parse_sql
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.device_cache import GLOBAL_DEVICE_CACHE, DeviceSegmentCache
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema

SCHEMA = Schema.build(
    "rc",
    dimensions=[("rck", "INT"), ("rcd", "INT")],
    metrics=[("rcv", "LONG")])

DENSE_SQL = ("SELECT rck, COUNT(*), SUM(rcv), AVG(rcv) FROM rc "
             "GROUP BY rck ORDER BY rck LIMIT 1000")
AGG_SQL = "SELECT COUNT(*), SUM(rcv), MIN(rcv), MAX(rcv) FROM rc"
SPARSE_SQL = ("SET sparseGroupBy = true; "
              "SELECT rck, COUNT(*), SUM(rcv) FROM rc "
              "GROUP BY rck ORDER BY rck LIMIT 100000")


@pytest.fixture(autouse=True)
def _fresh_caches(monkeypatch):
    """Every test starts from cold process-global caches with the segment
    cache at its default-on state, regardless of what other modules set."""
    monkeypatch.setenv("PINOT_TPU_SEGMENT_CACHE", "1")
    monkeypatch.setenv("PINOT_TPU_RESULT_CACHE", "1")
    GLOBAL_PARTIAL_CACHE.clear()
    GLOBAL_DEVICE_CACHE.drop_partials()
    yield
    GLOBAL_PARTIAL_CACHE.clear()
    GLOBAL_DEVICE_CACHE.drop_partials()


def _gen(rng, n=3000):
    return {"rck": rng.integers(0, 32, n).astype(np.int32),
            "rcd": rng.integers(0, 12, n).astype(np.int32),
            "rcv": rng.integers(-200, 200, n).astype(np.int64)}


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    rng = np.random.default_rng(53)
    d = tmp_path_factory.mktemp("rc_segs")
    dirs = []
    segs = []
    for i in range(4):
        path = d / f"rc{i}"
        SegmentBuilder(SCHEMA, segment_name=f"rc{i}").build(_gen(rng), path)
        dirs.append(path)
        segs.append(load_segment(path))
    qe = QueryExecutor(backend="tpu")
    qe.add_table(SCHEMA, segs)
    return SimpleNamespace(qe=qe, dirs=dirs)


def _rows(resp):
    assert not resp.exceptions, resp.exceptions
    return resp.result_table.rows


# -- tier 1: fingerprints -----------------------------------------------------


def test_program_fingerprint_stable_across_fresh_planners(engine):
    """Same SQL parsed twice, planned by two independent executors over two
    independent loads of the same segment → byte-identical program_fp."""
    q1, q2 = parse_sql(DENSE_SQL), parse_sql(DENSE_SQL)
    s1, s2 = load_segment(engine.dirs[0]), load_segment(engine.dirs[0])
    e1, e2 = QueryExecutor(backend="tpu"), QueryExecutor(backend="tpu")
    fp1 = program_fingerprint(e1.tpu.plan(q1, s1), q1)
    fp2 = program_fingerprint(e2.tpu.plan(q2, s2), q2)
    assert fp1 is not None
    assert fp1 == fp2
    assert segment_token(s1) == segment_token(s2)
    assert query_fingerprint(q1) == query_fingerprint(q2)


def test_literal_change_changes_fingerprint(engine):
    seg = load_segment(engine.dirs[0])
    e = QueryExecutor(backend="tpu")
    sql_a = "SELECT SUM(rcv) FROM rc WHERE rck > 4"
    sql_b = "SELECT SUM(rcv) FROM rc WHERE rck > 3"
    qa, qb = parse_sql(sql_a), parse_sql(sql_b)
    fpa = program_fingerprint(e.tpu.plan(qa, seg), qa)
    fpb = program_fingerprint(e.tpu.plan(qb, seg), qb)
    assert fpa is not None and fpb is not None
    assert fpa != fpb
    assert query_fingerprint(qa) != query_fingerprint(qb)


def test_canonical_encoder_is_closed_world():
    # value-equal containers encode identically regardless of construction
    assert canonical_bytes({"a": 1, "b": 2}) == canonical_bytes({"b": 2, "a": 1})
    assert canonical_bytes((1, "x", 2.5)) == canonical_bytes([1, "x", 2.5])
    assert canonical_bytes(np.int32(7)) == canonical_bytes(np.asarray(7, np.int32))
    # type tags keep equal payloads of different types apart
    assert canonical_bytes(1) != canonical_bytes(1.0)
    assert canonical_bytes(True) != canonical_bytes(1)
    # NO repr()/id() fallback: an arbitrary object raises instead of
    # silently leaking a memory address into the key
    with pytest.raises(UnfingerprintableError):
        canonical_bytes(object())

    class Opaque:
        pass

    with pytest.raises(UnfingerprintableError):
        canonical_bytes({"k": Opaque()})
    # ... and a plan carrying one yields None → callers bypass the cache
    fake = SimpleNamespace(program=Opaque(), slots=(), fused_ok=True, params=())
    assert program_fingerprint(fake, parse_sql("SELECT COUNT(*) FROM rc")) is None


# -- tier 2: segment partial reuse (the zero-dispatch warm repeat) ------------


@pytest.mark.parametrize("sql", [DENSE_SQL, AGG_SQL], ids=["groupby", "agg"])
def test_warm_repeat_is_zero_dispatch_bit_identical(engine, sql):
    cold = engine.qe.execute_sql(sql)
    assert cold.num_segments_cache_miss == 4
    assert cold.num_device_dispatches > 0
    warm = engine.qe.execute_sql(sql)
    assert _rows(warm) == _rows(cold)
    assert warm.num_segments_cache_hit == 4
    assert warm.num_device_dispatches == 0
    j = warm.to_json()
    assert j["numSegmentsCacheHit"] == 4
    assert j.get("numDeviceDispatches", 0) == 0


def test_sparse_warm_repeat_is_zero_dispatch(engine):
    cold = engine.qe.execute_sql(SPARSE_SQL)
    assert cold.num_device_dispatches > 0
    warm = engine.qe.execute_sql(SPARSE_SQL)
    assert _rows(warm) == _rows(cold)
    assert warm.num_segments_cache_hit == 4
    assert warm.num_device_dispatches == 0
    # the device-resident per-segment tabs are their own tier: with the
    # host cache wiped, warm overlap still skips every program dispatch
    GLOBAL_PARTIAL_CACHE.clear()
    tab_warm = engine.qe.execute_sql(SPARSE_SQL)
    assert _rows(tab_warm) == _rows(cold)
    assert tab_warm.num_device_dispatches == 0
    assert GLOBAL_DEVICE_CACHE.hbm_stats()["hbmPartialEntries"] >= 4


def test_cross_executor_warm_reuse(engine):
    """A second executor with its own planner over its own segment loads
    hits the first executor's partials — keys are content-addressed, never
    object identity."""
    cold = engine.qe.execute_sql(DENSE_SQL)
    qe2 = QueryExecutor(backend="tpu")
    qe2.add_table(SCHEMA, [load_segment(d) for d in engine.dirs])
    warm = qe2.execute_sql(DENSE_SQL)
    assert _rows(warm) == _rows(cold)
    assert warm.num_segments_cache_hit == 4
    assert warm.num_device_dispatches == 0


def test_segment_cache_opt_out(engine):
    off = "SET segmentCache = false; "
    engine.qe.execute_sql(off + DENSE_SQL)
    again = engine.qe.execute_sql(off + DENSE_SQL)
    assert not again.exceptions
    assert again.num_segments_cache_hit == 0
    assert again.num_segments_cache_miss == 0
    assert again.num_device_dispatches > 0


def test_triple_run_mutation_safety(engine):
    """combine merges agg states IN PLACE — three identical runs must stay
    bit-identical (the cache deep-copies on put AND get)."""
    sql = ("SELECT rck, DISTINCTCOUNT(rcd), AVG(rcv) FROM rc "
           "GROUP BY rck ORDER BY rck LIMIT 1000")
    first = _rows(engine.qe.execute_sql(sql))
    for _ in range(2):
        assert _rows(engine.qe.execute_sql(sql)) == first


def test_replaced_segment_same_name_never_serves_stale(engine, tmp_path):
    """A segment re-pushed under the SAME name with different content gets
    a different crc → different key → recomputed, even before any eager
    invalidation runs."""
    rng = np.random.default_rng(99)
    old_dir, new_dir = tmp_path / "va", tmp_path / "vb"
    SegmentBuilder(SCHEMA, segment_name="swap0").build(_gen(rng), old_dir)
    SegmentBuilder(SCHEMA, segment_name="swap0").build(_gen(rng), new_dir)
    sql = "SELECT COUNT(*), SUM(rcv) FROM rc"
    qe_old = QueryExecutor(backend="tpu")
    qe_old.add_table(SCHEMA, [load_segment(old_dir)])
    rows_old = _rows(qe_old.execute_sql(sql))
    qe_new = QueryExecutor(backend="tpu")
    qe_new.add_table(SCHEMA, [load_segment(new_dir)])
    resp_new = qe_new.execute_sql(sql)
    assert resp_new.num_segments_cache_hit == 0
    assert _rows(resp_new) != rows_old  # different content, fresh answer


def test_partial_cache_eviction_respects_budget():
    c = SegmentPartialCache(max_bytes=600)  # opaque entries estimate 256B
    c.put(("k1",), ["p1"], ("s1",))
    c.put(("k2",), ["p2"], ("s2",))
    c.put(("k3",), ["p3"], ("s3",))  # over budget → LRU k1 evicted
    assert c.get(("k1",)) is None
    assert c.get(("k2",)) == ["p2"]
    assert c.get(("k3",)) == ["p3"]
    st = c.stats()
    assert st["entries"] == 2 and st["evictions"] == 1
    assert st["bytes"] <= c.max_bytes
    assert c.invalidate_segment("s2") == 1
    assert c.get(("k2",)) is None
    # a value alone over budget is skipped, not inserted-then-thrashed
    tiny = SegmentPartialCache(max_bytes=100)
    tiny.put(("big",), ["x"], ("s",))
    assert tiny.stats()["entries"] == 0


def test_device_partial_budget_evicts_partials_first():
    cache = DeviceSegmentCache(budget_bytes=2000)
    a = np.zeros(150, np.int64)  # 1200 bytes
    cache.put_partial(("k1",), (a,), "segA")
    cache.put_partial(("k2",), (np.zeros(150, np.int64),), "segB")
    # 2400 > 2000: the oldest partial goes; the fresh insert survives
    assert cache.get_partial(("k1",)) is None
    assert cache.get_partial(("k2",)) is not None
    st = cache.hbm_stats()
    assert st["hbmPartialEntries"] == 1
    assert st["hbmPartialBytes"] == 1200
    # oversized partial is refused outright
    cache.put_partial(("big",), (np.zeros(1000, np.int64),), "segC")
    assert cache.get_partial(("big",)) is None
    # lineage drop by segment name
    assert cache.drop_partials(segment_name="segB") == 1
    assert cache.hbm_stats()["hbmPartialEntries"] == 0
    # OOM relief sheds partials
    cache.put_partial(("k3",), (np.zeros(8, np.int64),), "segD")
    cache.evict_all_except(None)
    assert cache.hbm_stats()["hbmPartialEntries"] == 0


# -- tier 3: broker result cache + lineage epochs -----------------------------


def test_broker_result_cache_ttl_and_capacity():
    clk = [0.0]
    c = BrokerResultCache(max_bytes=10_000, ttl_s=10.0, clock=lambda: clk[0])
    resp = SimpleNamespace(result_table=None)
    c.put(("k",), resp)
    assert c.get(("k",)) is not None
    clk[0] = 9.0
    assert c.get(("k",)) is not None
    clk[0] = 10.5  # past TTL: expired on read, counted as a miss
    assert c.get(("k",)) is None
    assert c.stats()["entries"] == 0 and c.stats()["misses"] == 1

    cap = BrokerResultCache(max_bytes=1200, ttl_s=1e9, clock=lambda: clk[0])
    for i in range(3):  # 512B each → third insert evicts the LRU first
        cap.put((f"k{i}",), SimpleNamespace(result_table=None))
    assert cap.get(("k0",)) is None
    assert cap.get(("k2",)) is not None
    st = cap.stats()
    assert st["evictions"] == 1 and st["bytes"] <= 1200
    assert cap.clear() == 2


def test_lineage_epoch_helpers():
    from pinot_tpu.cluster import PropertyStore

    store = PropertyStore()
    assert lineage_epoch(store, "t_OFFLINE") == 0
    bump_lineage_epoch(store, "t_OFFLINE")
    bump_lineage_epoch(store, "t_OFFLINE")
    assert lineage_epoch(store, "t_OFFLINE") == 2
    assert lineage_epoch(store, "t_REALTIME") == 0


@pytest.fixture()
def cluster(tmp_path):
    from pinot_tpu.cluster import (Broker, ClusterController, PropertyStore,
                                   ServerInstance)

    pschema = Schema.build("p", dimensions=[("k", "INT")],
                           metrics=[("v", "INT")])
    store = PropertyStore()
    controller = ClusterController(store)
    server = ServerInstance(store, "Server_0", backend="host")
    server.start()
    broker = Broker(store)
    controller.add_schema(pschema.to_json())

    def seg(name, vals):
        cols = {"k": np.arange(len(vals), dtype=np.int32),
                "v": np.asarray(vals, dtype=np.int32)}
        SegmentBuilder(pschema, segment_name=name).build(cols, tmp_path / name)
        return str(tmp_path / name)

    yield SimpleNamespace(store=store, controller=controller, server=server,
                          broker=broker, seg=seg)
    server.stop()


def test_broker_cache_hit_and_replace_invalidation(cluster):
    """Warm repeat hits; a lineage replace (the minion merge/refresh path)
    bumps the epoch and the next answer matches a cold broker bit-for-bit."""
    from pinot_tpu.cluster import Broker
    from pinot_tpu.cluster.periodic import SegmentLineageManager

    table = cluster.controller.create_table(
        {"tableName": "p", "replication": 1})
    cluster.controller.add_segment(table, "old0", {
        "location": cluster.seg("old0", [1, 2]), "numDocs": 2})
    assert lineage_epoch(cluster.store, table) >= 1  # upload bumped it
    sql = "SELECT COUNT(*), SUM(v) FROM p"
    r1 = cluster.broker.execute_sql(sql)
    assert _rows(r1) == [[2, 3.0]]
    assert r1.cache_outcome == "miss"
    r2 = cluster.broker.execute_sql(sql)
    assert r2.cache_outcome == "hit"
    assert _rows(r2) == _rows(r1)
    assert cluster.broker.result_cache.stats()["hits"] == 1

    lineage = SegmentLineageManager(cluster.store, cluster.controller)
    lid = lineage.start_replace(table, ["old0"], ["m0"])
    cluster.controller.add_segment(table, "m0", {
        "location": cluster.seg("m0", [10, 20]), "numDocs": 2})
    epoch_before = lineage_epoch(cluster.store, table)
    lineage.end_replace(table, lid)
    assert lineage_epoch(cluster.store, table) > epoch_before
    r3 = cluster.broker.execute_sql(sql)
    assert r3.cache_outcome == "miss"  # old key unreachable, recomputed
    cold = Broker(cluster.store).execute_sql(sql)
    assert _rows(r3) == _rows(cold) == [[2, 30.0]]


def test_lineage_revert_bumps_epoch(cluster):
    from pinot_tpu.cluster.periodic import SegmentLineageManager

    table = cluster.controller.create_table(
        {"tableName": "p", "replication": 1})
    cluster.controller.add_segment(table, "keep", {
        "location": cluster.seg("keep", [7]), "numDocs": 1})
    lineage = SegmentLineageManager(cluster.store, cluster.controller)
    lid = lineage.start_replace(table, ["keep"], ["bad"])
    before = lineage_epoch(cluster.store, table)
    lineage.revert_replace(table, lid)
    assert lineage_epoch(cluster.store, table) > before


def test_realtime_commit_bumps_epoch():
    from pinot_tpu.cluster import PropertyStore
    from pinot_tpu.realtime.completion import (COMMIT, COMMIT_SUCCESS,
                                               SegmentCompletionManager)

    store = PropertyStore()
    mgr = SegmentCompletionManager(store, num_replicas=1)
    t = "p_REALTIME"
    assert lineage_epoch(store, t) == 0
    assert mgr.segment_consumed(t, "p__0", "i1", 100).status == COMMIT
    mgr.segment_commit_start(t, "p__0", "i1", 100)
    out = mgr.segment_commit_end(t, "p__0", "i1", 100, "/deep/p__0")
    assert out.status == COMMIT_SUCCESS
    assert lineage_epoch(store, t) == 1


def test_realtime_table_bypasses_result_cache(cluster):
    """A REALTIME half means consuming rows advance without lineage events
    — the broker must never cache such a table's answers."""
    cluster.controller.create_table(
        {"tableName": "p", "tableType": "OFFLINE", "replication": 1})
    cluster.controller.create_table(
        {"tableName": "p", "tableType": "REALTIME", "replication": 1,
         "streamConfigs": {}})
    off = cluster.controller.add_segment(
        "p_OFFLINE", "o0", {"location": cluster.seg("o0", [5]), "numDocs": 1})
    assert off
    sql = "SELECT SUM(v) FROM p"
    r1 = cluster.broker.execute_sql(sql)
    r2 = cluster.broker.execute_sql(sql)
    assert r1.cache_outcome == "bypass" and r2.cache_outcome == "bypass"
    assert cluster.broker.result_cache.stats()["entries"] == 0


def test_result_cache_opt_outs(cluster):
    table = cluster.controller.create_table(
        {"tableName": "p", "replication": 1})
    cluster.controller.add_segment(table, "s0", {
        "location": cluster.seg("s0", [1]), "numDocs": 1})
    r = cluster.broker.execute_sql(
        "SET resultCache = false; SELECT SUM(v) FROM p")
    assert not r.exceptions, r.exceptions
    assert r.cache_outcome == "bypass"
    assert cluster.broker.result_cache.stats()["entries"] == 0
    # `SET trace = true` is NOT an opt-out: a traced query takes the path
    # an untraced one takes. It misses, is stored WITHOUT its trace, and a
    # traced repeat is a hit that says so in a span
    r = cluster.broker.execute_sql("SET trace = true; SELECT SUM(v) FROM p")
    assert not r.exceptions, r.exceptions
    assert r.cache_outcome == "miss" and r.trace_info
    assert cluster.broker.result_cache.stats()["entries"] == 1
    plain = cluster.broker.execute_sql("SELECT SUM(v) FROM p")
    assert plain.cache_outcome == "hit" and plain.trace_info is None
    traced = cluster.broker.execute_sql(
        "SET trace = true; SELECT SUM(v) FROM p")
    assert traced.cache_outcome == "hit"
    assert traced.result_table.rows == r.result_table.rows
    assert [s["operator"] for s in traced.trace_info] == ["RESULT_CACHE(hit)"]
    assert traced.trace_info[0]["attributes"]["cache"] == "hit"
    # the shared cached copy stayed plain
    again = cluster.broker.execute_sql("SELECT SUM(v) FROM p")
    assert again.cache_outcome == "hit" and again.trace_info is None
    # non-deterministic SQL bypasses at the key level (decision tree)
    try:
        q = parse_sql("SELECT SUM(v) FROM p WHERE v < NOW()")
    except Exception:
        q = None  # grammar rejects NOW(): nothing to cache either way
    if q is not None and "now(" in str(q).lower():
        assert cluster.broker._result_cache_key(q, None) is None


def test_debug_cache_and_delete_cache_endpoints(cluster):
    import json
    import urllib.request

    from pinot_tpu.cluster.rest import BrokerRestServer

    table = cluster.controller.create_table(
        {"tableName": "p", "replication": 1})
    cluster.controller.add_segment(table, "s0", {
        "location": cluster.seg("s0", [1, 2, 3]), "numDocs": 3})
    brest = BrokerRestServer(cluster.broker)
    try:
        for _ in range(2):
            r = cluster.broker.execute_sql("SELECT SUM(v) FROM p")
            assert not r.exceptions
        with urllib.request.urlopen(brest.url + "/debug/cache") as resp:
            dbg = json.loads(resp.read())
        assert dbg["resultCache"]["entries"] == 1
        assert dbg["resultCache"]["hits"] == 1
        assert "segmentPartialCache" in dbg
        assert "hbmPartialEntries" in dbg["devicePartials"]
        req = urllib.request.Request(brest.url + "/cache", method="DELETE")
        with urllib.request.urlopen(req) as resp:
            out = json.loads(resp.read())
        assert out["resultEntriesDropped"] == 1
        with urllib.request.urlopen(brest.url + "/debug/cache") as resp:
            dbg = json.loads(resp.read())
        assert dbg["resultCache"]["entries"] == 0
    finally:
        brest.close()


def test_querylog_tags_cache_outcome():
    from pinot_tpu.cluster.querylog import QueryLogger

    ql = QueryLogger(slow_threshold_ms=0.0)
    hit = SimpleNamespace(time_used_ms=5.0, cache_outcome="hit")
    plain = SimpleNamespace(time_used_ms=5.0)
    ql.log("SELECT 1", hit, table="p")
    ql.log("SELECT 2", plain, table="p")
    entries = {e["sql"]: e for e in ql.slow_queries()}
    assert entries["SELECT 1"]["cacheOutcome"] == "hit"
    assert "cacheOutcome" not in entries["SELECT 2"]
