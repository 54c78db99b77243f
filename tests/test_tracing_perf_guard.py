"""CI perf-structure guard: tracing OFF must cost nothing on the hot path,
and tracing ON must take the same path.

Call-count instrumentation, not wall-clock, so it can't flake: after the
query is warm (compile guard satisfied, fused validation settled, planes
resident in HBM), an untraced run must perform ZERO extra
``jax.block_until_ready`` / ``jax.device_get`` calls and allocate ZERO
trace spans — the only tracing cost allowed is the single thread-local
read in ``TRACING.scope``/``active_trace``. A traced run of the same query
must then make EXACTLY as many of those calls, and as many device→host
fetches and dispatches, as the untraced one — spans record, they never
add a sync, skip a cache or change a grouping — and still allocate spans,
proving the guard actually watches the instrumented sites.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.trace import span_allocations

SQL = "SELECT pgk, SUM(pgv) FROM perfguard GROUP BY pgk"


@pytest.fixture(scope="module")
def warm_engine(tmp_path_factory):
    d = tmp_path_factory.mktemp("perfguard")
    # unique column names -> fresh Program -> this module owns its own
    # compile-guard entries regardless of what other tests compiled
    schema = Schema.build("perfguard", dimensions=[("pgk", "INT")],
                         metrics=[("pgv", "INT")])
    rng = np.random.default_rng(7)
    segs = []
    for i in range(4):
        cols = {"pgk": rng.integers(0, 20, 2000).astype(np.int32),
                "pgv": rng.integers(0, 100, 2000).astype(np.int32)}
        SegmentBuilder(schema, segment_name=f"pg_{i}").build(cols, d / f"s{i}")
        segs.append(load_segment(d / f"s{i}"))
    qe = QueryExecutor()
    qe.add_table(schema, segs)
    # warm: first run compiles, second proves the steady state
    for _ in range(2):
        r = qe.execute_sql(SQL)
        assert not r.exceptions, r.exceptions
    return qe


class _CountingSync:
    """Counting wrappers over jax's host-sync entry points."""

    def __init__(self, monkeypatch):
        self.block_calls = 0
        self.device_get_calls = 0
        real_block = jax.block_until_ready
        real_get = jax.device_get

        def counting_block(x):
            self.block_calls += 1
            return real_block(x)

        def counting_get(x):
            self.device_get_calls += 1
            return real_get(x)

        monkeypatch.setattr(jax, "block_until_ready", counting_block)
        monkeypatch.setattr(jax, "device_get", counting_get)


def test_tracing_off_adds_zero_syncs_and_zero_spans(warm_engine, monkeypatch):
    sync = _CountingSync(monkeypatch)
    spans_before = span_allocations()
    r = warm_engine.execute_sql(SQL)
    assert not r.exceptions, r.exceptions
    assert r.trace_info is None
    assert sync.block_calls == 0, (
        "tracing-off dispatch must not add block_until_ready syncs")
    assert sync.device_get_calls == 0, (
        "tracing-off dispatch must not add device_get syncs")
    assert span_allocations() == spans_before, (
        "tracing-off path must allocate zero Span objects")


@pytest.mark.parametrize("options", [
    "", "SET segmentCache = false; "], ids=["cache-hit", "device-work"])
def test_traced_run_syncs_exactly_as_untraced_and_allocates(
        warm_engine, monkeypatch, options):
    """A traced warm run takes the untraced path: the same count of
    block_until_ready / device_get calls, host fetches, dispatches and
    segment-cache hits — and it still allocates spans."""
    from pinot_tpu.ops.kernels import host_fetches

    sync = _CountingSync(monkeypatch)

    def run(prefix):
        before = (sync.block_calls, sync.device_get_calls, host_fetches(),
                  span_allocations())
        r = warm_engine.execute_sql(prefix + options + SQL)
        assert not r.exceptions, r.exceptions
        after = (sync.block_calls, sync.device_get_calls, host_fetches(),
                 span_allocations())
        return r, tuple(a - b for a, b in zip(after, before))

    plain, (p_block, p_get, p_fetch, p_spans) = run("")
    traced, (t_block, t_get, t_fetch, t_spans) = run("SET trace = true; ")
    assert plain.trace_info is None and traced.trace_info
    assert (t_block, t_get, t_fetch) == (p_block, p_get, p_fetch)
    assert traced.num_device_dispatches == plain.num_device_dispatches
    assert traced.num_segments_cache_hit == plain.num_segments_cache_hit
    assert traced.result_table.rows == plain.result_table.rows
    assert p_spans == 0 and t_spans > 0
    ops = [s["operator"] for s in traced.trace_info]
    if options:
        assert plain.num_device_dispatches == 1 and p_fetch == 1
        assert "DEVICE_FETCH" in ops and "family_dispatch" in ops
    else:
        # the warm repeat is answered by the segment cache, traced or not,
        # and the trace says so
        assert plain.num_device_dispatches == 0
        assert "SEGMENT_CACHE(hit)" in ops and "family_dispatch" not in ops


# -- cluster-path guard: cost accounting + health rollup stay off the hot
# -- path (observability PR discipline: with tracing off and no ANALYZE,
# -- a broker query does zero span allocations, zero extra syncs, and
# -- zero store writes — no beacon publish, no scrape work)


CSQL = "SET resultCache = false; SELECT pck, SUM(pcv) FROM pgclu GROUP BY pck"


@pytest.fixture(scope="module")
def warm_cluster(tmp_path_factory):
    from pinot_tpu.cluster import (Broker, ClusterController, PropertyStore,
                                   ServerInstance)
    from pinot_tpu.segment.builder import SegmentBuilder as SB

    d = tmp_path_factory.mktemp("pg_cluster")
    store = PropertyStore()
    controller = ClusterController(store)
    server = ServerInstance(store, "Server_0", backend="host")
    server.start()
    schema = Schema.build("pgclu", dimensions=[("pck", "INT")],
                          metrics=[("pcv", "INT")])
    controller.add_schema(schema.to_json())
    controller.create_table({"tableName": "pgclu", "replication": 1})
    rng = np.random.default_rng(9)
    for i in range(2):
        cols = {"pck": rng.integers(0, 16, 1500).astype(np.int32),
                "pcv": rng.integers(0, 100, 1500).astype(np.int32)}
        name = f"pgclu_{i}"
        SB(schema, segment_name=name).build(cols, d / name)
        controller.add_segment("pgclu_OFFLINE", name,
                               {"location": str(d / name), "numDocs": 1500})
    broker = Broker(store)
    broker.backoff_base_s = 0.001
    for _ in range(2):
        r = broker.execute_sql(CSQL)
        assert not r.exceptions, r.exceptions
    yield store, broker, server
    server.stop()


def test_cluster_off_path_zero_spans_zero_store_writes(warm_cluster,
                                                       monkeypatch):
    store, broker, _ = warm_cluster
    writes = {"n": 0}
    real_set = store.set

    def counting_set(path, value, *a, **kw):
        writes["n"] += 1
        return real_set(path, value, *a, **kw)

    monkeypatch.setattr(store, "set", counting_set)
    spans_before = span_allocations()
    r = broker.execute_sql(CSQL)
    assert not r.exceptions, r.exceptions
    assert r.trace_info is None
    assert span_allocations() == spans_before, (
        "untraced broker query must allocate zero Span objects")
    assert writes["n"] == 0, (
        "untraced broker query must do zero store writes — no state "
        "beacon, no scrape work on the query thread")


def test_sampling_disabled_adds_zero_spans_zero_syncs(warm_cluster,
                                                      monkeypatch):
    """Flight recorder off-path guard: with PINOT_TPU_TRACE_SAMPLE unset
    (and again explicitly 0.0) a broker query allocates zero spans and
    adds zero device syncs — the sampler must stay a cheap decision, not
    an armed trace."""
    _store, broker, _ = warm_cluster
    sync = _CountingSync(monkeypatch)
    for env in (None, "0.0"):
        if env is None:
            monkeypatch.delenv("PINOT_TPU_TRACE_SAMPLE", raising=False)
        else:
            monkeypatch.setenv("PINOT_TPU_TRACE_SAMPLE", env)
        spans_before = span_allocations()
        r = broker.execute_sql(CSQL)
        assert not r.exceptions, r.exceptions
        assert r.trace_info is None
        assert getattr(r, "trace_id", None) is None
        assert span_allocations() == spans_before
    assert sync.block_calls == 0 and sync.device_get_calls == 0


def test_sampled_run_traces_but_ships_plain(warm_cluster, monkeypatch):
    """Sanity for the guard above: sampling armed DOES allocate spans and
    retain the trace — while the client response still ships without it."""
    _store, broker, _ = warm_cluster
    monkeypatch.setenv("PINOT_TPU_TRACE_SAMPLE", "1.0")
    spans_before = span_allocations()
    r = broker.execute_sql(CSQL)
    assert not r.exceptions, r.exceptions
    assert span_allocations() > spans_before
    assert r.trace_info is None, "sampled trace must not ship to the client"
    assert broker.trace_store.get(r.query_id) is not None


def test_warm_dispatch_counts_without_fingerprint_work(warm_engine,
                                                       monkeypatch):
    """The compile registry's warm path must be counter bumps only: no
    span allocations and ZERO family-fingerprint computations (the
    canonical-bytes IR walk happens exclusively on compile-guard misses).
    segmentCache is disabled so the dispatch actually runs."""
    from pinot_tpu.cache import keys as cache_keys
    from pinot_tpu.engine.compile_registry import COMPILE_REGISTRY

    sql = "SET segmentCache = false; " + SQL
    r = warm_engine.execute_sql(sql)  # settle the family
    assert not r.exceptions, r.exceptions
    # count the IR walk itself: family_fingerprint intentionally does not
    # bump fingerprint_computations(), so the guard watches canonical_bytes
    walks = {"n": 0}
    real_cb = cache_keys.canonical_bytes

    def counting_cb(obj):
        walks["n"] += 1
        return real_cb(obj)

    monkeypatch.setattr(cache_keys, "canonical_bytes", counting_cb)
    spans_before = span_allocations()
    d_before = COMPILE_REGISTRY.snapshot()["totalDispatches"]
    r = warm_engine.execute_sql(sql)
    assert not r.exceptions, r.exceptions
    assert COMPILE_REGISTRY.snapshot()["totalDispatches"] > d_before, (
        "warm dispatch must register in the compile registry")
    assert walks["n"] == 0, (
        "warm dispatch must not re-walk the Program IR")
    assert span_allocations() == spans_before


# -- performance-ledger guard: the per-plan ledger records every broker
# -- query as pure counter bumps — zero syncs, zero span allocations,
# -- zero store writes, zero fingerprint (IR-walk) computations, and a
# -- single attribute read for the disarmed exemplar check


def test_ledger_records_warm_query_at_zero_cost(warm_cluster, monkeypatch):
    from pinot_tpu.cache import keys as cache_keys
    from pinot_tpu.engine.perf_ledger import PERF_LEDGER

    store, broker, _ = warm_cluster
    monkeypatch.delenv("PINOT_TPU_TRACE_SAMPLE", raising=False)
    # the ledger is process-global: an alert another test file fired on
    # this worker may have left exemplar sampling armed
    PERF_LEDGER.disarm_exemplars()
    assert PERF_LEDGER.exemplar_armed is False
    sync = _CountingSync(monkeypatch)
    walks = {"n": 0}
    real_cb = cache_keys.canonical_bytes

    def counting_cb(obj):
        walks["n"] += 1
        return real_cb(obj)

    monkeypatch.setattr(cache_keys, "canonical_bytes", counting_cb)
    writes = {"n": 0}
    real_set = store.set

    def counting_set(path, value, *a, **kw):
        writes["n"] += 1
        return real_set(path, value, *a, **kw)

    monkeypatch.setattr(store, "set", counting_set)
    spans_before = span_allocations()

    def ledger_queries():
        return sum(p["totals"]["queries"]
                   for p in PERF_LEDGER.snapshot()["plans"]
                   if p["table"] == "pgclu")

    q_before = ledger_queries()
    r = broker.execute_sql(CSQL)
    assert not r.exceptions, r.exceptions
    assert ledger_queries() == q_before + 1, (
        "the ledger must record every broker query")
    assert sync.block_calls == 0 and sync.device_get_calls == 0, (
        "ledger recording must not add device syncs")
    assert span_allocations() == spans_before, (
        "ledger recording must allocate zero Span objects")
    assert writes["n"] == 0, (
        "ledger persistence belongs to the sentinel scrape, never the "
        "query thread")
    assert walks["n"] == 0, (
        "the ledger key must reuse the result-cache fingerprint or a "
        "crc32 — never a fresh canonical-bytes IR walk")


def test_ledger_memory_bounded_under_fingerprint_churn(warm_cluster,
                                                       monkeypatch):
    """A fingerprint flood (distinct SQL per query) must not grow the
    ledger past its plan cap — batch eviction absorbs the churn."""
    from pinot_tpu.engine.perf_ledger import PERF_LEDGER

    _store, broker, _ = warm_cluster
    PERF_LEDGER.clear()  # drop plans accumulated by earlier test files
    monkeypatch.setattr(PERF_LEDGER, "max_plans", 8)
    for i in range(40):
        r = broker.execute_sql(
            f"SET resultCache = false; SELECT pck, SUM(pcv) FROM pgclu "
            f"WHERE pcv < {1000 + i} GROUP BY pck")
        assert not r.exceptions, r.exceptions
        assert len(PERF_LEDGER) <= 8, (
            "fingerprint churn must stay inside the plan cap")


def test_armed_exemplar_pins_a_trace(warm_cluster, monkeypatch):
    """Sanity for the zero-cost guard: arming exemplars DOES force-trace
    the next matching query and link it to the alert."""
    from pinot_tpu.engine.perf_ledger import ALERTS, PERF_LEDGER

    _store, broker, _ = warm_cluster
    monkeypatch.delenv("PINOT_TPU_TRACE_SAMPLE", raising=False)
    aid, _new = ALERTS.fire("latency-drift", "pgclu-test", "pgclu",
                            "guard sanity", {})
    PERF_LEDGER.arm_exemplars(aid, table="pgclu", count=1)
    try:
        spans_before = span_allocations()
        r = broker.execute_sql(CSQL)
        assert not r.exceptions, r.exceptions
        assert span_allocations() > spans_before, (
            "armed exemplar must force a sampled trace")
        rec = ALERTS.get(aid)
        assert r.query_id in rec["exemplarTraceIds"]
        ent = broker.trace_store.get(r.query_id)
        assert ent and aid in ent["alertIds"] and ent["pinned"]
        assert PERF_LEDGER.exemplar_armed is False, (
            "a one-shot budget must auto-disarm")
    finally:
        PERF_LEDGER.disarm_exemplars()
        ALERTS.resolve("latency-drift", "pgclu-test")


def test_analyze_and_beacon_move_the_new_counters(warm_cluster):
    """Sanity for the guard above: an armed run DOES move the new
    observability counters — ANALYZE allocates spans, the workload
    tracker folds the query in, and an explicit beacon publish writes
    broker state to the store."""
    store, broker, _ = warm_cluster
    spans_before = span_allocations()
    q0 = broker.workload.snapshot()["tables"].get("pgclu", {})
    r = broker.execute_sql(
        "EXPLAIN ANALYZE SELECT pck, SUM(pcv) FROM pgclu GROUP BY pck "
        "LIMIT 7")
    assert not r.exceptions, r.exceptions
    assert span_allocations() > spans_before
    q1 = broker.workload.snapshot()["tables"]["pgclu"]
    assert q1["queries"] > q0.get("queries", 0.0)
    assert q1["tracedQueries"] > q0.get("tracedQueries", 0.0)
    broker.publish_state()
    beacon = store.get(f"/BROKERSTATE/{broker.broker_id}")
    assert beacon and beacon["brokerId"] == broker.broker_id
