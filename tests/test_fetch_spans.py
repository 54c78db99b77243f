"""The host's side of a device fetch, and the CPU time of every span.

- a scope records its thread's CPU time over the span (`cpuMs`): a sleep
  reads next to none, a spin about its wall time; a span recorded in one
  shot carries none, and a worker's scope keeps its own;
- every DEVICE_FETCH holds one DEVICE_COPY (the wait for the results and
  the copy) and one FETCH_DECODE (the host's decode of the bytes), in that
  order, inside it: a batch family through the executor's fetch, packs of
  equal length concatenated on the device, packs of unequal length, the
  unpacked outputs of a raw dispatch, and the device merge's fetch.
"""

from __future__ import annotations

import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from pinot_tpu.engine.executor import device_fetch, fetch_outputs
from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.ops.kernels import fetch_packed_batch, pack_outputs
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.trace import TRACING

SLACK_MS = 0.05  # both clocks are rounded to the microsecond


def _traced(fn):
    """(fn()'s result, the spans it recorded) under a trace of its own."""
    trace = TRACING.start_trace("fetch-spans")
    try:
        out = fn()
    finally:
        TRACING.end_trace()
    return out, trace.to_json()


def _end_ms(span) -> float:
    return span["startNs"] / 1e6 + span["durationMs"]


def _check_fetches(spans) -> list:
    """Every DEVICE_FETCH's children: DEVICE_COPY then FETCH_DECODE, each
    inside its parent. Returns the DEVICE_FETCH spans."""
    fetches = [s for s in spans if s["operator"] == "DEVICE_FETCH"]
    assert fetches
    for fetch in fetches:
        kids = [s for s in spans if s.get("parentId") == fetch["spanId"]]
        assert [k["operator"] for k in kids] == ["DEVICE_COPY",
                                                 "FETCH_DECODE"], kids
        copy, decode = kids
        for kid in kids:
            assert kid["startNs"] >= fetch["startNs"]
            assert _end_ms(kid) <= _end_ms(fetch) + SLACK_MS
        assert _end_ms(copy) <= decode["startNs"] / 1e6 + SLACK_MS
        # what crossed is counted on the fetch, not on its parts
        assert "hostFetches" in fetch["attributes"]
        assert "attributes" not in copy and "attributes" not in decode
    return fetches


def _check_cpu(spans) -> None:
    for s in spans:
        assert 0.0 <= s["cpuMs"] <= s["durationMs"] + SLACK_MS, s


# -- cpuMs ---------------------------------------------------------------------


def _spin(seconds: float) -> None:
    """Work until this thread has used ``seconds`` of CPU: however busy
    the machine, the thread's CPU clock advances by that much."""
    end = time.thread_time_ns() + int(seconds * 1e9)
    while time.thread_time_ns() < end:
        pass


@pytest.mark.parametrize("work,busy", [
    pytest.param(lambda: time.sleep(0.02), False, id="a-sleep"),
    pytest.param(lambda: _spin(0.02), True, id="a-spin"),
])
def test_a_scope_records_its_threads_cpu_time(work, busy):
    def run():
        with TRACING.scope("work"):
            work()

    _, spans = _traced(run)
    (span,) = spans
    assert span["durationMs"] >= 19.0
    if busy:
        assert 20.0 <= span["cpuMs"] <= span["durationMs"] + SLACK_MS
    else:
        assert span["cpuMs"] < 5.0


def test_a_span_recorded_in_one_shot_carries_no_cpu_time():
    def run():
        trace = TRACING.active_trace()
        t0 = time.perf_counter()
        trace.record("RESULT_CACHE(hit)", t0, t0 + 0.001)

    _, (span,) = _traced(run)
    assert "cpuMs" not in span and span["durationMs"] == 1.0


def test_a_worker_scope_keeps_its_own_cpu_time():
    """A span whose work runs on another thread counts only its own
    thread: the worker's scope, adopted under it, carries the spin."""
    def worker(trace, parent):
        TRACING.adopt(trace, parent)
        try:
            with TRACING.scope("worker"):
                _spin(0.02)
        finally:
            TRACING.adopt(None)

    def run():
        with TRACING.scope("parent") as span:
            t = threading.Thread(target=worker,
                                 args=(TRACING.active_trace(), span))
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()

    _, spans = _traced(run)
    by_name = {s["operator"]: s for s in spans}
    assert by_name["worker"]["parentId"] == by_name["parent"]["spanId"]
    assert by_name["worker"]["cpuMs"] >= 20.0
    assert by_name["parent"]["cpuMs"] < 5.0
    _check_cpu(spans)


# -- DEVICE_COPY and FETCH_DECODE under every DEVICE_FETCH --------------------


def _packs(n_equal: int, other: bool):
    outs = [(jnp.arange(6, dtype=jnp.int64) * (i + 1) - (1 << 40),
             jnp.asarray([1.5, -2.25, 1e300]) * (i + 1),
             jnp.arange(5, dtype=jnp.int32) + i) for i in range(n_equal)]
    if other:
        outs.append((jnp.arange(9, dtype=jnp.int32),))
    return outs, [pack_outputs(o) for o in outs]


@pytest.mark.parametrize("n_equal,other,crossings", [
    pytest.param(2, False, 1, id="two-equal-packs-concatenated"),
    pytest.param(1, True, 2, id="unequal-packs-one-at-a-time"),
    pytest.param(2, True, 2, id="a-pair-and-a-lone-pack"),
])
def test_a_packed_fetch_is_one_copy_then_one_decode(n_equal, other,
                                                    crossings):
    outs, packs = _packs(n_equal, other)

    def run():
        with device_fetch():
            return fetch_packed_batch(packs)

    got, spans = _traced(run)
    (fetch,) = _check_fetches(spans)
    assert fetch["attributes"]["hostFetches"] == crossings
    assert fetch["attributes"]["fetchBytes"] == sum(
        4 * int(p.flat.shape[0]) for p in packs)
    _check_cpu(spans)
    for want, have in zip(outs, got):
        for w, h in zip(want, have):
            assert h.dtype == w.dtype
            np.testing.assert_array_equal(h, np.asarray(w))


def test_the_unpacked_outputs_of_a_dispatch_are_copied_and_passed_on():
    outs = (jnp.arange(7, dtype=jnp.int32), jnp.ones(3, jnp.float32))
    got, spans = _traced(lambda: fetch_outputs(outs))
    _check_fetches(spans)
    _check_cpu(spans)
    assert [g.tolist() for g in got] == [list(range(7)), [1.0, 1.0, 1.0]]


def test_untraced_fetches_record_nothing():
    from pinot_tpu.spi.trace import span_allocations

    _, packs = _packs(2, True)
    before = span_allocations()
    with device_fetch():
        fetch_packed_batch(packs)
    fetch_outputs((jnp.arange(3),))
    assert span_allocations() == before


FS = Schema.build("fsp", dimensions=[("fk", "INT")], metrics=[("fv", "INT")])
NOCACHE = "SET resultCache = false; SET segmentCache = false; "


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    d = Path(tmp_path_factory.mktemp("fetch_spans"))
    rng = np.random.default_rng(41)
    segs = []
    for i in range(4):
        # every key in every segment: one program, one batch family
        cols = {"fk": rng.permutation(np.arange(1200) % 300).astype(np.int32),
                "fv": rng.integers(0, 100, 1200).astype(np.int32)}
        SegmentBuilder(FS, segment_name=f"fsp_{i}").build(cols, d / f"s{i}")
        segs.append(load_segment(d / f"s{i}"))
    qe = QueryExecutor(backend="tpu")
    qe.add_table(FS, segs)
    return qe, segs


@pytest.mark.parametrize("sql,merged", [
    pytest.param("SELECT fk, SUM(fv) FROM fsp GROUP BY fk LIMIT 1000",
                 False, id="a-batch-family-through-the-fetch"),
    pytest.param("SET sparseGroupBy = true; SELECT fk, SUM(fv) FROM fsp "
                 "GROUP BY fk ORDER BY SUM(fv) DESC, fk LIMIT 10",
                 True, id="the-device-merge"),
])
def test_a_traced_query_splits_its_fetch_and_times_every_span(engine, sql,
                                                              merged):
    qe, segs = engine
    plain = qe.execute_sql(NOCACHE + sql)
    traced = qe.execute_sql("SET trace = true; " + NOCACHE + sql)
    assert not traced.exceptions, traced.exceptions
    assert traced.result_table.rows == plain.result_table.rows
    assert traced.num_device_dispatches == 1
    spans = traced.trace_info
    (fetch,) = _check_fetches(spans)
    assert fetch["attributes"]["hostFetches"] == 1
    _check_cpu(spans)
    combine = next(s for s in spans if s["operator"] == "SERVER_COMBINE")
    assert ("groupsCombined" in combine.get("attributes", {})) == merged
