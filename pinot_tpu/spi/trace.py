"""Tracing SPI: per-query hierarchical phase spans.

Reference analogue: pinot-spi/.../spi/trace/Tracing.java:45
(InvocationScope recordings, per-request registration in
ServerQueryExecutorV1Impl.execute:143-156) and the phase timers
(pinot-common/.../metrics/ServerQueryPhase.java:29-36). Traces attach to
the broker response when the `trace` query option is set, exactly like the
reference's `trace=true`.

Spans RECORD; they never change what runs: a traced request takes the
path an untraced one takes (no added sync, no cache bypass, no change of
grouping). Per server shard the tree is

    QUERY_PROCESSING            entry of the server's handler to the blob
      SCHEDULER_WAIT            admission (engine/scheduler.py)
      BUILD_QUERY_PLAN          prune, route, plan, cache lookups, families
      family_dispatch           gather + enqueue, no sync
        GATHER_STACK            member planes to [S, ...] stacks
      DEVICE_FETCH              the host blocks until results are host arrays
        DEVICE_COPY             the wait for the results and their copy
        FETCH_DECODE            the host's decode of the fetched bytes
      SERVER_COMBINE            `groupsFetched`: groups the segments bring
      RESPONSE_SERIALIZATION    datatable.encode

under the broker's BROKER_SCATTER / BROKER_REDUCE. `to_json()` stays a
FLAT list — consumers that only care about phase names/durations keep
working — with `spanId`/`parentId` conveying the hierarchy, `startNs` (the
epoch clock, the one the JAX profiler stamps its events with) aligning
the spans of broker and servers, and an `attributes` dict carrying
device-phase detail (compileMs, transferBytes, hostFetches). A span
opened by `TRACING.scope` also carries `cpuMs`, the CPU time of the
thread that opened it over the span (`time.thread_time_ns`): beside
`durationMs` it says how much of the wall time the thread worked and how
much it waited (for the device, a lock, the interpreter). While a trace
is active every scope is also a
`jax.profiler.TraceAnnotation`, so a profiler session running at the time
records the same interval, with `query_id` and `span_id`, on the host
plane of its own trace, beside the device's operations.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
import zlib
from contextlib import contextmanager, nullcontext
from typing import Any, Optional


class ServerQueryPhase:
    """Reference: ServerQueryPhase enum values (those this engine emits)."""

    SCHEDULER_WAIT = "SCHEDULER_WAIT"
    BUILD_QUERY_PLAN = "BUILD_QUERY_PLAN"
    QUERY_PLAN_EXECUTION = "QUERY_PLAN_EXECUTION"
    RESPONSE_SERIALIZATION = "RESPONSE_SERIALIZATION"
    QUERY_PROCESSING = "QUERY_PROCESSING"
    SERVER_COMBINE = "SERVER_COMBINE"


# this engine's own span names, beside the reference's phases: the host
# blocked on the device's results (its two steps: the copy, which waits
# for the results, and the host's decode of them), and the [S, ...]
# stacking of a family
DEVICE_FETCH = "DEVICE_FETCH"
DEVICE_COPY = "DEVICE_COPY"
FETCH_DECODE = "FETCH_DECODE"
GATHER_STACK = "GATHER_STACK"
FAMILY_DISPATCH = "family_dispatch"


# Process-wide span-allocation counter: the tracing-off perf guard asserts
# this does not move when `trace` is unset (tests/test_tracing_perf_guard).
_SPAN_ALLOCS = 0


def span_allocations() -> int:
    return _SPAN_ALLOCS


# -- sampled trace retention (flight recorder head sampling) ----------------
#
# PINOT_TPU_TRACE_SAMPLE ∈ [0, 1] arms probabilistic tracing of production
# queries (no SET trace, no EXPLAIN ANALYZE). The decision is a
# deterministic hash of the queryId, NOT a coin flip: the broker stamps one
# queryId per query and every scatter shard carries a `<queryId>:<n>` id,
# so broker and servers — each consulting only its own environment — agree
# on exactly which queries trace and the merged trace is always complete.
# Rate 0 (the default) keeps the hot path at one thread-local read: the
# env is consulted only where a trace could be armed (broker/server entry),
# never per span.

TRACE_SAMPLE_ENV = "PINOT_TPU_TRACE_SAMPLE"

# hash-space denominator: crc32(queryId) % 10000 < rate * 10000 gives a
# 0.01% sampling granularity, stable across processes and restarts
_SAMPLE_SPACE = 10000


def trace_sample_rate() -> float:
    """Current head-sampling rate — read per query (not cached) so tests
    and operators can re-arm a live process via the environment."""
    raw = os.environ.get(TRACE_SAMPLE_ENV)
    if not raw:
        return 0.0
    try:
        return min(1.0, max(0.0, float(raw)))
    except ValueError:
        return 0.0


def sample_decision(query_id: str, rate: float) -> bool:
    """Deterministic per-queryId head-sampling verdict: same id + same
    rate → same answer in every process. Shard ids (`<queryId>:<n>`) must
    be stripped to the queryId prefix BY THE CALLER so all shards of one
    query agree with the broker's decision."""
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    return zlib.crc32(query_id.encode()) % _SAMPLE_SPACE \
        < int(rate * _SAMPLE_SPACE)


class Span:
    """One recorded scope: a node in the query's span tree."""

    __slots__ = ("name", "start_ms", "start_ns", "duration_ms", "cpu_ms",
                 "span_id", "parent_id", "seq", "attributes")

    def __init__(self, name: str, start_ms: float, start_ns: int,
                 span_id: int, parent_id: Optional[int], seq: int):
        global _SPAN_ALLOCS
        _SPAN_ALLOCS += 1
        self.name = name
        self.start_ms = start_ms
        self.start_ns = start_ns
        self.duration_ms = 0.0
        # the opening thread's CPU time over the span (TRACING.scope);
        # None for a span recorded in one shot (Trace.record)
        self.cpu_ms: Optional[float] = None
        self.span_id = span_id
        self.parent_id = parent_id
        self.seq = seq
        self.attributes: dict[str, Any] = {}

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def to_json(self) -> dict:
        out = {"operator": self.name, "startMs": self.start_ms,
               "startNs": self.start_ns,
               "durationMs": self.duration_ms, "spanId": self.span_id}
        if self.cpu_ms is not None:
            out["cpuMs"] = self.cpu_ms
        if self.parent_id is not None:
            out["parentId"] = self.parent_id
        if self.attributes:
            out["attributes"] = dict(self.attributes)
        return out


class Trace:
    """One query's recorded spans (flat store; tree via parentId)."""

    def __init__(self, query_id: str):
        # the broker's queryId (shard suffix stripped): every participant's
        # root spans carry it, so the spans of one request find each other
        self.query_id = query_id
        self.spans: list[Span] = []
        # one reading of the epoch clock per trace; spans add their
        # perf_counter offset to it, so `startNs` is monotonic within a
        # trace and comparable across the processes of one host
        self._t0 = time.perf_counter()
        self._t0_ns = time.time_ns()
        # list.append and itertools.count.__next__ are GIL-atomic, so
        # combine workers on adopted traces need no lock here
        self._ids = itertools.count(1)
        self._seq = itertools.count()

    def new_span(self, name: str, start: float,
                 parent: Optional[Span] = None) -> Span:
        offset = start - self._t0
        span = Span(name, round(offset * 1000, 3),
                    self._t0_ns + int(offset * 1e9), next(self._ids),
                    None if parent is None else parent.span_id,
                    next(self._seq))
        if parent is None:
            span.attributes["queryId"] = self.query_id
        self.spans.append(span)
        return span

    def record(self, name: str, start: float, end: float,
               parent: Optional[Span] = None) -> Span:
        """Record a completed scope in one shot (no `cpuMs`: the work was
        not timed on this thread)."""
        span = self.new_span(name, start, parent)
        span.duration_ms = round((end - start) * 1000, 3)
        return span

    def to_json(self) -> list:
        # combine workers append from multiple threads, so raw list order
        # is interleave-dependent: sort by startMs, ties by record order
        return [s.to_json()
                for s in sorted(self.spans, key=lambda s: (s.start_ms, s.seq))]

    def to_tree(self) -> list:
        """Nested form: children grouped under their parent span."""
        nodes = {s.span_id: dict(s.to_json(), children=[])
                 for s in sorted(self.spans,
                                 key=lambda s: (s.start_ms, s.seq))}
        roots = []
        for node in nodes.values():
            parent = nodes.get(node.get("parentId"))
            (parent["children"] if parent else roots).append(node)
        return roots


def phase_breakdown(trace_json: list) -> dict:
    """Roll a flat span list up into the device-phase totals bench.py
    emits: compile time, the host's wait for the device, host-combine time
    and host->device transfer volume. `deviceWaitMs` is the sum of the
    DEVICE_FETCH spans: how long the host stood blocked until results were
    host arrays — queueing behind other requests' programs, execution, the
    output pack, the copy and the host's decode of the copied bytes (its
    DEVICE_COPY and FETCH_DECODE children), NOT device time of this query
    alone."""
    out = {"compileMs": 0.0, "deviceWaitMs": 0.0, "hostCombineMs": 0.0,
           "transferBytes": 0, "shuffledBytes": 0}
    for span in trace_json:
        attrs = span.get("attributes") or {}
        out["compileMs"] += attrs.get("compileMs", 0.0)
        out["transferBytes"] += attrs.get("transferBytes", 0)
        out["shuffledBytes"] += attrs.get("shuffled_bytes", 0)
        if span.get("operator") == DEVICE_FETCH:
            out["deviceWaitMs"] += span.get("durationMs", 0.0)
        if span.get("operator") in (ServerQueryPhase.SERVER_COMBINE,
                                    "BROKER_REDUCE"):
            out["hostCombineMs"] += span.get("durationMs", 0.0)
    for k in ("compileMs", "deviceWaitMs", "hostCombineMs"):
        out[k] = round(out[k], 3)
    if not out["shuffledBytes"]:
        # MSE-only phase: single-stage queries keep the classic four-key shape
        del out["shuffledBytes"]
    return out


_ANNOTATION = None


def _annotation(name: str, **kwargs):
    """The profiler's own host-plane span for ``name``. jax is imported on
    first use (never at module load: spi/ stays importable without it);
    with no profiler session running a TraceAnnotation is one atomic read
    in C++."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation as _ANNOTATION
        except Exception:  # no jax in this process: spans still record
            _ANNOTATION = lambda name, **kw: nullcontext()  # noqa: E731
    return _ANNOTATION(name, **kwargs)


class _Tracing:
    """Per-thread active trace registry (reference: Tracing.ThreadLocal)."""

    def __init__(self):
        self._local = threading.local()

    def start_trace(self, query_id: str) -> Trace:
        trace = Trace(query_id)
        self._local.trace = trace
        self._local.stack = []
        return trace

    def active_trace(self) -> Optional[Trace]:
        return getattr(self._local, "trace", None)

    def current_span(self) -> Optional[Span]:
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    def adopt(self, trace: Optional[Trace],
              parent: Optional[Span] = None) -> None:
        """Make another thread's trace active here (worker-pool fan-out:
        the reference's per-thread registration in combine workers).
        ``parent`` seeds the span stack so worker scopes nest under the
        caller's span instead of floating at the root."""
        self._local.trace = trace
        self._local.stack = [] if parent is None else [parent]

    def end_trace(self) -> Optional[Trace]:
        trace = self.active_trace()
        self._local.trace = None
        self._local.stack = []
        return trace

    @contextmanager
    def scope(self, name: str):
        """Records a span into the active trace, nested under the current
        span; yields the Span so callers can attach attributes. The same
        interval is entered as a profiler TraceAnnotation carrying the
        trace's query id and the span's id. The span's `cpu_ms` is the CPU
        time of this thread between open and close: where the span's work
        fans out to worker threads (`adopt`), the workers' own scopes
        carry theirs, and the parent counts its own thread only. No-op when
        tracing is off — the hot path pays one thread-local read and yields
        None (zero Span allocations, no annotation, no clock read)."""
        trace = self.active_trace()
        if trace is None:
            yield None
            return
        # the CPU interval nests inside the wall one: cpu_ms <= duration_ms
        start = time.perf_counter()
        cpu0 = time.thread_time_ns()
        span = trace.new_span(name, start, self.current_span())
        stack = self._local.stack
        stack.append(span)
        try:
            with _annotation(name, query_id=trace.query_id,
                             span_id=span.span_id):
                yield span
        finally:
            cpu = time.thread_time_ns() - cpu0
            span.duration_ms = round((time.perf_counter() - start) * 1000, 3)
            span.cpu_ms = round(cpu / 1e6, 3)
            if stack and stack[-1] is span:
                stack.pop()


TRACING = _Tracing()
