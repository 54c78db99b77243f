"""In-process query executor: tables of segments → BrokerResponse.

The round-1 equivalent of the reference's in-process test harness topology
(BaseQueriesTest.getBrokerResponse, pinot-core/src/test/.../BaseQueriesTest.java:126-207
— plan maker → per-segment operators → combine → broker reduce, no
networking). The cluster layer (broker/server processes over gRPC) builds on
exactly these pieces.

Per segment, the TPU path is tried first; UnsupportedQueryError falls back to
the host engine — mirroring BASELINE.json's "CPU path remains the default"
backend selection, inverted: TPU is the default here, host is the safety net.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

from ..query.context import QueryContext
from ..query.parser.sql import SqlParseError, parse_sql
from ..spi.metrics import SERVER_METRICS, ServerMeter, ServerTimer
from ..spi.trace import TRACING, ServerQueryPhase
from .scheduler import GLOBAL_ACCOUNTANT
from ..segment.loader import ImmutableSegment
from ..spi.data_types import Schema
from .aggregation import UnsupportedQueryError, semantics_for
from .combine import (combine_aggregation, combine_group_by,
                      combine_selection, trim_group_by)
from .ir import program_label
from .plan import table_bucket
from ..ops.kernels import fetch_packed_batch, unpack_outputs
from .executor import (BatchFamilyMismatch, TpuSegmentExecutor,
                       batch_families, device_fetch, fetch_outputs,
                       dispatch_counters,
                       reset_dispatch_counters)
from .host_executor import HostSegmentExecutor
from .oom import HbmExhaustedError, with_oom_retry
from .pruner import SegmentPrunerService
from .reduce import BrokerReducer
from .results import (
    AggIntermediate,
    BrokerResponse,
    GroupByIntermediate,
    SelectionIntermediate,
)


def _estimate_bytes(inter) -> int:
    """Rough intermediate footprint for the accountant (reference samples
    real allocations via ThreadMXBean; here: container-size heuristics)."""
    from .results import GroupArrays

    if isinstance(inter, GroupArrays):
        # size from the columns; do NOT touch .groups (materializing the
        # dict is exactly the per-group cost the columnar path avoids)
        return (sum(k.nbytes for k in inter.key_cols)
                + sum(c.nbytes for comps in inter.state_cols for c in comps)
                + 64)
    if isinstance(inter, GroupByIntermediate):
        width = 1 + max((len(v) for v in inter.groups.values()), default=0)
        return 64 * width * len(inter.groups)
    if isinstance(inter, SelectionIntermediate):
        width = max(1, len(inter.columns))
        return 32 * width * len(inter.rows)
    if isinstance(inter, AggIntermediate):
        return 64 * max(1, len(inter.states))
    return 64


def _groups_in(intermediates) -> int:
    """Groups the segments' results bring to the combine (a traced
    request's SERVER_COMBINE says so as `groupsFetched`)."""
    from .results import GroupArrays

    return sum(im.num_groups if isinstance(im, GroupArrays)
               else len(im.groups)
               for im in intermediates
               if isinstance(im, GroupByIntermediate))


def _cut_order(query: QueryContext, plan):
    """((output index | None, descending, ties_high), trim size, threshold)
    where the ordered server-level trim (combine.trim_rule) ranks by
    something the device merge can rank: ONE count or state column that an
    aggregation finalizes to as it is, then the group key (or nothing: the
    host's stable sort lets the lower key win a tie too), or the key
    alone. None for any other ORDER BY: the host trims what it fetches."""
    from .combine import trim_rule

    rule = trim_rule(query)
    if rule is None or len(query.group_by_expressions) != 1:
        return None
    trim_size, threshold, order = rule
    key = str(query.group_by_expressions[0])
    (expr, ascending), rest = order[0], order[1:]
    if expr == key:
        return (None, not ascending, False), trim_size, threshold
    aggs = [str(a) for a in query.aggregations]
    if expr not in aggs or (rest and rest[0][0] != key):
        return None
    vec = plan.lowered_aggs[aggs.index(expr)].vec
    if vec.fin_tag[0] != "id":
        return None
    col = vec.outs[vec.fin_tag[1]]
    ties_high = bool(rest) and not rest[0][1]
    return (col, not ascending, ties_high), trim_size, threshold


@dataclass
class _SegmentTask:
    """One kept segment on its way through `QueryExecutor._run_segments`:
    the query and segment as routed (a star-tree `rewrite` swaps both), its
    plan (None: the planner refused it, host work), its partial-cache key,
    and what has come back for it so far — `got` says what `value` holds:
    "pack" a solo dispatch's PackedOuts, still on the device; "member" its
    (family key, row) in a batch family's outputs; "arrays" its outputs as
    host arrays; "decoded" a finished intermediate (the vectorised family
    decode). "cached" (a partial-cache hit) and "done" are stored."""
    idx: int
    query: QueryContext
    segment: object
    rewrite: object
    plan: object = None
    cache_key: object = None
    got: str = ""
    value: object = None


@dataclass
class _SegmentRun:
    """What the stages of one `_run_segments` call share."""
    query: QueryContext
    kept: list
    tracker: object
    deadline: Optional[float]
    timeout_ms: object
    cstats: dict
    planned: object
    msig: tuple
    done: int = 0
    rt_device: bool = False  # a consuming segment answered on device
    # by family key: the batched PackedOuts (on the device), its (segments,
    # plans) for a re-dispatch, the batched HOST arrays
    fam_packs: dict = field(default_factory=dict)
    fam_inputs: dict = field(default_factory=dict)
    fam_outs: dict = field(default_factory=dict)

    def __post_init__(self):
        self.intermediates: list = [None] * len(self.kept)

    def check(self, done: int = None) -> None:
        if self.tracker is not None:
            self.tracker.check_cancel()
        if self.deadline is not None and time.perf_counter() > self.deadline:
            raise TimeoutError(
                f"query exceeded timeoutMs={self.timeout_ms} "
                f"({self.done if done is None else done}/{len(self.kept)} "
                f"segments done)")


@dataclass
class Table:
    name: str
    schema: Schema
    segments: list[ImmutableSegment] = field(default_factory=list)


class QueryExecutor:
    """Executes SQL over registered tables. backend: "tpu" | "host" | "auto"
    (auto = tpu with host fallback per query shape)."""

    def __init__(self, backend: str = "auto", num_threads: int = 1):
        self.backend = backend
        self.tables: dict[str, Table] = {}
        self.tpu = TpuSegmentExecutor()
        self.host = HostSegmentExecutor()
        self.pruner = SegmentPrunerService()
        self.use_star_tree = True  # reference: useStarTree query option default true
        # >1: host-path segments run on a worker pool, the reference's
        # combine-operator fan-out (GroupByCombineOperator.java:54 runs one
        # task per segment on a shared executor)
        self.num_threads = max(1, int(num_threads))
        self._pool = None
        # cross-query coalescing rendezvous (engine/coalesce.py): shared
        # by every concurrent query through this executor
        from .coalesce import QueryCoalescer

        self.coalescer = QueryCoalescer()

    def _host_pool(self):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(max_workers=self.num_threads)
        return self._pool

    def add_table(self, schema: Schema, segments: list[ImmutableSegment], name: Optional[str] = None):
        """``segments`` is held BY REFERENCE when it is a list: realtime data
        managers mutate it in place as segments commit/rotate and queries see
        the live view (snapshotted per query). Segments predating schema
        columns are backfilled with virtual default columns on registration
        (reference: on-load default-column update — schema evolution)."""
        if not isinstance(segments, list):
            segments = list(segments)  # before iterating: may be a generator
        for seg in segments:
            if hasattr(seg, "apply_schema"):
                seg.apply_schema(schema)
        self.tables[name or schema.schema_name] = Table(
            name or schema.schema_name, schema, segments)
        # compile-free cold starts: pre-warm the table's top persisted
        # family executables (engine/aot_cache.py) so the first queries
        # after a restart skip XLA compiles. No-op unless
        # PINOT_TPU_AOT_CACHE_DIR is set; refusals fall back silently.
        from .aot_cache import enabled as aot_enabled, prewarm_table

        if aot_enabled():
            prewarm_table(name or schema.schema_name)

    def add_dimension_table(self, schema: Schema, segments: list,
                            name: Optional[str] = None) -> None:
        """Register a queryable table that ALSO serves LOOKUP joins
        (reference: TableConfig.isDimTable + DimensionTableDataManager —
        dim tables replicate fully and back the LOOKUP transform). The
        schema must declare primaryKeyColumns (single key)."""
        import numpy as np

        from .dim_tables import register_dimension_table

        self.add_table(schema, segments, name)
        if len(schema.primary_key_columns) != 1:
            raise ValueError("dimension tables need exactly one primary key")
        segs = self.tables[name or schema.schema_name].segments
        cols = {}
        for c in schema.column_names():
            parts = [np.asarray(s.get_values(c)) for s in segs]
            cols[c] = parts[0] if len(parts) == 1 else np.concatenate(parts)
        register_dimension_table(name or schema.schema_name,
                                 schema.primary_key_columns[0], cols)

    def execute_sql(self, sql: str) -> BrokerResponse:
        """Engine selection mirrors the reference's
        BrokerRequestHandlerDelegate: V1 for single-table queries, V2 (MSE)
        for joins/subqueries/set-ops/windows or when the
        ``useMultistageEngine`` query option is set."""
        try:
            query = parse_sql(sql)
        except SqlParseError:
            return self.multistage.execute_sql(sql)
        if query.query_options.get("useMultistageEngine") in (True, "true", 1):
            return self.multistage.execute_sql(sql)
        resp = self.execute(query)
        if resp.exceptions and any("UnsupportedQueryError" in e for e in resp.exceptions):
            # shapes V1 rejects (e.g. ORDER BY on unselected columns) that
            # the MSE can plan — mirrors the reference's option to fall back
            # across engines per query
            mse = self.multistage.execute_sql(sql)
            if not mse.exceptions:
                return mse
        return resp

    @property
    def multistage(self):
        if not hasattr(self, "_multistage"):
            from ..mse.executor import MultistageExecutor

            self._multistage = MultistageExecutor(self)
        return self._multistage

    def execute(self, query: QueryContext, tracker=None) -> BrokerResponse:
        t0 = time.perf_counter()
        table = self.tables.get(query.table_name)
        if table is None:
            # tolerate _OFFLINE/_REALTIME suffixes (reference table name with type)
            base = query.table_name.rsplit("_", 1)[0]
            table = self.tables.get(base)
        if table is None:
            return BrokerResponse(exceptions=[f"table {query.table_name} not found"])

        if getattr(query, "explain", False) == "analyze":
            return self._execute_analyze(query, tracker=tracker)

        if getattr(query, "explain", False):
            from .explain import explain_plan

            try:
                rt = explain_plan(query, table, self.pruner,
                                  backend=self.backend,
                                  use_star_tree=self.use_star_tree)
                return BrokerResponse(
                    result_table=rt,
                    time_used_ms=(time.perf_counter() - t0) * 1000)
            except Exception as e:
                return BrokerResponse(exceptions=[f"{type(e).__name__}: {e}"])

        # own the trace only when nobody upstream (the MSE stage runner)
        # already started one — nested engine calls join the caller's span
        # tree and leave attaching trace_info to the owner
        trace = None
        owns_trace = False
        if query.query_options.get("trace") in (True, "true", 1):
            trace = TRACING.active_trace()
            if trace is None:
                trace = TRACING.start_trace(
                    f"{query.table_name}:{id(query):x}")
                owns_trace = True
        try:
            with TRACING.scope(ServerQueryPhase.QUERY_PLAN_EXECUTION):
                combined, stats = self.execute_segments(
                    query, list(table.segments), tracker=tracker)
            reducer = BrokerReducer(table.schema)
            with TRACING.scope("BROKER_REDUCE"):
                result = reducer.reduce(query, combined)
        except Exception as e:  # clean broker-style error (reference QueryException)
            SERVER_METRICS.add_meter(ServerMeter.QUERY_EXECUTION_EXCEPTIONS)
            if owns_trace:
                TRACING.end_trace()
            return BrokerResponse(
                exceptions=[f"{type(e).__name__}: {e}"],
                num_segments_queried=len(table.segments),
                time_used_ms=(time.perf_counter() - t0) * 1000,
            )
        resp = BrokerResponse(
            result_table=result,
            num_docs_scanned=getattr(combined, "num_docs_scanned", 0),
            total_docs=stats["total_docs"],
            num_segments_queried=len(table.segments),
            num_segments_processed=stats["num_segments_processed"],
            num_segments_pruned=stats["num_segments_pruned"],
            num_groups_limit_reached=getattr(combined, "groups_trimmed",
                                             False),
            num_device_dispatches=stats.get("num_device_dispatches", 0),
            num_compiles=stats.get("num_compiles", 0),
            num_segments_cache_hit=stats.get("num_segments_cache_hit", 0),
            num_segments_cache_miss=stats.get("num_segments_cache_miss", 0),
            num_coalesced_queries=stats.get("num_coalesced_queries", 0),
            coalesce_wait_ms=stats.get("coalesce_wait_ms", 0.0),
            time_used_ms=(time.perf_counter() - t0) * 1000,
        )
        if owns_trace:
            TRACING.end_trace()
            resp.trace_info = trace.to_json()
        return resp

    def _execute_analyze(self, query: QueryContext,
                         tracker=None) -> BrokerResponse:
        """EXPLAIN ANALYZE: run the query for real under a trace (which
        changes nothing of the run: caches stay live) and return the span
        tree rendered as the annotated plan table, counters carried over
        from the actual run."""
        import copy

        from .explain import analyze_table

        sub = copy.copy(query)
        sub.explain = False
        sub.query_options = dict(query.query_options)
        sub.query_options["trace"] = True
        owns = TRACING.active_trace() is None
        if owns:
            trace = TRACING.start_trace(f"analyze:{query.table_name}")
        else:
            trace = TRACING.active_trace()
        try:
            resp = self.execute(sub, tracker=tracker)
        finally:
            if owns:
                TRACING.end_trace()
        if resp.exceptions:
            return resp
        trace_json = resp.trace_info if resp.trace_info is not None \
            else trace.to_json()
        out = copy.copy(resp)
        out.result_table = analyze_table(trace_json, resp,
                                         table_name=query.table_name)
        out.trace_info = trace_json
        return out

    def execute_selection_columnar(self, query: QueryContext):
        """Columnar leaf for MSE scan+filter stages: device filter mask →
        numpy column gather, skipping SelectionIntermediate's Python row
        materialization and the broker's row→column round trip. Returns
        (source-column arrays, stats) or None when the shape or backend
        doesn't qualify — the caller falls back to the row path, which owns
        ordering, deadlines and null handling."""
        import numpy as np

        if self.backend == "host":
            return None
        if (not query.is_selection or query.distinct
                or query.group_by_expressions or query.order_by_expressions
                or query.having_filter is not None or query.offset
                or query.null_handling
                or query.query_options.get("timeoutMs") is not None):
            return None
        if not query.select_expressions or not all(
                e.is_identifier and e.identifier != "*"
                for e in query.select_expressions):
            return None
        table = self.tables.get(query.table_name)
        if table is None:
            table = self.tables.get(query.table_name.rsplit("_", 1)[0])
        if table is None:
            return None
        # consuming segments join through a pinned snapshot; if the plan
        # can't lower on the realtime planner the except below falls back
        segments = [s.snapshot_view() if getattr(s, "is_mutable", False)
                    else s for s in table.segments]
        from ..query.optimizer import optimize_filter
        from ..segment.bitpack import unpack_bitmap

        names = [e.identifier for e in query.select_expressions]
        reset_dispatch_counters()
        try:
            query.filter = optimize_filter(query.filter)
            kept, _ = self.pruner.prune(query, segments)
            pending = []
            for seg in kept:
                plan = self.tpu.plan(query, seg)
                if plan.program.mode != "selection" or plan.selection_exprs:
                    return None
                outs = with_oom_retry(
                    lambda: self.tpu.dispatch_plan(seg, plan),
                    keep_segment=seg, cache=self.tpu.cache)
                pending.append((seg, outs))
            parts: dict[str, list] = {c: [] for c in names}
            scanned = 0
            remaining = max(0, int(query.limit))
            for seg, outs in pending:
                if remaining <= 0:
                    break
                mats = fetch_outputs(outs)
                bits = unpack_bitmap(np.asarray(mats[0]), seg.num_docs)
                doc_ids = np.nonzero(bits)[0]
                if len(doc_ids) > remaining:
                    doc_ids = doc_ids[:remaining]
                scanned += len(doc_ids)
                remaining -= len(doc_ids)
                for c in names:
                    parts[c].append(np.asarray(seg.get_values(c))[doc_ids])
        except Exception:
            # any planning/device hiccup: the row path re-runs the leaf
            # with identical semantics (and surfaces real failures)
            return None
        if any(getattr(s, "is_mutable", False) for s in kept):
            from ..realtime.device_plane import note_realtime_device_query

            note_realtime_device_query()
        cols: dict = {}
        for c, ps in parts.items():
            if not ps:
                cols[c] = np.empty(0)
            elif len(ps) == 1:
                cols[c] = ps[0]
            else:
                if any(p.dtype.kind == "O" for p in ps):
                    ps = [p.astype(object) for p in ps]
                cols[c] = np.concatenate(ps)
        num_dispatches, num_compiles = dispatch_counters()
        return cols, {"num_docs_scanned": scanned,
                      "total_docs": sum(s.num_docs for s in segments),
                      "num_device_dispatches": num_dispatches,
                      "num_compiles": num_compiles}

    def execute_segments(self, query: QueryContext, segments: list, tracker=None):
        """Server-side half of a query: prune → per-segment execute →
        combine. Returns (combined_intermediate, stats). This is what a
        cluster server runs for its assigned segments (reference:
        ServerQueryExecutorV1Impl.executeInternal without broker reduce);
        the in-process path and the cluster data plane share it.

        ``tracker`` (engine/scheduler.py QueryResourceTracker) enables
        cooperative cancellation + allocation accounting; the per-query
        deadline comes from the timeoutMs query option."""
        # filter canonicalization (query/optimizer.py — reference
        # QueryOptimizer runs once at the broker; here once per query on the
        # server path so every engine entry benefits). Idempotent, so a
        # re-dispatched QueryContext is safe to re-optimize.
        from contextlib import ExitStack

        t_start = time.perf_counter()
        # BUILD_QUERY_PLAN: canonicalize, prune, route, plan per segment,
        # cache lookups, family grouping. _run_segments closes the span
        # (``planned``) where the first dispatch follows; an error closes
        # it here.
        with ExitStack() as plan_scope:
            plan_scope.enter_context(
                TRACING.scope(ServerQueryPhase.BUILD_QUERY_PLAN))
            return self._execute_segments(query, segments, tracker, t_start,
                                          plan_scope.close)

    def _execute_segments(self, query: QueryContext, segments: list,
                          tracker, t_start: float, planned):
        from ..query.optimizer import optimize_filter

        query.filter = optimize_filter(query.filter)
        # per-query dispatch/compile counters (engine/executor.py): every
        # device dispatch for this query happens on this thread
        reset_dispatch_counters()
        # table attribution for AOT-persisted executables + per-query
        # coalescing counters (both thread-local, like the counters above)
        from .aot_cache import set_current_table
        from .coalesce import reset_coalesce_stats

        set_current_table(query.table_name)
        reset_coalesce_stats()
        # snapshot: realtime tables mutate the live list concurrently;
        # consuming segments pin a consistent row-count view per query
        segments = [s.snapshot_view() if getattr(s, "is_mutable", False) else s
                    for s in segments]
        kept, num_pruned = self.pruner.prune(query, segments)
        total_docs = sum(s.num_docs for s in segments)
        deadline = None
        timeout_ms = query.query_options.get("timeoutMs")
        if timeout_ms is not None:
            deadline = time.perf_counter() + float(timeout_ms) / 1000
        cstats = {"hit": 0, "miss": 0}
        intermediates = self._run_segments(query, kept, tracker, deadline,
                                           timeout_ms, cstats, planned)
        planned()  # no segment left to run: nothing closed it yet
        with TRACING.scope(ServerQueryPhase.SERVER_COMBINE) as span:
            if span is not None:
                span.set_attribute("groupsFetched",
                                   _groups_in(intermediates))
                if "groups_combined" in cstats:
                    # a device merge: the groups that entered it (summed
                    # over segments) and the size it was cut to, or 0
                    span.set_attribute("groupsCombined",
                                       cstats["groups_combined"])
                    span.set_attribute("deviceCut", cstats["device_cut"])
            combined = self._combine(query, intermediates)
        num_dispatches, num_compiles = dispatch_counters()
        # the declared server-phase timer (reference ServerQueryPhase
        # QUERY_PROCESSING): wall time of the server-side half, into the
        # histogram that backs the /metrics p50/p95/p99
        SERVER_METRICS.update_timer(ServerTimer.QUERY_PROCESSING_TIME_MS,
                                    (time.perf_counter() - t_start) * 1000)
        SERVER_METRICS.add_meter(ServerMeter.QUERIES)
        SERVER_METRICS.add_table_meter(query.table_name, ServerMeter.QUERIES)
        SERVER_METRICS.add_meter(ServerMeter.NUM_DOCS_SCANNED,
                                 getattr(combined, "num_docs_scanned", 0))
        SERVER_METRICS.add_meter(ServerMeter.NUM_SEGMENTS_PROCESSED, len(kept))
        SERVER_METRICS.add_meter(ServerMeter.NUM_SEGMENTS_PRUNED, num_pruned)
        SERVER_METRICS.add_meter(ServerMeter.NUM_DEVICE_DISPATCHES,
                                 num_dispatches)
        SERVER_METRICS.add_meter(ServerMeter.NUM_COMPILES, num_compiles)
        SERVER_METRICS.add_meter(ServerMeter.SEGMENT_CACHE_HITS,
                                 cstats["hit"])
        SERVER_METRICS.add_meter(ServerMeter.SEGMENT_CACHE_MISSES,
                                 cstats["miss"])
        from .coalesce import coalesce_stats

        co_peers, co_wait_ms = coalesce_stats()
        return combined, {
            "total_docs": total_docs,
            "num_segments_processed": len(kept),
            "num_segments_pruned": num_pruned,
            "num_device_dispatches": num_dispatches,
            "num_compiles": num_compiles,
            "num_segments_cache_hit": cstats["hit"],
            "num_segments_cache_miss": cstats["miss"],
            "num_coalesced_queries": co_peers,
            "coalesce_wait_ms": co_wait_ms,
        }

    def _run_segments(self, query: QueryContext, kept: list, tracker,
                      deadline, timeout_ms, cstats: dict, planned) -> list:
        """Multi-segment execution, stage by stage: plan every kept segment
        ONCE → partial-cache lookup → (the device merge | dispatch every
        family, async, so the device queue fills and runs back-to-back →
        host-fallback segments while the device works → one fetch →
        decode) → partial-cache store. The overlap the reference gets from
        a worker pool (GroupByCombineOperator.java:54) comes from XLA's
        async dispatch. `planned` closes BUILD_QUERY_PLAN where the lookup
        ends, on both routes."""
        run = _SegmentRun(query, kept, tracker, deadline, timeout_ms, cstats,
                          planned, self._mesh_sig(query))
        tasks, host_tasks, families = self._plan_segments(run)
        merged = None
        if self._device_merge_takes(query, tasks, host_tasks):
            merged = self._merge_on_device(run, tasks, families)
        if merged is None:
            self._lookup_partials(run, tasks)
            run.planned()
            self._dispatch_families(run, tasks, families)
            self._run_host_work(run, host_tasks)
            self._fetch(run, tasks)
            self._decode(run, tasks)
            self._store_partials(run, tasks)
        if run.rt_device:
            from ..realtime.device_plane import note_realtime_device_query

            note_realtime_device_query()
        return merged or run.intermediates

    def _plan_segments(self, run: _SegmentRun):
        """(device tasks, host tasks, families): every kept segment routed
        (star-tree rewrite) and planned once — a segment the planner
        refuses is host work —, the plans' sorted tables sized alike and
        grouped into batch families (positions index the device tasks), the
        partial-cache keys derived once, for both tiers."""
        tasks, host_tasks = [], []
        for idx, segment in enumerate(run.kept):
            run.check(idx)
            task = _SegmentTask(idx, *self._segment_route(run.query, segment))
            if self.backend != "host":
                try:
                    # consuming-segment snapshots lower through the realtime
                    # planner (realtime/device_plane.py) and join the device
                    # path; unsupported shapes fall back per segment
                    task.plan = self.tpu.plan(task.query, task.segment)
                except UnsupportedQueryError:
                    # mutable snapshots stay best-effort even under the
                    # forced-device backend: realtime tables must answer
                    if self.backend == "tpu" and not getattr(
                            task.segment, "is_mutable", False):
                        raise
            (host_tasks if task.plan is None else tasks).append(task)
        plans, families = batch_families(
            [(t.segment, t.plan) for t in tasks], run.msig,
            self._segment_batch_enabled(run.query))
        cache_on = tasks and self._segment_cache_enabled(run.query)
        for task, plan in zip(tasks, plans):
            task.plan = plan
            if cache_on:
                task.cache_key = self._partial_cache_key(
                    task.query, task.segment, task.rewrite, plan)
        return tasks, host_tasks, families

    def _lookup_partials(self, run: _SegmentRun, tasks: list) -> None:
        """The per-segment tier of the partial cache (cache/partial.py): a
        hit fills the intermediate directly and the segment never reaches
        dispatch; `_store_partials` inserts what missed. A traced run looks
        the cache up like any other and says so in a span."""
        from ..cache.partial import GLOBAL_PARTIAL_CACHE

        hits = 0
        for task in tasks:
            hit = None if task.cache_key is None \
                else GLOBAL_PARTIAL_CACHE.get(task.cache_key)
            if hit is not None:
                run.intermediates[task.idx], task.got = hit, "cached"
                hits += 1
            elif task.cache_key is not None:
                run.cstats["miss"] += 1
        if hits:
            run.cstats["hit"] += hits
            with TRACING.scope("SEGMENT_CACHE(hit)") as sp:
                if sp is not None:
                    sp.set_attribute("segments", hits)
                    sp.set_attribute("cache", "hit")

    def _store_partials(self, run: _SegmentRun, tasks: list) -> None:
        from ..cache.partial import GLOBAL_PARTIAL_CACHE

        for task in tasks:
            inter = run.intermediates[task.idx]
            # selections bypass (LIMIT makes row sets order-dependent
            # across segments and the payoff is row materialization, not
            # device work); agg/group partials are pure merges
            if task.cache_key is not None and task.got != "cached" \
                    and isinstance(inter, (AggIntermediate,
                                           GroupByIntermediate)):
                GLOBAL_PARTIAL_CACHE.put(
                    task.cache_key, inter,
                    (getattr(task.segment, "name", "?"),))

    def _dispatch_families(self, run: _SegmentRun, tasks: list,
                           families: list) -> None:
        """Stacked segment batching: one vmapped dispatch per batch FAMILY
        (equal host-side family key → identical plane shapes); a family of
        one, or one whose batch dispatch is refused, keeps the per-segment
        path (incl. the fused kernel). Nothing is fetched here."""
        from ..realtime.device_plane import RealtimeUploadError

        for fkey, positions in families:
            members = [tasks[p] for p in positions
                       if tasks[p].got != "cached"]
            if fkey is not None and len(members) > 1 \
                    and self._dispatch_family(run, fkey, members):
                continue
            for task in members:
                try:
                    task.value = with_oom_retry(
                        lambda: self.tpu.dispatch_plan(task.segment,
                                                       task.plan),
                        keep_segment=task.segment, cache=self.tpu.cache)
                except RealtimeUploadError:
                    # delta upload faulted/overran its budget: THIS query
                    # answers on host (bit-identical); plane state is
                    # pre-fault-consistent or dropped for full re-upload
                    self._host_execute(run, task)
                    continue
                task.got = "pack"
                run.rt_device |= getattr(task.segment, "is_mutable", False)

    def _dispatch_family(self, run: _SegmentRun, fkey, members: list) -> bool:
        """One batch dispatch for a family's members, or their rows of a
        coalesced group's. False: the family goes segment by segment."""
        from .coalesce import coalesce_enabled
        from ..realtime.device_plane import RealtimeUploadError

        segs = [t.segment for t in members]
        plans = [t.plan for t in members]
        mutable = any(getattr(s, "is_mutable", False) for s in segs)

        def dispatch(segs_d=segs, plans_d=plans):
            # HBM pressure during plane upload/dispatch: evict cold cached
            # segments once and retry (engine/oom.py — the DirectOOMHandler
            # analogue). Relief drops whole stacks.
            return with_oom_retry(
                lambda: self.tpu.dispatch_plan_batch(segs_d, plans_d,
                                                     mesh=run.msig),
                keep_segment=segs[0], cache=self.tpu.cache)

        def co_runner(segs_all, plans_all):
            pack = dispatch(segs_all, plans_all)
            with device_fetch():
                return fetch_packed_batch([pack])[0]

        # cross-query coalescing (engine/coalesce.py): only armed when the
        # opt-in hold window is set AND the family has repeat traffic. Its
        # family key carries no snapshot generation, so a held group could
        # serve one generation's stack to a later query — consuming
        # families never join
        co = self.coalescer.offer(
            run.query.table_name, fkey, segs, plans, run.msig, co_runner) \
            if coalesce_enabled(run.query) and not mutable else None
        if co is not None:
            # this query's S rows are zero-copy views of the group's
            # fetched stack: host-side already
            run.fam_outs[fkey] = co.outs
        else:
            try:
                run.fam_packs[fkey] = dispatch()
            except (BatchFamilyMismatch, RealtimeUploadError,
                    HbmExhaustedError):
                # the host key over-grouped; a consuming member's upload
                # faulted (the per-segment path host-falls that one); or
                # the [S, N] stacks, ~double the family's footprint, do
                # not fit even after relief: per-segment is always valid
                return False
            run.fam_inputs[fkey] = (segs, plans)
            run.rt_device |= mutable
        for row, task in enumerate(members):
            task.got, task.value = "member", (fkey, row)
        return True

    def _run_host_work(self, run: _SegmentRun, host_tasks: list) -> None:
        """Host-fallback segments, while the device works; with
        num_threads > 1 on the worker pool, the reference's combine-operator
        fan-out (one task a segment on a shared executor)."""
        if self.num_threads > 1 and len(host_tasks) > 1:
            trace, span = TRACING.active_trace(), TRACING.current_span()

            def run_one(task):
                # traces are thread-local; seed the caller's span so
                # worker scopes nest under QUERY_PLAN_EXECUTION
                TRACING.adopt(trace, span)
                try:
                    return self._host_timed(task)
                finally:
                    TRACING.adopt(None)

            results = [self._host_pool().submit(run_one, t).result
                       for t in host_tasks]
        else:
            results = [lambda t=t: self._host_timed(t) for t in host_tasks]
        for task, result in zip(host_tasks, results):
            run.check()
            self._finish(run, task, *result())

    def _fetch(self, run: _SegmentRun, tasks: list) -> None:
        """ONE device→host transfer for the whole multi-segment batch: each
        batched family is already a single flat buffer, solo packs of equal
        length concat with it. A lone solo pack is left to its collect();
        coalesced families are host-side already."""
        from ..realtime.device_plane import RealtimeUploadError

        solo = [t for t in tasks if t.got == "pack"]
        fam_keys = list(run.fam_packs)
        if not fam_keys and len(solo) < 2:
            return

        # async dispatch means an in-flight OOM surfaces HERE on
        # error-poisoned buffers: the retry must RE-DISPATCH every pending
        # segment/family after eviction, not re-fetch the dead outputs
        def refetch():
            return fetch_packed_batch(
                [self.tpu.dispatch_plan(t.segment, t.plan) for t in solo]
                + [self.tpu.dispatch_plan_batch(*run.fam_inputs[k],
                                                mesh=run.msig)
                   for k in fam_keys])

        try:
            # where the untraced path waits for the device: queue behind
            # other requests, execution, pack, copy
            with device_fetch():
                fetched = with_oom_retry(
                    lambda: fetch_packed_batch(
                        [t.value for t in solo]
                        + [run.fam_packs[k] for k in fam_keys]),
                    cache=self.tpu.cache, retry_fn=refetch)
        except RealtimeUploadError:
            # double fault: OOM relief dropped the realtime planes
            # mid-query and the re-dispatch's re-upload faulted too. Upload
            # faults must never fail a query — host-execute every
            # still-pending segment instead.
            for task in tasks:
                if task.got in ("pack", "member"):
                    self._host_execute(run, task)
            run.fam_outs.clear()
            return
        for task, raw in zip(solo, fetched):
            task.got, task.value = "arrays", raw
        run.fam_outs.update(zip(fam_keys, fetched[len(solo):]))

    def _decode(self, run: _SegmentRun, tasks: list) -> None:
        """Fetched outputs → intermediates. Vectorized family combine
        (engine/combine.py): dense and un-grouped aggregation families
        decode all members in one pass over the batched arrays; other modes
        slice per member and ride the normal collect()."""
        from .combine import (combine_batched_aggregation,
                              combine_batched_dense)

        vectorized = {"group_by": combine_batched_dense,
                      "aggregation": combine_batched_aggregation}
        for fkey, outs in run.fam_outs.items():
            members = [t for t in tasks
                       if t.got == "member" and t.value[0] == fkey]
            plans = [t.plan for t in members]
            decode = vectorized.get(plans[0].program.mode)
            batched = decode(outs, plans) if decode else None
            for row, task in enumerate(members):
                # else: zero-copy per-segment views of the batched
                # [S, ...] host arrays; collect() consumes them unchanged
                task.got, task.value = ("decoded", batched[row]) \
                    if batched is not None \
                    else ("arrays", [o[row] for o in outs])
        for task in tasks:
            if task.got in ("pack", "arrays", "decoded"):
                run.check()
                self._finish(run, task, *self._timed(
                    task.segment, lambda: self._collect(task)))

    def _collect(self, task: _SegmentTask):
        if task.got == "decoded":
            return task.value
        return with_oom_retry(
            lambda: self.tpu.collect(task.query, task.segment, task.plan,
                                     task.value),
            keep_segment=task.segment, cache=self.tpu.cache,
            retry_fn=lambda: self.tpu.collect(
                task.query, task.segment, task.plan,
                self.tpu.dispatch_plan(task.segment, task.plan)))

    @staticmethod
    def _timed(segment, fn):
        """(fn(), its thread CPU ns), under the segment's span."""
        cpu0 = time.thread_time_ns()
        with TRACING.scope(f"segment:{getattr(segment, 'name', '?')}"):
            inter = fn()
        return inter, time.thread_time_ns() - cpu0

    def _host_timed(self, task: _SegmentTask):
        return self._timed(task.segment, lambda: self.host.execute(
            task.query, task.segment))

    def _host_execute(self, run: _SegmentRun, task: _SegmentTask) -> None:
        self._finish(run, task, *self._host_timed(task))

    def _finish(self, run: _SegmentRun, task: _SegmentTask, inter,
                cpu_ns: int) -> None:
        """What every stage ends a segment with: account its intermediate
        to the query, take a star-tree result back to the outer
        aggregations, store it."""
        if run.tracker is not None:
            run.tracker.add_cpu_ns(cpu_ns)
            GLOBAL_ACCOUNTANT.on_allocation(run.tracker,
                                            _estimate_bytes(inter))
        if task.rewrite is not None:
            inter = self._remap_star_tree(task.rewrite, inter)
        run.intermediates[task.idx], task.got = inter, "done"
        run.done += 1

    def _segment_cache_enabled(self, query: QueryContext) -> bool:
        """Segment partial-result caching is ON by default for the device
        path; ``SET segmentCache = false`` opts a query out and
        PINOT_TPU_SEGMENT_CACHE=0 disables it process-wide. The option is
        checked FIRST so opted-out queries never touch fingerprinting."""
        opt = query.query_options.get("segmentCache")
        if opt is not None and str(opt).lower() in ("false", "0", "off"):
            return False
        from ..cache.partial import partial_cache_enabled

        return partial_cache_enabled()

    def _partial_cache_key(self, run_query, run_segment, rewrite, plan):
        """(program_fp, segment_token) for one routed segment, or None when
        this segment can't participate: star-tree rewrites (the cached
        partial would be pre-remap against a derived view), crc-less
        immutable segments, mutable snapshots without a generation stamp,
        or plans with unfingerprintable state. Generation-stamped realtime
        snapshots DO participate — their token folds (rows, upsert_gen), so
        a new row or upsert flip mints a fresh key and stale partials are
        invalidated by name at commit."""
        if rewrite is not None:
            return None
        from ..cache.keys import program_fingerprint, segment_token

        token = segment_token(run_segment)
        if token is None:
            return None
        fp = program_fingerprint(plan, run_query)
        if fp is None:
            return None
        return (fp, token)

    def _segment_batch_enabled(self, query: QueryContext) -> bool:
        """Stacked segment batching is ON by default; SET segmentBatch =
        false opts a query out (same spelling family as deviceCombine)."""
        return str(query.query_options.get("segmentBatch")).lower() \
            not in ("false", "0", "off")

    def _mesh_sig(self, query: QueryContext) -> tuple:
        """Mesh shape for this query's family dispatches: (ndev,) when the
        sharded path is active, () for solo batching. Part of the batch
        family key so sharded and solo executables cache separately. Mesh
        execution (segment-axis sharding of batch families over the local
        devices) is ON by default when more than one device exists; ``SET
        meshExecution = false`` opts a query out and PINOT_TPU_MESH_DEVICES
        sizes/disables it process-wide."""
        if self.backend == "host" or str(query.query_options.get(
                "meshExecution")).lower() in ("false", "0", "off"):
            return ()
        from ..parallel.mesh import mesh_device_count

        ndev = mesh_device_count()
        return (ndev,) if ndev > 1 else ()

    # device merge ops per sparse AggOp kind (count columns merge like sums)
    _SPARSE_COMBINE_KINDS = {"count": "add", "sum": "add", "sumsq": "add",
                             "min": "min", "max": "max"}

    def _device_merge_takes(self, query: QueryContext, tasks: list,
                            host_tasks: list) -> bool:
        """Whether `_merge_on_device` can take the query: shapes where
        value-space keys are exact. Every kept segment planned as a sparse
        group-by with no star-tree rewrite, one identifier group key over
        an integer dictionary, aggregations the device merges
        (`_SPARSE_COMBINE_KINDS`), all vectorizable; `SET deviceCombine =
        false` opts a query out. The tests for ONE key and an INTEGER
        dictionary are the merge's own (segment-local ids become values on
        the device): `plan._sorted_table_rule` sorts tables of several
        keys and of string keys too, and those run the per-segment stages
        (fetch, decode, `combine_group_arrays`) as a dense table does."""
        import numpy as np

        if len(tasks) < 2 or host_tasks or query.query_options.get(
                "deviceCombine") in (False, "false", 0):
            return False
        p0 = tasks[0].plan.program
        agg_kinds = tuple(a.kind for a in p0.aggs)
        if not agg_kinds or any(k not in self._SPARSE_COMBINE_KINDS
                                for k in agg_kinds):
            return False
        return all(
            t.rewrite is None
            and p.mode == "group_by_sparse"
            and p.group_strides == (1,)
            and len(p.group_slots) == 1
            and not p.group_vexprs
            and p.mv_group_slot is None
            and p.exact_trim == p0.exact_trim
            and tuple(a.kind for a in p.aggs) == agg_kinds
            and t.plan.group_dims
            and np.issubdtype(
                t.plan.group_dims[0].dictionary.values.dtype, np.integer)
            and all(la.vec is not None for la in t.plan.lowered_aggs)
            for t in tasks for p in (t.plan.program,))

    def _merge_on_device(self, run: _SegmentRun, tasks: list, families: list):
        """Server-level merge ON DEVICE for multi-segment single-key sparse
        group-bys: one batch dispatch of the segments' kernels, each key
        column taken to dictionary VALUE space on device (dictionaries are
        segment-local), the S tables merged by one sort that carries the
        columns (kernels.merge_group_tables), and the merged table CUT on
        the device where the ordered server-level trim would cut it
        (combine.trim_group_by: same size, same conditions, the whole
        ORDER BY) — so what crosses to the host is the cut, or the merged
        groups, and never S tables for the host's factorize/scatter merge.
        Two cache tiers of its own (cache/partial.py): the fully merged
        host GroupArrays keyed by the ORDERED per-segment keys — a hit is
        the whole warm repeat with ZERO device dispatches — and per-segment
        value-space tables kept DEVICE-resident against the HBM budget, so
        partial overlap still skips member dispatches. Returns the
        1-element intermediates list, or None to fall back to the
        per-segment stages (any failure here is recoverable — nothing has
        been consumed)."""
        import logging

        from ..cache.partial import GLOBAL_PARTIAL_CACHE

        segs = [t.segment for t in tasks]
        plans = [t.plan for t in tasks]
        keys = [t.cache_key for t in tasks]
        merged_key = None
        if None in keys:
            keys = None
        else:
            # sorted: the sort/edge-reduce merge is order-insensitive, so
            # any segment ordering of the same set may hit
            merged_key = ("sparse_merged",) + tuple(sorted(keys))
            hit = GLOBAL_PARTIAL_CACHE.get(merged_key)
            if hit is not None:
                run.cstats["hit"] += len(segs)
                if run.tracker is not None:
                    GLOBAL_ACCOUNTANT.on_allocation(
                        run.tracker, _estimate_bytes(hit))
                with TRACING.scope("SEGMENT_CACHE(hit:merged)") as sp:
                    if sp is not None:
                        sp.set_attribute("segments", len(segs))
                        sp.set_attribute("cache", "hit")
                        sp.set_attribute("cacheHitBytes",
                                         int(_estimate_bytes(hit)))
                return [hit]
        run.planned()
        try:
            ga, stats = self._sparse_device_combine(
                segs, plans, families, run.msig,
                tuple(self._SPARSE_COMBINE_KINDS[a.kind]
                      for a in plans[0].program.aggs),
                _cut_order(run.query, plans[0]), keys, run.check,
                run.cstats)
        except TimeoutError:
            raise
        except Exception as e:
            # counted like every other engine fallback (perf_ledger's
            # fallbackEvents; chip_smoke.py requires zero)
            from .perf_ledger import PERF_LEDGER

            PERF_LEDGER.note_event("sparse-combine-host")
            logging.getLogger(__name__).warning(
                "sparse device combine failed (%s: %s); host merge "
                "fallback", type(e).__name__, e)
            return None
        if merged_key is not None:
            GLOBAL_PARTIAL_CACHE.put(
                merged_key, ga,
                tuple(getattr(s, "name", "?") for s in segs))
        if run.tracker is not None:
            GLOBAL_ACCOUNTANT.on_allocation(run.tracker, _estimate_bytes(ga))
        run.rt_device = any(getattr(s, "is_mutable", False) for s in segs)
        run.cstats.update(stats)
        return [ga]

    def _sparse_device_combine(self, segs, plans, families, msig, kinds,
                               cut, keys, check, cstats):
        """The device half of `_merge_on_device`: (merged
        GroupArrays, what the device merge counted). `cut` is the trim the
        device may apply (`_cut_order`) or None. Raises on anything it
        cannot do; the caller falls back."""
        import numpy as np

        from ..ops import kernels
        from .combine import DEFAULT_TRIM_THRESHOLD
        from .plan import DEFAULT_NUM_GROUPS_LIMIT
        from .results import GroupArrays

        column = plans[0].group_dims[0].column
        dicts = [np.asarray(pl.group_dims[0].dictionary.values)
                 for pl in plans]
        filled = [d for d in dicts if len(d)]
        lowest = min((int(d[0]) for d in filled), default=0)
        highest = max((int(d[-1]) for d in filled), default=0)
        # 64-bit sorts are emulated on the chip: merge on int32 keys
        # wherever every dictionary's values fit (below the sentinel)
        key32 = -(1 << 31) <= lowest and highest < (1 << 31) - 1
        p0 = plans[0].program
        slots = p0.num_groups
        # a per-segment table in value space is kept on the device for a
        # later request only at the sizes numGroupsLimit used to give: a
        # table that holds a whole dictionary is four planes of a million
        # slots a segment, for a hit that needs the same statement again
        # over another set of segments
        tabs_on = keys is not None and slots <= DEFAULT_NUM_GROUPS_LIMIT
        cached_tabs: dict = {}
        if tabs_on:
            for i, k in enumerate(keys):
                tab = self.tpu.cache.get_partial(("sparse_tab",) + k)
                if tab is not None:
                    cached_tabs[i] = tab
        # one vmapped dispatch per batch family; its outputs stay on the
        # device with their leading [S] dim (the merged table below is
        # the only D2H transfer)
        chunks: list = []  # (member positions, outs with a leading [S])
        for fkey, positions in families:
            positions = [i for i in positions if i not in cached_tabs]
            if not positions:
                continue
            if fkey is not None and len(positions) > 1:
                try:
                    # same batched-OOM discipline as _dispatch_family: a
                    # transient OOM gets one eviction+retry, a persistent
                    # one (or a family-key drift) falls back to the 1x-
                    # footprint per-segment dispatch loop below instead
                    # of abandoning the device combine entirely
                    # (mesh-sharded dispatches arrive gathered to
                    # device 0 so the table merge below colocates)
                    outs_b, _views = with_oom_retry(
                        lambda: self.tpu.dispatch_plan_batch_raw(
                            [segs[i] for i in positions],
                            [plans[i] for i in positions], mesh=msig),
                        keep_segment=segs[positions[0]],
                        cache=self.tpu.cache)
                except (BatchFamilyMismatch, HbmExhaustedError):
                    pass
                else:
                    chunks.append((positions, tuple(outs_b)))
                    continue
            for i in positions:
                outs, _view = self.tpu.dispatch_plan_raw(segs[i], plans[i])
                chunks.append(([i], tuple(o[None] for o in outs)))
        tables, how = [], []
        done = 0
        for positions, outs in chunks:
            check(done)
            done += len(positions)
            members = [dicts[i] for i in positions]
            if all(len(d) and int(d[-1]) - int(d[0]) == len(d) - 1
                   for d in members):
                # dictionaries of consecutive integers: value = first + id
                source = np.asarray([d[0] for d in members], dtype=np.int64)
                how.append("base")
            else:
                source = self._dict_plane_stack(
                    [segs[i] for i in positions], column)
                how.append("plane")
            tables.append((outs[-1], source, outs[0], tuple(outs[1:-1])))
            if keys is not None:
                cstats["miss"] += len(positions)
            if tabs_on:
                values = kernels.table_keys_to_values(
                    outs[-1], source, how=how[-1])
                for row, i in enumerate(positions):
                    self.tpu.cache.put_partial(
                        ("sparse_tab",) + keys[i],
                        (values[row], outs[0][row])
                        + tuple(o[row] for o in outs[1:-1]),
                        segment_name=getattr(segs[i], "name", "?"))
        for i, tab in sorted(cached_tabs.items()):
            tables.append((tab[0][None], None, tab[1][None],
                           tuple(t[None] for t in tab[2:])))
            how.append("values")
            cstats["hit"] += 1
        # what may cross blind: the merged groups are at most the slots
        # merged and at most the integers between the dictionaries' ends
        bound = min(len(segs) * slots, highest - lowest + 1)
        order, trim_size, threshold = None, 0, 0
        cut_slots = table_slots = 0
        if cut is not None and bound > max(cut[1], cut[2]):
            order, trim_size, threshold = cut
            cut_slots = max(kernels.MIN_CUT_SLOTS,
                            1 << (trim_size - 1).bit_length())
        elif bound <= DEFAULT_TRIM_THRESHOLD:
            table_slots = table_bucket(bound)
        label = "merge_" + program_label(p0)

        def merged(**size):
            header, table = kernels.merge_group_tables(
                tuple(tables), np.int64(trim_size), np.int64(threshold),
                how=tuple(how), key32=key32, kinds=kinds, order=order,
                **size)
            return unpack_outputs(
                kernels.pack_outputs((header,) + tuple(table), label))

        with device_fetch():
            # one flat D2H transfer for the whole query: the header and
            # the cut, or the header and the merged table
            header, *table_np = merged(cut_slots=cut_slots,
                                       table_slots=table_slots)
            groups, combined, scanned, trash, is_cut = (
                int(x) for x in header)
            if is_cut:
                groups = trim_size
            elif not table_slots:
                # not cut after all (few groups, or a column the device
                # cannot rank exactly), or too large a table to fetch
                # blind: merged again into a table of the size the header
                # gives, and fetched
                _, *table_np = merged(
                    cut_slots=0, table_slots=max(
                        1 << 10, 1 << max(0, groups - 1).bit_length()))
        gids = np.arange(groups)
        dim = plans[0].group_dims[0]
        key_col = table_np[-1][:groups].astype(dim.dictionary.values.dtype,
                                               copy=False)
        las = plans[0].lowered_aggs
        ga = GroupArrays(
            [key_col],
            [la.vec.extract(table_np, gids) for la in las],
            [la.vec.spec for la in las],
            [la.vec.fin_tag for la in las],
            num_docs_scanned=scanned,
            groups_trimmed=trash > 0 and not p0.exact_trim)
        return ga, {"groups_combined": combined,
                    "device_cut": trim_size if is_cut else 0}

    def _dict_plane_stack(self, segs: list, column: str):
        """The segments' dictionary-values planes of `column` as one
        (S, D) device array, zero-padded to the longest's bucket (pads are
        never gathered) and kept with the family's other stacks."""
        import jax.numpy as jnp

        planes = [self.tpu._view_for(s).dict_values(column) for s in segs]
        width = table_bucket(max(p.shape[0] for p in planes))

        def build():
            return jnp.stack([
                p if p.shape[0] == width
                else jnp.pad(p, (0, width - p.shape[0])) for p in planes])

        pkey = ((column, "dict"), str(planes[0].dtype), (width,))
        return self.tpu.cache.stacked_view(segs).plane(pkey, build)

    def _segment_route(self, query: QueryContext, segment):
        rewrite = None
        # star-tree pre-aggregates ignore upsert validity → not applicable
        if self.use_star_tree and getattr(segment, "valid_doc_ids", None) is None:
            from ..segment.startree import try_rewrite

            rewrite = try_rewrite(query, segment)
        if rewrite is not None:
            return rewrite.query, rewrite.view, rewrite
        return query, segment, None

    @staticmethod
    def _remap_star_tree(rewrite, result):
        """Inner (pre-agg) states → outer aggregation states; scanned-doc
        count reflects pre-agg rows read (the star-tree speedup is visible
        in numDocsScanned, same as the reference)."""
        from ..segment.startree import remap_states

        if isinstance(result, GroupByIntermediate):
            return GroupByIntermediate(
                {k: remap_states(rewrite, v) for k, v in result.groups.items()},
                result.num_docs_scanned,
            )
        if isinstance(result, AggIntermediate):
            return AggIntermediate(remap_states(rewrite, result.states),
                                   result.num_docs_scanned)
        return result

    def _combine(self, query: QueryContext, intermediates):
        from .combine import combine_group_arrays
        from .results import GroupArrays

        semantics = [semantics_for(a) for a in query.aggregations]
        first = intermediates[0] if intermediates else None
        if (isinstance(first, GroupArrays)
                and all(isinstance(im, GroupArrays) for im in intermediates)):
            merged = combine_group_arrays(intermediates)
            if merged is not None:
                return trim_group_by(merged, query, semantics)
        if isinstance(first, GroupByIntermediate):
            return trim_group_by(combine_group_by(intermediates, semantics),
                                 query, semantics)
        if isinstance(first, AggIntermediate):
            return combine_aggregation(intermediates, semantics)
        if isinstance(first, SelectionIntermediate):
            return combine_selection(intermediates)
        # no segments: shape an empty intermediate from the query
        if query.is_aggregation_query and not query.is_group_by and not query.distinct:
            return AggIntermediate([])
        if query.is_group_by or query.distinct or query.is_aggregation_query:
            return GroupByIntermediate({})
        return SelectionIntermediate([e.identifier for e in query.select_expressions if e.is_identifier], [])
