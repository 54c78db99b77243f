"""MSE facade: SQL → stage DAG → BrokerResponse.

Reference analogue: MultiStageBrokerRequestHandler + QueryDispatcher
(pinot-query-runtime/.../service/dispatch/QueryDispatcher.java:126 —
submitAndReduce) collapsed into one in-process entry point, the same
topology the reference uses in its own in-process MSE tests
(QueryRunnerTestBase).
"""

from __future__ import annotations

import re
import time
from typing import Optional

import numpy as np

from ..cache.keys import mse_plan_fingerprint, segment_token
from ..cache.results import BrokerResultCache, result_cache_enabled
from ..engine.results import BrokerResponse, DataSchema, ResultTable
from ..spi.trace import TRACING
from .fragmenter import explain_stages, fragment
from .logical import LogicalPlanner, prune_columns
from .optimizer import push_filters
from .mailbox import Block, block_len
from .parser import parse_relational
from .runtime import StageRunner


class MultistageExecutor:
    """Runs the multi-stage dialect over a single-stage QueryExecutor's
    table registry (engine/query_executor.py)."""

    def __init__(self, query_executor, parallelism: int = 2):
        self.qe = query_executor
        self.parallelism = parallelism
        # stage-plan result cache (the MSE analogue of the broker tier):
        # keyed by (plan fingerprint, every scanned segment's (name, crc)),
        # so segment replacement/refresh self-invalidates through the crc
        # with no epoch plumbing. The executor instance is persistent
        # (engine/query_executor.py caches it), so warm repeats of a join
        # query skip the runner entirely.
        self.result_cache = BrokerResultCache()

    def _cache_key(self, stages, options) -> Optional[tuple]:
        """None = uncacheable (unfingerprintable plan, missing table,
        mutable or crc-less segment). Computed AFTER the resultCache
        option gate so opted-out queries never pay a fingerprint."""
        fp = mse_plan_fingerprint(stages, options, self.parallelism)
        if fp is None:
            return None
        toks = []
        for st in stages:
            if st.root is None:
                continue
            for scan in st.scans():
                t = self.qe.tables.get(scan.table)
                if t is None:
                    return None
                for seg in list(t.segments):
                    tok = segment_token(seg)
                    if tok is None:
                        return None
                    toks.append((scan.table,) + tok)
        return (fp, tuple(sorted(toks)))

    # -- catalog -----------------------------------------------------------
    def _catalog(self) -> dict[str, list[str]]:
        return {name: t.schema.column_names()
                for name, t in self.qe.tables.items()}

    def _partition_catalog(self) -> dict[str, dict]:
        """table → {column: (pfunc, n_partitions)} where EVERY segment is
        stamped with the same function/count (reference: the broker's
        TablePartitionInfo is computed the same way — from per-segment
        ColumnPartitionMetadata, invalidated on any inconsistent segment)."""
        out: dict[str, dict] = {}
        for name, t in self.qe.tables.items():
            segs = list(t.segments)
            if not segs:
                continue
            per_col: dict[str, tuple] = {}
            for col in t.schema.column_names():
                infos = set()
                for seg in segs:
                    meta = getattr(seg, "metadata", None)
                    m = meta.columns.get(col) if meta is not None else None
                    if m is None or not getattr(m, "partition_function", None) \
                            or not getattr(m, "num_partitions", None):
                        infos = None
                        break
                    infos.add((m.partition_function, m.num_partitions))
                if infos and len(infos) == 1:
                    per_col[col] = next(iter(infos))
            if per_col:
                out[name] = per_col
        return out

    def _read_table(self, table: str, columns: list[str]) -> dict[str, np.ndarray]:
        t = self.qe.tables.get(table)
        if t is None:
            raise KeyError(f"table {table} not found")
        out: dict[str, list] = {c: [] for c in columns}
        for seg in list(t.segments):
            view = seg.snapshot_view() if getattr(seg, "is_mutable", False) else seg
            vd = getattr(view, "valid_doc_ids", None)
            keep = vd.mask(view.num_docs) if vd is not None else None
            for c in columns:
                vals = np.asarray(view.get_values(c))
                out[c].append(vals if keep is None else vals[keep])
        result = {}
        for c, parts in out.items():
            if not parts:
                result[c] = np.empty(0)
            elif len(parts) == 1:
                result[c] = parts[0]
            else:
                if any(p.dtype.kind == "O" for p in parts):
                    parts = [p.astype(object) for p in parts]
                result[c] = np.concatenate(parts)
        return result

    # -- entry -------------------------------------------------------------
    def execute_sql(self, sql: str) -> BrokerResponse:
        t0 = time.perf_counter()
        trace = None
        try:
            query = parse_relational(sql)
            # the MSE entry owns the span tree: stage spans (runtime.py)
            # and nested leaf-engine dispatch spans all join this trace.
            # EXPLAIN ANALYZE arms it unconditionally — the annotated plan
            # IS the trace (a trace changes nothing of the run).
            analyze = query.explain == "analyze"
            if (analyze or query.options.get("trace") in (True, "true", 1)) \
                    and TRACING.active_trace() is None:
                trace = TRACING.start_trace(f"mse:{id(query):x}")
            planner = LogicalPlanner(query, self._catalog(),
                                     partition_catalog=self._partition_catalog)
            plan = planner.plan()
            plan = push_filters(plan)
            prune_columns(plan)
            stages = fragment(plan)
            if query.explain is True:
                text = explain_stages(stages)
                return BrokerResponse(
                    result_table=ResultTable(
                        DataSchema(["plan"], ["STRING"]),
                        [[line] for line in text.split("\n")]),
                    time_used_ms=(time.perf_counter() - t0) * 1000)
            cache_key = None
            if query.explain is False and trace is None \
                    and result_cache_enabled() \
                    and not _option_false(query.options, "resultCache"):
                cache_key = self._cache_key(stages, query.options)
            if cache_key is not None:
                cached = self.result_cache.get(cache_key)
                if cached is not None:
                    # bit-identical rows, zero dispatches: restamp only the
                    # per-request fields on the shallow copy
                    cached.cache_outcome = "hit"
                    cached.num_device_dispatches = 0
                    cached.num_compiles = 0
                    cached.time_used_ms = (time.perf_counter() - t0) * 1000
                    return cached
            from .operators import pop_join_overflow

            pop_join_overflow()  # clear any stale flag on this thread
            runner = StageRunner(
                stages, self.parallelism, self.qe.execute, self._read_table,
                query_options=query.options,
                execute_columnar=getattr(self.qe, "execute_selection_columnar",
                                         None))
            block = runner.run()
            if query.explain == "implementation":
                # the query RAN; the plan text carries each stage's
                # measured rows/bytes/time
                text = explain_stages(stages, runner.stage_stats)
                return BrokerResponse(
                    result_table=ResultTable(
                        DataSchema(["plan"], ["STRING"]),
                        [[line] for line in text.split("\n")]),
                    time_used_ms=(time.perf_counter() - t0) * 1000)
            schema = stages[0].root.schema
            result = _block_to_result(block, schema)
            resp = BrokerResponse(
                result_table=result,
                num_docs_scanned=runner.stats["num_docs_scanned"],
                total_docs=runner.stats["total_docs"],
                partial_result=pop_join_overflow()
                or bool(runner.stats.get("join_overflow")),
                num_groups_limit_reached=runner.stats.get(
                    "num_groups_limit_reached", False),
                num_device_dispatches=runner.stats.get(
                    "num_device_dispatches", 0),
                num_compiles=runner.stats.get("num_compiles", 0),
                mse_stage_stats=runner.stage_stats,
                time_used_ms=(time.perf_counter() - t0) * 1000)
            if cache_key is not None:
                resp.cache_outcome = "miss"
                if not resp.partial_result:
                    self.result_cache.put(cache_key, resp)
            if trace is not None:
                resp.trace_info = trace.to_json()
            if analyze:
                from ..engine.explain import analyze_table

                resp.result_table = analyze_table(
                    resp.trace_info or [], resp)
            return resp
        except Exception as e:
            return BrokerResponse(
                exceptions=[f"{type(e).__name__}: {e}"],
                time_used_ms=(time.perf_counter() - t0) * 1000)
        finally:
            if trace is not None:
                TRACING.end_trace()


def _option_false(options: dict, name: str) -> bool:
    for k, v in (options or {}).items():
        if str(k).lower() == name.lower():
            return v is False or str(v).lower() in ("0", "false", "off")
    return False


def _block_to_result(block: Block, schema: list[str]) -> ResultTable:
    n = block_len(block)
    cols = []
    types = []
    for name in schema:
        v = np.asarray(block.get(name, np.empty(0)))
        cols.append(v)
        types.append(_np_type(v))
    rows = []
    for i in range(n):
        rows.append([_py(c[i]) for c in cols])
    return ResultTable(DataSchema([_display(s) for s in schema], types), rows)


def _np_type(v: np.ndarray) -> str:
    k = v.dtype.kind
    if k == "b":
        return "BOOLEAN"
    if k in "iu":
        return "LONG"
    if k == "f":
        return "DOUBLE"
    return "STRING"


def _py(v):
    if isinstance(v, np.generic):
        v = v.item()
    if isinstance(v, float) and np.isnan(v):
        return None
    return v


_QUALIFIED_RE = re.compile(r"[A-Za-z_][\w$]*(?:\.[A-Za-z_][\w$]*)+")


def _display(name: str) -> str:
    """Qualified plain identifiers render unqualified in the response header
    (reference: MSE result headers use the field name, not `table.field`);
    expression strings pass through untouched."""
    if _QUALIFIED_RE.fullmatch(name):
        return name.rsplit(".", 1)[-1]
    return name
