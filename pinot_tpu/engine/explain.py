"""EXPLAIN PLAN FOR — single-stage engine.

Reference: pinot-core's EXPLAIN output (ExplainPlanDataTableReducer et al.)
renders an operator tree as (Operator, Operator_Id, Parent_Id) rows:
BROKER_REDUCE → COMBINE → per-segment plan operators. Here the per-segment
"operators" are the kernel IR the query compiles to — one fused device
program — so the tree shows the program mode, the lowered filter algebra,
group dims/strides, and the primitive device reductions, plus which
segments pruned and whether the shape falls back to the host engine.
"""

from __future__ import annotations

from . import ir
from .aggregation import UnsupportedQueryError
from .plan import SegmentPlanner
from .results import DataSchema, ResultTable


def explain_plan(query, table, pruner, backend: str = "auto",
                 use_star_tree: bool = True) -> ResultTable:
    import copy

    from ..query.optimizer import optimize_filter

    # explain what EXECUTES: the same canonicalized filter the executor
    # runs (NOT elimination, EQ/IN + range merging, constant folding)
    query = copy.copy(query)
    query.filter = optimize_filter(query.filter)

    rows: list[list] = []
    next_id = [0]

    def add(op: str, parent: int) -> int:
        oid = next_id[0]
        next_id[0] += 1
        rows.append([op, oid, parent])
        return oid

    ob = ""
    if query.order_by_expressions:
        ob = ", sort:[" + ", ".join(map(str, query.order_by_expressions)) + "]"
    having = f", having:{query.having_filter}" if query.having_filter else ""
    root = add(f"BROKER_REDUCE(limit:{query.limit}{ob}{having})", -1)

    segments = [s for s in table.segments
                if not getattr(s, "is_mutable", False)]
    kept, pruned = pruner.prune(query, segments) if segments else ([], 0)
    mutable = len(table.segments) - len(segments)

    if query.is_aggregation_query or query.is_group_by or query.distinct:
        combine = "COMBINE_GROUP_BY" if (query.is_group_by or query.distinct) \
            else "COMBINE_AGGREGATE"
    else:
        combine = "COMBINE_SELECT"
    cid = add(f"{combine}(segments:{len(kept)}, pruned:{pruned}"
              + (f", consuming(host):{mutable}" if mutable else "") + ")",
              root)

    if not kept:
        add("EMPTY(no immutable segments matched)", cid)
        return _table(rows)

    # mirror _segment_route: star-tree rewrite happens before planning
    plan_query, plan_seg = query, kept[0]
    star = None
    if use_star_tree and getattr(kept[0], "valid_doc_ids", None) is None:
        from ..segment.startree import try_rewrite

        star = try_rewrite(query, kept[0])
        if star is not None:
            plan_query, plan_seg = star.query, star.view
            cid = add("FILTER_STARTREE_INDEX(pre-aggregated docs)", cid)

    try:
        plan = SegmentPlanner(plan_query, plan_seg).plan()
    except UnsupportedQueryError as e:
        add(f"HOST_ENGINE(numpy fallback: {e})", cid)
        return _table(rows)

    engine = "HOST_KERNEL" if backend == "host" else "DEVICE_KERNEL"
    p = plan.program
    desc = f"{engine}(mode:{p.mode}"
    if p.mode in ("group_by", "group_by_sparse"):
        dims = ", ".join(f"{d.column}[card:{d.cardinality}]"
                         for d in plan.group_dims)
        desc += f", groups:{p.num_groups}, dims:[{dims}]"
        # the concrete group-by kernel variant: dense segment_sum table,
        # MXU one-hot matmul, or one of the sparse sort strategies
        # (ir.sparse_groupby_path mirrors the kernel's branch)
        path = "dense"
        if p.mode == "group_by_sparse":
            path = ir.sparse_groupby_path(p)
            desc += f", key_space:{p.key_space}"
            if p.exact_trim:
                desc += ", orderByTrim:exact"
        if p.mv_group_slot is not None:
            desc += ", mvExpansion:true"
        if backend != "host":
            from ..ops import fused_groupby

            if fused_groupby.plan(p, None) is not None:
                # single-pass MXU kernel shape (ops/fused_groupby.py);
                # actual use still depends on plane dtypes + backend
                desc += ", fusedMxu:eligible"
                if p.mode == "group_by":
                    path = "mxu"
        desc += f", path:{path}"
        if getattr(query, "explain", False) == "implementation" \
                and plan.group_table_reason:
            desc += f", why:{plan.group_table_reason}"
    kid = add(desc + ")", cid)

    if getattr(query, "explain", False) == "implementation" and \
            p.mode in ("group_by", "group_by_sparse"):
        # implementation mode also names HOW the per-segment tables merge:
        # the sparse device concat+edge-reduce, the columnar factorize/
        # scatter merge, or the per-group dict merge fallback
        import numpy as np

        vec_ok = all(la.vec is not None for la in plan.lowered_aggs)
        if (p.mode == "group_by_sparse" and backend != "host"
                and len(kept) > 1 and p.group_strides == (1,)
                and len(p.group_slots) == 1 and plan.group_dims and vec_ok
                and np.issubdtype(
                    plan.group_dims[0].dictionary.values.dtype, np.integer)):
            impl = "device-sparse(concat+edge-reduce)"
        elif vec_ok:
            impl = "host-columnar-scatter"
        else:
            impl = "host-dict-merge"
        add(f"SERVER_COMBINE(impl:{impl}, segments:{len(kept)})", cid)

    if getattr(query, "explain", False) == "implementation" and \
            backend != "host" and len(kept) > 1:
        # stacked segment batching: families = device dispatches
        from .executor import batch_families

        if str(query.query_options.get("segmentBatch")).lower() in (
                "false", "0", "off"):
            add("SEGMENT_BATCH(disabled)", cid)
        else:
            members: list = []  # (segment, plan)
            for seg in kept:
                pq, ps = query, seg
                if use_star_tree and getattr(
                        seg, "valid_doc_ids", None) is None:
                    from ..segment.startree import try_rewrite

                    st = try_rewrite(query, seg)
                    if st is not None:
                        pq, ps = st.query, st.view
                try:
                    pl = SegmentPlanner(pq, ps).plan()
                except UnsupportedQueryError:
                    continue
                members.append((ps, pl))
            if members:
                # the dispatcher's own grouping (sorted tables of one
                # query are sized alike before they are grouped)
                _, fams = batch_families(members)
                add(f"SEGMENT_BATCH(families:{len(fams)}, "
                    f"segments:{len(members)})", cid)

    for a in query.aggregations:
        # SQL-level functions; COUNT(*) answers from the shared per-group
        # count column and registers no primitive op of its own
        add(f"AGGREGATE(fn:{a})", kid)
    reduce_tag = "HOST_REDUCE" if backend == "host" else "DEVICE_REDUCE"
    for agg in p.aggs:
        label = f"{reduce_tag}(op:{agg.kind}"
        if agg.card is not None:
            label += f", card:{agg.card}"
        if agg.bins is not None:
            label += f", bins:{agg.bins}"
        if agg.vmin is not None:
            label += f", bounds:[{agg.vmin},{agg.vmax}]"
        add(label + ")", kid)
    if not p.aggs and p.mode == "selection":
        cols = ", ".join(str(e) for e in query.select_expressions)
        add(f"SELECT(columns:[{cols}])", kid)

    fid = add("FILTER" if p.filter is not None else "MATCH_ALL", kid)
    if p.filter is not None:
        _walk_filter(p.filter, fid, add)
    return _table(rows)


# span attributes rendered on EXPLAIN ANALYZE nodes, in display order;
# everything else (HBM gauge snapshots, internals) stays in trace_info
_ANALYZE_ATTRS = ("segment", "numSegments", "segments", "device",
                  "meshDevices", "mode", "padded",
                  "fused", "workers", "leaf_pushdown", "rows_in", "rows_out",
                  "shuffled_rows", "shuffled_bytes", "join_impl",
                  "cross_stage_bytes", "device_partition_ms",
                  "host_crossings", "program", "compileMs",
                  "hostFetches", "fetchBytes", "transferBytes",
                  "cache")


def _cache_outcome(resp) -> str:
    """One word for the run's cache behaviour: broker result-cache outcome
    when known, else the segment-cache hit/miss counters."""
    outcome = getattr(resp, "cache_outcome", None)
    if outcome == "hit":
        return "hit"
    hits = getattr(resp, "num_segments_cache_hit", 0)
    misses = getattr(resp, "num_segments_cache_miss", 0)
    if hits and not misses and not getattr(resp, "num_device_dispatches", 0):
        return "hit"
    if hits and misses:
        return "partial"
    if misses:
        return "miss"
    if hits:
        return "hit"
    # no segment-cache traffic at all: report the broker result-cache
    # outcome (a cacheable run that missed is "miss", bypass is "off")
    return "miss" if outcome == "miss" else "off"


def analyze_table(trace_json: list, resp, table_name: str = "") -> ResultTable:
    """Render an executed run's span tree as the (Operator, Operator_Id,
    Parent_Id) plan table, each node annotated with its observed stats —
    the EXPLAIN ANALYZE product. Works on both the engine-local trace
    (integer span ids) and the broker's merged cross-server trace
    (ids namespaced ``instance:id``); spans whose parent is missing attach
    to the root so a partial trace still renders one connected tree."""
    rows: list[list] = []
    next_id = [0]

    def add(op: str, parent: int) -> int:
        oid = next_id[0]
        next_id[0] += 1
        rows.append([op, oid, parent])
        return oid

    n_rows = len(resp.result_table.rows) if getattr(
        resp, "result_table", None) is not None else 0
    parts = [f"table:{table_name}"] if table_name else []
    parts += [f"rows:{n_rows}",
              f"timeMs:{round(getattr(resp, 'time_used_ms', 0.0), 3)}",
              f"docsScanned:{getattr(resp, 'num_docs_scanned', 0)}",
              f"segments:{getattr(resp, 'num_segments_processed', 0)}",
              f"dispatches:{getattr(resp, 'num_device_dispatches', 0)}",
              f"compiles:{getattr(resp, 'num_compiles', 0)}",
              f"cacheHit:{getattr(resp, 'num_segments_cache_hit', 0)}",
              f"cacheMiss:{getattr(resp, 'num_segments_cache_miss', 0)}",
              f"cache:{_cache_outcome(resp)}"]
    if getattr(resp, "num_hedged_requests", 0):
        parts.append(f"hedged:{resp.num_hedged_requests}")
    if getattr(resp, "num_scatter_retries", 0):
        parts.append(f"retries:{resp.num_scatter_retries}")
    if getattr(resp, "num_coalesced_queries", 0):
        parts.append(f"coalescedWith:{resp.num_coalesced_queries}")
        parts.append(
            f"coalesceWaitMs:{round(getattr(resp, 'coalesce_wait_ms', 0.0), 3)}")
    root = add("EXPLAIN_ANALYZE(" + ", ".join(parts) + ")", -1)

    by_span: dict = {}  # trace spanId -> plan row id
    for s in trace_json:
        label = s.get("operator", "?")
        bits = []
        attrs = s.get("attributes") or {}
        for k in _ANALYZE_ATTRS:
            if k in attrs:
                bits.append(f"{k}:{attrs[k]}")
        bits.append(f"ms:{s.get('durationMs', 0.0)}")
        server = s.get("server")
        if server:
            label = f"{server}/{label}"
        parent = by_span.get(s.get("parentId"), root)
        by_span[s.get("spanId")] = add(
            label + "(" + ", ".join(bits) + ")", parent)
    if not trace_json:
        if getattr(resp, "cache_outcome", None) == "hit":
            # broker result-cache hit: nothing executed, no spans — the
            # whole answer came from the cache tier
            add(f"RESULT_CACHE(hit, rows:{n_rows}, dispatches:0)", root)
        else:
            add("NO_TRACE(execution recorded no spans)", root)
    return _table(rows)


def _walk_filter(node, parent: int, add) -> None:
    if isinstance(node, ir.FAnd):
        oid = add("AND", parent)
        for c in node.children:
            _walk_filter(c, oid, add)
    elif isinstance(node, ir.FOr):
        oid = add("OR", parent)
        for c in node.children:
            _walk_filter(c, oid, add)
    elif isinstance(node, ir.FNot):
        oid = add("NOT", parent)
        _walk_filter(node.child, oid, add)
    elif isinstance(node, ir.Interval):
        add(f"RANGE(slot dict-id/value interval, "
            f"inclusive:[{node.lo_inclusive},{node.hi_inclusive}])", parent)
    elif isinstance(node, ir.Lut):
        add(f"DICT_LUT(ids_slot:{node.ids_slot}, mv:{node.mv})", parent)
    elif isinstance(node, ir.Isin):
        add("RAW_IN", parent)
    elif isinstance(node, ir.Null):
        add(f"IS_NULL(slot:{node.null_slot})", parent)
    elif isinstance(node, ir.MaskParam):
        add("HOST_INDEX_MASK(text/json/vector posting list)", parent)
    elif isinstance(node, ir.FConst):
        add(f"CONST({node.value})", parent)
    else:
        add(type(node).__name__.upper(), parent)


def _table(rows) -> ResultTable:
    return ResultTable(
        DataSchema(["Operator", "Operator_Id", "Parent_Id"],
                   ["STRING", "INT", "INT"]), rows)
