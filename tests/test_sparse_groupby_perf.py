"""Perf-structure guards for the sparse group-by fast paths (ISSUE 2).

These tests pin the SHAPE of the compiled program, not its timings, so CI
catches a regression that silently reintroduces the O(n log n) sort or the
full-payload sort without any flaky wall-clock assertions:

  * the presorted path (keys_presorted=True) must compile to a jaxpr that
    never sorts the ROWS BY KEY — the whole point of the fast path;
  * the sort-iota path must sort exactly (sort keys + iota32), never the
    payload columns: the one grouping `sort` eqn carries num_sort_keys + 1
    operands regardless of how many aggregation payloads ride the query;
  * every path moves its group-ending rows to the table's first slots by
    ONE unstable sort keyed by the row index (kernels._GroupEnds; since
    PR 30, in place of a binary search of log2(n) gathers a slot); the
    sorted path has it twice, on either side of a `lax.cond`: over the
    first eighth of the rows where the filter kept no more, or over all.
"""

from __future__ import annotations

import numpy as np
import pytest

from pinot_tpu.engine.plan import SegmentPlanner
from pinot_tpu.ops.kernels import _run_program_impl
from pinot_tpu.query.parser.sql import parse_sql
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.device_cache import SegmentDeviceView
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema

SCHEMA = Schema.build(
    "perfguard",
    dimensions=[("k", "INT"), ("d", "INT")],
    metrics=[("v1", "LONG"), ("v2", "LONG")],
)
N = 4096
N_KEYS = 64


def _build(tmp_path, sort_keys: bool):
    rng = np.random.default_rng(7)
    k = rng.integers(0, N_KEYS, N).astype(np.int32)
    if sort_keys:
        k = np.sort(k)
    cols = {
        "k": k,
        "d": rng.integers(0, 8, N).astype(np.int32),
        "v1": rng.integers(0, 1000, N).astype(np.int64),
        "v2": rng.integers(0, 1000, N).astype(np.int64),
    }
    name = "sorted" if sort_keys else "shuffled"
    SegmentBuilder(SCHEMA, segment_name=name).build(cols, str(tmp_path / name))
    return load_segment(str(tmp_path / name))


def _jaxpr_for(segment, sql):
    """Plan the query against the segment and trace the kernel body."""
    import jax

    query = parse_sql(sql)
    plan = SegmentPlanner(query, segment).plan()
    view = SegmentDeviceView(segment)
    arrays = plan.gather_arrays(view)
    params = tuple(p if isinstance(p, (np.ndarray, np.generic))
                   else np.asarray(p) for p in plan.params)

    def fn(arrays, params):
        return _run_program_impl(plan.program, arrays, params,
                                 np.int32(segment.num_docs), view.padded)

    return plan.program, jax.make_jaxpr(fn)(arrays, params)


def _sort_eqns(jaxpr):
    """All `sort` eqns in the jaxpr, recursing into sub-jaxprs, each with
    the {var: producing eqn} map of the jaxpr it stands in."""
    found = []

    def walk(j):
        made = {v: e for e in j.eqns for v in e.outvars}
        for eqn in j.eqns:
            if eqn.primitive.name == "sort":
                found.append((eqn, made))
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    walk(sub)

    walk(jaxpr.jaxpr)
    return found


def _is_row_index(var, made, depth: int = 4) -> bool:
    """Whether `var` holds the row index (an iota), as it is or with a
    sentinel selected in: `jnp.where(flag, arange(n), I32_MAX)`. Only the
    VALUES of a select are followed, never its predicate (every mask comes
    from an iota compared with the doc count)."""
    eqn = made.get(var)
    if eqn is None or depth == 0:
        return False
    name = eqn.primitive.name
    if name == "iota":
        return True
    if name == "select_n" or (name in ("pjit", "jit")
                              and eqn.params.get("name") == "_where"):
        return any(_is_row_index(v, made, depth - 1)
                   for v in eqn.invars[1:] if not hasattr(v, "val"))
    if name in ("convert_element_type", "broadcast_in_dim"):
        return _is_row_index(eqn.invars[0], made, depth - 1)
    return False


def _table_sorts(jaxpr):
    """The sorts that move group-ending rows to the table's front: ONE key,
    the row index (kernels._flagged_to_front), which carries the columns."""
    return [e for e, made in _sort_eqns(jaxpr)
            if e.params["num_keys"] == 1 and _is_row_index(e.invars[0], made)]


def _grouping_sorts(jaxpr):
    """The sorts that order the ROWS: every sort that is not led by the row
    index, stable or not (a sort by key is what the presorted path must
    never lower)."""
    table = {id(e) for e in _table_sorts(jaxpr)}
    return [e for e, _ in _sort_eqns(jaxpr) if id(e) not in table]


def _subjaxprs(v):
    if hasattr(v, "jaxpr"):  # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):  # raw Jaxpr
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)


# force the sparse kernel on the tiny (dense-eligible) test cardinality
FORCE = "SET sparseGroupBy = true; "


def test_presorted_path_compiles_with_zero_sorts(tmp_path):
    seg = _build(tmp_path, sort_keys=True)
    program, jaxpr = _jaxpr_for(
        seg, FORCE + "SELECT k, SUM(v1), COUNT(*) FROM perfguard "
                     "GROUP BY k LIMIT 1000")
    assert program.mode == "group_by_sparse"
    assert program.keys_presorted
    eqns = _grouping_sorts(jaxpr)
    assert eqns == [], (
        f"presorted fast path must not sort the rows by key, "
        f"found {len(eqns)} such sorts")
    table = _table_sorts(jaxpr)
    assert len(table) == 1
    # row index | sentinel, key, running count, the SUM's prefix
    assert len(table[0].invars) == 4 and table[0].params["num_keys"] == 1


def test_presorted_detection_requires_sorted_column(tmp_path):
    seg = _build(tmp_path, sort_keys=False)
    program, jaxpr = _jaxpr_for(
        seg, FORCE + "SELECT k, SUM(v1), COUNT(*) FROM perfguard "
                     "GROUP BY k LIMIT 1000")
    assert program.mode == "group_by_sparse"
    assert not program.keys_presorted
    assert len(_grouping_sorts(jaxpr)) == 1
    # one on either side of the branch on how many rows the filter kept
    assert len(_table_sorts(jaxpr)) == 2


@pytest.mark.parametrize("aggs,num_sort_keys", [
    # 3 payloads (v1, v2, v1) sorted through one iota: key + iota = 2 operands
    ("SUM(v1), SUM(v2), MAX(v1)", 1),
    # distinct ids PACK into the key's low digits here (key_space × card
    # fits int32), so the distinct query still sorts a single packed key
    ("DISTINCTCOUNT(d), SUM(v1), SUM(v2)", 1),
])
def test_sort_iota_gather_sorts_keys_plus_iota_only(tmp_path, aggs,
                                                    num_sort_keys):
    seg = _build(tmp_path, sort_keys=False)
    program, jaxpr = _jaxpr_for(
        seg, FORCE + f"SELECT k, {aggs} FROM perfguard GROUP BY k LIMIT 1000")
    assert program.mode == "group_by_sparse"
    assert not program.keys_presorted
    eqns = _grouping_sorts(jaxpr)
    assert len(eqns) == 1, f"expected exactly one sort, got {len(eqns)}"
    got = len(eqns[0].invars)
    want = num_sort_keys + 1  # keys + iota32; payloads gather post-sort
    assert got == want, (
        f"sort carries {got} operands; the sort-iota path must sort only "
        f"{want} (payloads must ride the gather, not the sort)")


def test_single_payload_skips_the_iota(tmp_path):
    # with <2 payloads the extra gather costs more than it saves: the
    # kernel sorts (key, payload) directly — still exactly one sort, but
    # carrying the payload instead of an iota
    seg = _build(tmp_path, sort_keys=False)
    program, jaxpr = _jaxpr_for(
        seg, FORCE + "SELECT k, SUM(v1) FROM perfguard GROUP BY k LIMIT 1000")
    assert program.mode == "group_by_sparse"
    eqns = _grouping_sorts(jaxpr)
    assert len(eqns) == 1
    assert len(eqns[0].invars) == 2  # key + the single payload
    assert len(_table_sorts(jaxpr)) == 2
