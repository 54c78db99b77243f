"""MIN and MAX of a dense group-by over a few groups (`ops/kernels.
_min_max_reduce`): masked reductions where a larger table scatters, and bit
for bit what `jax.ops.segment_min` / `segment_max` give on the same rows.

Value path (int32 plane, int64 plane with the planner's bounds, float32,
float64) x groups (1, 7, the constant, the constant + 1: the scatter) x form
(one segment, a batch family of three under `vmap`, the MV pre-expansion).
Every case has a group no row falls in, a mask that keeps about half the
rows, one that keeps all and one that keeps none, the dtype's extremes
(INT32_MIN / INT32_MAX, +-inf, -0.0) among the values.
"""

from __future__ import annotations

import re

import jax
import numpy as np
import pytest

from pinot_tpu.engine import ir
from pinot_tpu.engine.ir import program_label
from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.ops import kernels
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

C = kernels.MINMAX_REDUCE_MAX_GROUPS
ROWS, MV_WIDTH = 512, 3
I32 = np.iinfo(np.int32)
PATHS = {
    # name: (plane dtype, the planner's bounds, the values no case may miss)
    "int32": (np.int32, None, [I32.min, I32.max, -1, 0]),
    "int64-bounds": (np.int64, (I32.min, I32.max), [I32.min, I32.max, -1, 0]),
    "float32": (np.float32, None, [np.inf, -np.inf, -0.0, 0.0, 1e-45]),
    "float64": (np.float64, None, [np.inf, -np.inf, -0.0, 0.0, 5e-324]),
}


def _program(path: str, groups: int, mv: bool) -> ir.Program:
    _, bounds, _ = PATHS[path]
    vmin, vmax = bounds or (None, None)
    aggs = tuple(ir.AggOp(kind, ir.Col(1), vmin=vmin, vmax=vmax)
                 for kind in ("min", "max"))
    extra = dict(mv_group_slot=0, mv_group_card=groups,
                 mv_doc_slots=(1,)) if mv else {}
    return ir.Program(mode="group_by", filter=None, aggs=aggs,
                      group_slots=(0,), group_strides=(1,),
                      num_groups=groups, **extra)


def _member(path: str, groups: int, mv: bool, seed: int):
    """(ids, values) of one segment: ids (ROWS,) in [0, groups), or under
    `mv` (ROWS, MV_WIDTH) with `groups` marking a slot that holds no entry;
    no row falls in group `groups // 2` where there are two or more."""
    dtype, _, special = PATHS[path]
    rng = np.random.default_rng(seed)
    shape = (ROWS, MV_WIDTH) if mv else (ROWS,)
    ids = rng.integers(0, groups, shape).astype(np.int32)
    if groups > 1:
        ids[ids == groups // 2] = 0
    if mv:
        ids[rng.random(shape) < 0.3] = groups
    if np.issubdtype(dtype, np.integer):
        values = rng.integers(-10 ** 9, 10 ** 9, ROWS).astype(dtype)
    else:
        values = (rng.standard_normal(ROWS) * 1e3).astype(dtype)
    at = rng.permutation(ROWS)[:4 * len(special)]
    values[at] = np.resize(np.asarray(special, dtype=dtype), len(at))
    return ids, values


def _reference(kind: str, path: str, ids, values, mask, groups: int):
    """The contract, from `jax.ops.segment_min` / `segment_max` themselves:
    (groups + 1,) float64, masked rows in the trash slot, an empty group
    +inf / -inf by the COUNT on the 32-bit integer paths and by the
    identity on the float paths."""
    if ids.ndim == 2:  # the MV pre-expansion: (doc x slot) pairs
        mask = (mask[:, None] & (ids != groups)).reshape(-1)
        values = np.broadcast_to(values[:, None], ids.shape).reshape(-1)
        ids = ids.reshape(-1)
    lo = kind == "min"
    segment = jax.ops.segment_min if lo else jax.ops.segment_max
    gid = np.where(mask, ids, groups).astype(np.int32)
    if path in ("int32", "int64-bounds"):
        vm = np.where(mask, values.astype(np.int32), I32.max if lo else I32.min)
        out = np.asarray(segment(vm, gid, num_segments=groups + 1))
        counts = np.bincount(gid[mask], minlength=groups + 1)
        return np.where(counts == 0, np.inf if lo else -np.inf,
                        out.astype(np.float64))
    dtype = PATHS[path][0]
    vm = np.where(mask, values, dtype(np.inf if lo else -np.inf))
    return np.asarray(segment(vm, gid, num_segments=groups + 1)) \
        .astype(np.float64)


@pytest.mark.parametrize("form", ["solo", "family3", "mv-expanded"])
@pytest.mark.parametrize("groups", [1, 7, C, C + 1])
@pytest.mark.parametrize("path", sorted(PATHS))
def test_outputs_are_segment_min_and_segment_max_bit_for_bit(path, groups,
                                                             form):
    mv = form == "mv-expanded"
    program = _program(path, groups, mv)
    members = [_member(path, groups, mv, seed=groups * 10 + i)
               for i in range(3 if form == "family3" else 1)]
    rng = np.random.default_rng(groups)
    half = rng.random(ROWS) < 0.5
    # by turns over a family's members: about half the rows, none, all
    masks = [np.roll(np.stack([half, np.zeros(ROWS, bool),
                               np.ones(ROWS, bool)]), -i, axis=0)
             for i in range(len(members))]

    def one(ids, values, mask):
        return kernels._run_masked(program, (ids, values), (), mask, ROWS)

    run = jax.jit(jax.vmap(one) if form == "family3" else one)
    for turn in range(3):
        ids = np.stack([m[0] for m in members])
        values = np.stack([m[1] for m in members])
        mask = np.stack([m[turn] for m in masks])
        if form != "family3":
            ids, values, mask = ids[0], values[0], mask[0]
        outs = [np.asarray(o) for o in run(ids, values, mask)]
        assert len(outs) == (4 if mv else 3)  # counts, MIN, MAX[, docs]
        for kind, out in zip(("min", "max"), outs[1:3]):
            got = out if form == "family3" else out[None]
            assert got.dtype == np.float64
            assert got.shape == (len(members), groups + 1)
            for i, (m_ids, m_values) in enumerate(members):
                want = _reference(kind, path, m_ids, m_values,
                                  masks[i][turn], groups)
                assert got[i].tobytes() == want.tobytes(), (kind, turn, i)
            if groups > 1:  # the group no row falls in, and the trash slot
                empty = np.inf if kind == "min" else -np.inf
                assert (got[:, groups // 2] == empty).all()
                assert (got[:, groups] == empty).all()


# -- the form: one rule, read off the program ---------------------------------


def _primitives_of(groups: int) -> set:
    """The scatters and row reductions in the jaxpr of a MIN + MAX program
    over `groups` groups."""
    program = _program("int32", groups, mv=False)
    ids, values = _member("int32", groups, False, seed=1)
    text = str(jax.make_jaxpr(
        lambda i, v, m: kernels._run_masked(program, (i, v), (), m, ROWS))(
            ids, values, np.ones(ROWS, bool)))
    return set(re.findall(r"\b(scatter(?:-\w+)?|reduce_min|reduce_max)\[",
                          text))


def test_a_small_table_holds_no_scatter_and_a_large_one_still_does():
    # off the chip the COUNT column's limb kernel is a `segment_sum`
    # (`mxu_groupby._xla_limb_sums`): the one scatter a small table keeps
    # here; tests/test_tpu_compile.py reads the chip's HLO, which has none
    assert _primitives_of(7) == {"scatter-add", "reduce_min", "reduce_max"}
    assert _primitives_of(C) == {"scatter-add", "reduce_min", "reduce_max"}
    assert _primitives_of(C + 1) == {"scatter-add", "scatter-min",
                                     "scatter-max"}


def test_form_and_count_share_one_rule():
    assert [kernels.min_max_form(g) for g in (0, 1, 7, C, C + 1, 1 << 15)] \
        == ["scatter", "reduce", "reduce", "reduce", "scatter", "scatter"]
    for groups in (7, C, C + 1):
        form = kernels.min_max_form(groups)
        want = "reduce:2,scatter:0" if form == "reduce" \
            else "reduce:0,scatter:2"
        assert kernels.min_max_forms(_program("int32", groups, False)) == want
    # only the dense group-by chooses: an ungrouped MIN is a reduction of
    # its own, a sort-based one reads its groups' ends
    for mode in ("aggregation", "group_by_sparse", "selection"):
        other = ir.Program(mode=mode, filter=None,
                           aggs=(ir.AggOp("min", ir.Col(1)),), num_groups=7)
        assert kernels.min_max_forms(other) == "reduce:0,scatter:0"


# -- the counter on the dispatch span ------------------------------------------

# `dd_distinct_by_year` of the benchmark's `ssb16.drilldown`, and its columns
DISTINCT_BY_YEAR = (
    "SELECT d_year, DISTINCTCOUNT(lo_discount), MIN(lo_revenue), "
    "MAX(lo_revenue) FROM {t} WHERE lo_quantity BETWEEN 12 AND 37 "
    "GROUP BY d_year LIMIT 10")
NOCACHE = "SET resultCache = false; SET segmentCache = false; "


@pytest.fixture(scope="module")
def engines(tmp_path_factory):
    d = tmp_path_factory.mktemp("minmax_reduce")
    schema = Schema.build(
        "mmr", dimensions=[("d_year", "INT"), ("lo_discount", "INT"),
                           ("lo_quantity", "INT"), ("wide", "INT")],
        metrics=[("lo_revenue", "INT")])
    cfg = TableConfig(table_name="mmr", indexing=IndexingConfig(
        no_dictionary_columns=["lo_quantity", "lo_revenue"]))
    rng = np.random.default_rng(11)
    segs = []
    for i in range(3):
        n = 900
        cols = {"d_year": rng.integers(1992, 1999, n).astype(np.int32),
                "lo_discount": rng.integers(0, 11, n).astype(np.int32),
                "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
                "wide": rng.permutation(n).astype(np.int32) % (C + 40),
                "lo_revenue": rng.integers(1, 600_000, n).astype(np.int32)}
        SegmentBuilder(schema, cfg, f"mmr_{i}").build(cols, d / f"s{i}")
        segs.append(load_segment(d / f"s{i}"))
    device, host = QueryExecutor(backend="tpu"), QueryExecutor(backend="host")
    device.add_table(schema, segs)
    host.add_table(schema, segs)
    return device, host


@pytest.mark.parametrize("sql,label,want", [
    pytest.param(DISTINCT_BY_YEAR,
                 "gby_rng_c0_by1_distinctbitmap_i2_min_c3_max_c3",
                 "reduce:2,scatter:0", id="dd_distinct_by_year"),
    pytest.param("SELECT wide, MIN(lo_revenue), MAX(lo_revenue), COUNT(*) "
                 "FROM {t} WHERE lo_quantity < 40 GROUP BY wide LIMIT 1000",
                 None, "reduce:0,scatter:2", id="above-the-constant"),
    pytest.param("SELECT d_year, SUM(lo_revenue) FROM {t} WHERE "
                 "lo_discount BETWEEN 1 AND 3 GROUP BY d_year LIMIT 10",
                 None, "reduce:0,scatter:0", id="no-min-or-max"),
    pytest.param("SELECT MIN(lo_revenue), MAX(lo_revenue) FROM {t} WHERE "
                 "lo_quantity < 40", None, "reduce:0,scatter:0",
                 id="ungrouped"),
])
def test_dispatch_span_says_which_form_ran(engines, sql, label, want):
    device, host = engines
    sql = sql.format(t="mmr")
    got = device.execute_sql("SET trace = true; " + NOCACHE + sql)
    assert not got.exceptions, got.exceptions
    assert sorted(got.result_table.rows) == sorted(
        host.execute_sql(NOCACHE + sql).result_table.rows)
    assert got.num_device_dispatches == 1
    spans = [s["attributes"] for s in got.trace_info
             if s["operator"] == "family_dispatch"]
    assert len(spans) == 1 and spans[0]["numSegments"] == 3
    assert spans[0]["minMax"] == want
    if label:  # the module's name in the benchmark's `breakdown.device_ops`
        assert spans[0]["program"] == label
    # the span and the lowering read the same rule off the same program
    from pinot_tpu.query.parser.sql import parse_sql

    segment = device.tables["mmr"].segments[0]
    program = device.tpu.plan(parse_sql(sql), segment).program
    assert kernels.min_max_forms(program) == want
    assert program_label(program) == spans[0]["program"]
    if program.mode == "group_by":
        form = kernels.min_max_form(program.num_groups)
        n = sql.count("MIN(") + sql.count("MAX(")
        assert want == f"reduce:{n * (form == 'reduce')}," \
                       f"scatter:{n * (form == 'scatter')}"
