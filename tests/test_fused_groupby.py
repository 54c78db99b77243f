"""Fused single-pass dense group-by kernel (ops/fused_groupby.py) parity
vs the two-step path, via Pallas interpret mode on CPU."""

from __future__ import annotations

import numpy as np
from pathlib import Path
import pytest

jnp = pytest.importorskip("jax.numpy")

from pinot_tpu.engine.plan import SegmentPlanner  # noqa: E402
from pinot_tpu.engine.query_executor import QueryExecutor  # noqa: E402
from pinot_tpu.ops import fused_groupby  # noqa: E402
from pinot_tpu.ops.kernels import run_program  # noqa: E402
from pinot_tpu.query.parser.sql import parse_sql  # noqa: E402
from pinot_tpu.segment.builder import SegmentBuilder  # noqa: E402
from pinot_tpu.segment.device_cache import SegmentDeviceView  # noqa: E402
from pinot_tpu.segment.loader import load_segment  # noqa: E402
from pinot_tpu.spi.data_types import Schema  # noqa: E402
from pinot_tpu.spi.table_config import IndexingConfig, TableConfig  # noqa: E402

N = 20_000


@pytest.fixture(scope="module")
def segment(tmp_path_factory):
    rng = np.random.default_rng(3)
    schema = Schema.build(
        "fg",
        dimensions=[("year", "INT"), ("brand", "INT"), ("region", "STRING"),
                    ("qty", "INT")],
        metrics=[("rev", "INT"), ("signed", "INT")])
    cols = {
        "year": rng.integers(1992, 1999, N).astype(np.int32),
        "brand": rng.integers(0, 700, N).astype(np.int32),
        "region": np.asarray(["A", "B", "C", "D", "E"], dtype=object)[
            rng.integers(0, 5, N)],
        "qty": rng.integers(1, 51, N).astype(np.int32),
        "rev": rng.integers(0, 600_000, N).astype(np.int32),
        "signed": rng.integers(-50_000, 50_000, N).astype(np.int32),
    }
    d = tmp_path_factory.mktemp("fg") / "s"
    cfg = TableConfig(table_name="fg", indexing=IndexingConfig(
        no_dictionary_columns=["rev", "signed"]))
    SegmentBuilder(schema, cfg, "fg0").build(cols, d)
    return load_segment(d), schema, cols


SQLS = [
    # the bench q2 shape: dict EQ filter + 2-dim group + nonneg sum
    ("SELECT year, brand, SUM(rev), COUNT(*) FROM fg WHERE region = 'B' "
     "GROUP BY year, brand LIMIT 10000"),
    # range + BETWEEN filters, signed sum (neg plane)
    ("SELECT year, SUM(signed) FROM fg WHERE qty < 25 AND "
     "year BETWEEN 1993 AND 1996 GROUP BY year LIMIT 100"),
    # no filter at all
    ("SELECT brand, COUNT(*), SUM(rev) FROM fg GROUP BY brand LIMIT 10000"),
    # empty result (filter matches nothing)
    ("SELECT year, SUM(rev) FROM fg WHERE qty > 1000 GROUP BY year LIMIT 10"),
    # DISTINCT → group-by with zero aggregations (count plane only)
    ("SELECT DISTINCT year, region FROM fg LIMIT 100"),
    # multiple sums incl. signed (many limb planes in one pass)
    ("SELECT year, SUM(rev), SUM(signed), COUNT(*) FROM fg "
     "WHERE brand < 350 GROUP BY year LIMIT 100"),
]


def _outs(segment, sql, fused):
    plan = SegmentPlanner(parse_sql(sql), segment).plan()
    view = SegmentDeviceView(segment)
    arrays, packed = plan.gather_arrays_packed(view)
    params = tuple(np.asarray(p) for p in plan.params)
    return plan, [np.asarray(o) for o in run_program(
        plan.program, tuple(arrays), params, np.int32(segment.num_docs),
        view.padded, packed=tuple(packed), fused=fused)]


@pytest.mark.parametrize("sql", SQLS)
def test_fused_matches_two_step(segment, sql):
    seg, schema, cols = segment
    _plan, base = _outs(seg, sql, fused="")
    _plan2, got = _outs(seg, sql, fused="interpret")
    assert len(base) == len(got)
    for b, g in zip(base, got):
        np.testing.assert_array_equal(b, g)


def test_plan_accepts_the_hot_shape(segment):
    seg, *_ = segment
    sql = SQLS[0]
    p = SegmentPlanner(parse_sql(sql), seg).plan()
    view = SegmentDeviceView(seg)
    arrays, _ = p.gather_arrays_packed(view)
    fp = fused_groupby.plan(p.program, tuple(arrays))
    assert fp is not None
    assert fp.planes[0] == ("count",)
    assert any(x[0] == "limb" for x in fp.planes)


@pytest.mark.parametrize("sql", [
    # OR filter → outside fused scope
    "SELECT year, SUM(rev) FROM fg WHERE qty < 5 OR qty > 45 GROUP BY year",
    # MIN: not a fusable agg
    "SELECT year, MIN(rev) FROM fg GROUP BY year",
    # float-typed aggregation input via transform
    "SELECT year, SUM(rev * 0.5) FROM fg GROUP BY year",
])
def test_plan_rejects_out_of_scope(segment, sql):
    seg, *_ = segment
    p = SegmentPlanner(parse_sql(sql), seg).plan()
    view = SegmentDeviceView(seg)
    arrays, _ = p.gather_arrays_packed(view)
    assert fused_groupby.plan(p.program, tuple(arrays)) is None


def test_engine_end_to_end_with_fused_interpret(segment, monkeypatch):
    """Whole-engine parity with the fused kernel forced on (interpret)."""
    seg, schema, cols = segment
    monkeypatch.setenv("PINOT_TPU_FUSED", "interpret")
    tpu = QueryExecutor(backend="tpu")
    host = QueryExecutor(backend="host")
    for qe in (tpu, host):
        qe.add_table(schema, [seg])
    for sql in SQLS[:3]:
        a = tpu.execute_sql(sql)
        b = host.execute_sql(sql)
        assert not a.exceptions and not b.exceptions, (a.exceptions, b.exceptions)
        ra = sorted(map(tuple, a.result_table.rows))
        rb = sorted(map(tuple, b.result_table.rows))
        assert ra == rb, sql


def test_failure_falls_back_to_two_step(segment, monkeypatch):
    """A kernel failure disables fusion for the process; queries succeed."""
    seg, schema, cols = segment
    monkeypatch.setenv("PINOT_TPU_FUSED", "interpret")
    monkeypatch.setitem(fused_groupby._STATE, "error", None)

    def boom(*a, **k):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(fused_groupby, "execute", boom)
    qe = QueryExecutor(backend="tpu")
    qe.add_table(schema, [seg])
    # a query shape not yet in the jit cache, so the trace hits execute()
    r = qe.execute_sql(
        "SELECT brand, SUM(rev) FROM fg WHERE year = 1994 "
        "GROUP BY brand LIMIT 77")
    assert not r.exceptions, r.exceptions
    assert fused_groupby._STATE["error"] is not None
    monkeypatch.setitem(fused_groupby._STATE, "error", None)


FLOAT_BOUND_SQLS = [
    # fractional bounds on a raw int32 column round INWARD
    "SELECT year, SUM(rev) FROM fg WHERE rev >= 299999.5 GROUP BY year LIMIT 100",
    "SELECT year, COUNT(*) FROM fg WHERE rev <= 0.5 GROUP BY year LIMIT 100",
    "SELECT year, COUNT(*) FROM fg WHERE signed > -0.5 GROUP BY year LIMIT 100",
    # bounds outside int32 range: empty / all rows, never a clipped match
    "SELECT year, COUNT(*) FROM fg WHERE rev = 2147483648 GROUP BY year LIMIT 100",
    "SELECT year, COUNT(*) FROM fg WHERE signed < -3000000000 GROUP BY year LIMIT 10",
    "SELECT year, COUNT(*) FROM fg WHERE rev < 3000000000 GROUP BY year LIMIT 100",
]


@pytest.mark.parametrize("sql", FLOAT_BOUND_SQLS)
def test_fused_bound_normalization(segment, sql):
    """Float and out-of-int32 predicate bounds must agree with the
    two-step path (inward rounding; empty — not clipped — intervals)."""
    seg, *_ = segment
    _p1, base = _outs(seg, sql, fused="")
    _p2, got = _outs(seg, sql, fused="interpret")
    for b, g in zip(base, got):
        np.testing.assert_array_equal(b, g)


LUT_SQLS = [
    # IN list → dict LUT with (usually) several runs
    "SELECT year, SUM(rev), COUNT(*) FROM fg WHERE region IN ('A', 'C') "
    "GROUP BY year LIMIT 100",
    # NOT-EQ → two runs around the excluded id
    "SELECT year, SUM(rev) FROM fg WHERE region <> 'C' GROUP BY year LIMIT 100",
    # LUT combined with an interval term
    "SELECT year, COUNT(*) FROM fg WHERE region IN ('B', 'D', 'E') "
    "AND qty < 30 GROUP BY year LIMIT 100",
]


def _engine_pair(segment, monkeypatch):
    seg, schema, cols = segment
    monkeypatch.setenv("PINOT_TPU_FUSED", "interpret")
    tpu = QueryExecutor(backend="tpu")
    host = QueryExecutor(backend="host")
    for qe in (tpu, host):
        qe.add_table(schema, [seg])
    return tpu, host


@pytest.mark.parametrize("sql", LUT_SQLS)
def test_fused_lut_runs_parity(segment, sql, monkeypatch):
    """Dict-LUT predicates whose LUT compresses to ≤4 id runs ride the
    fused kernel; results must match the host engine."""
    tpu, host = _engine_pair(segment, monkeypatch)
    a = tpu.execute_sql(sql)
    b = host.execute_sql(sql)
    assert not a.exceptions and not b.exceptions, (sql, a.exceptions, b.exceptions)
    assert sorted(map(tuple, a.result_table.rows)) == \
        sorted(map(tuple, b.result_table.rows)), sql


def test_lut_run_params_extraction(segment):
    """Run extraction: adjacency merges; >MAX_LUT_RUNS bails; empty LUT
    yields an empty interval."""
    import numpy as np

    from pinot_tpu.engine import ir

    prog = ir.Program(mode="group_by", filter=ir.Lut(ids_slot=0, lut_param=0),
                      group_slots=(1,), group_strides=(1,), num_groups=4,
                      aggs=())
    lut = np.zeros(10, dtype=bool)
    lut[[2, 3, 4, 7]] = True  # two runs: [2,4], [7,7]
    extra, meta = fused_groupby.lut_run_params(prog, (lut,))
    assert meta == ((0, 1, 2),)
    assert list(extra[0]) == [2, 4, 7, 7]
    # empty LUT → the canonical empty interval
    extra, meta = fused_groupby.lut_run_params(prog, (np.zeros(6, bool),))
    assert list(extra[0]) == [1, 0]
    # too fragmented → not fusable
    frag = np.zeros(12, dtype=bool)
    frag[[0, 2, 4, 6, 8]] = True
    extra, meta = fused_groupby.lut_run_params(prog, (frag,))
    assert extra == () and meta == ()


def test_lut_query_takes_fused_path(segment):
    """End-to-end wiring check: the planner's Lut program + concrete params
    produce a FusedPlan with a runs term (not a silent two-step fall)."""
    seg, *_ = segment
    p = SegmentPlanner(parse_sql(LUT_SQLS[0]), seg).plan()
    view = SegmentDeviceView(seg)
    arrays, _ = p.gather_arrays_packed(view)
    params = tuple(np.asarray(x) for x in p.params)
    extra, meta = fused_groupby.lut_run_params(p.program, params)
    assert meta, "IN-list LUT should compress to runs"
    fp = fused_groupby.plan(p.program, tuple(arrays), meta)
    assert fp is not None
    assert any(t[0] == "runs" for t in fp.terms)


def test_use_fused_kernel_option(segment, monkeypatch):
    """SET useFusedKernel = false forces the two-step path per query."""
    seg, schema, cols = segment
    monkeypatch.setenv("PINOT_TPU_FUSED", "interpret")
    qe = QueryExecutor(backend="tpu")
    qe.add_table(schema, [seg])
    plain = SegmentPlanner(parse_sql(SQLS[0]), seg).plan()
    assert plain.fused_ok
    off = SegmentPlanner(
        parse_sql("SET useFusedKernel = false; " + SQLS[0]), seg).plan()
    assert not off.fused_ok
    a = qe.execute_sql("SET useFusedKernel = false; " + SQLS[0])
    b = qe.execute_sql(SQLS[0])
    assert not a.exceptions and not b.exceptions
    assert sorted(map(tuple, a.result_table.rows)) == \
        sorted(map(tuple, b.result_table.rows))


def test_fused_bf16_mode_parity(tmp_path):
    """PINOT_TPU_MXU_INT8=0 switches the plane dtype to bf16/8-bit limbs at
    import time — the designated fallback when int8 matmul misbehaves on a
    new Mosaic version, so it must stay tested. Runs ALWAYS: a subprocess
    with one CPU device (the suite's 8-virtual-device flag slows its
    compiles ~15x), a tiny shape, and the persistent compile cache keeps it
    to seconds."""
    import subprocess
    import sys

    code = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PINOT_TPU_MXU_INT8"] = "0"
import numpy as np
from pathlib import Path
import jax
jax.config.update("jax_compilation_cache_dir", r"CACHE")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
from pinot_tpu.ops import mxu_groupby
assert mxu_groupby.LIMB_BITS == 8 and "bfloat16" in str(mxu_groupby.PLANE_DTYPE)
from pinot_tpu.engine.plan import SegmentPlanner
from pinot_tpu.ops.kernels import run_program
from pinot_tpu.query.parser.sql import parse_sql
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.device_cache import SegmentDeviceView
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.table_config import IndexingConfig, TableConfig
rng = np.random.default_rng(7)
n = 1500
schema = Schema.build("b", dimensions=[("g", "INT")], metrics=[("v", "INT"), ("s", "INT")])
cfg = TableConfig(table_name="b", indexing=IndexingConfig(no_dictionary_columns=["v", "s"]))
SegmentBuilder(schema, cfg, "b0").build(
    {"g": rng.integers(0, 20, n).astype(np.int32),
     "v": rng.integers(0, 1_000_000, n).astype(np.int32),
     "s": rng.integers(-99_000, 99_000, n).astype(np.int32)}, r"OUT")
seg = load_segment(r"OUT")
plan = SegmentPlanner(parse_sql(
    "SELECT g, SUM(v), SUM(s), COUNT(*) FROM b WHERE g < 15 GROUP BY g LIMIT 100"), seg).plan()
view = SegmentDeviceView(seg)
arrays, packed = plan.gather_arrays_packed(view)
params = tuple(np.asarray(p) for p in plan.params)
base = [np.asarray(o) for o in run_program(
    plan.program, tuple(arrays), params, np.int32(seg.num_docs),
    view.padded, packed=tuple(packed), fused="")]
got = [np.asarray(o) for o in run_program(
    plan.program, tuple(arrays), params, np.int32(seg.num_docs),
    view.padded, packed=tuple(packed), fused="interpret")]
for b_, g_ in zip(base, got):
    np.testing.assert_array_equal(b_, g_)
print("BF16 PARITY OK")
""".replace("OUT", str(tmp_path / "bfseg")).replace(
        "CACHE", str(Path(__file__).resolve().parent.parent / ".jax_cache_bf16"))
    import os as _os

    env = {k: v for k, v in _os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=str(
                           Path(__file__).resolve().parent.parent))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "BF16 PARITY OK" in r.stdout
