"""Compile the main path's programs for a described TPU v5e, without a chip.

The TPU compiler is installed beside JAX and compiles for a topology that
is described, not attached. Interpret-mode and CPU-branch tests cannot see
what it refuses (an i64 inside a Pallas kernel, a vector op the chip lacks,
VMEM at the widest accumulator), so these cases guard the kernels of the
served path at real shapes. Nothing runs: a compile that passes says
nothing about results or speed.

This file holds the limb and fused kernels, the dense, selection and batch
programs and the output pack: seconds each. The cases that cost the
compiler a minute or more each are in files of their own, so that
`--dist loadfile` spreads them over workers: test_tpu_compile_sparse_scan.py
(the sort-based scan, one segment), test_tpu_compile_sorted_table.py (the
drill-down's two whole-table scans), test_tpu_compile_merge_cut.py,
test_tpu_compile_merge.py and test_tpu_compile_merge_values.py (the three
device merges). tpu_compile_support.py has what they share and says when
several processes may describe the chip at once; conftest.py starts the
long files first.
"""

import re
import time

import jax
import jax.numpy as jnp
import pytest

from pinot_tpu.engine import ir
from pinot_tpu.ops import kernels, mxu_groupby
from tpu_compile_support import R20, R22, R24, compile_program, spec

# -- the limb kernel ---------------------------------------------------------

# widest plane count per limb dtype (mxu_groupby.MAX_PLANES for each)
_WIDEST = {"int8": 24, "bfloat16": 16}


def _limb_cases():
    for dt in ("int8", "bfloat16"):
        p = _WIDEST[dt]
        yield pytest.param(dt, R20, 7000, 7, id=f"{dt}-2^20-G7000-P7")
        yield pytest.param(dt, R24, 7000, p, id=f"{dt}-2^24-G7000-P{p}")
        yield pytest.param(dt, R20, 8, 7, id=f"{dt}-2^20-G8-P7")
        # widest accumulators supports() admits: planes * s1 <= 4096
        yield pytest.param(dt, R20, 32768, 16, id=f"{dt}-2^20-G32768-P16")
    yield pytest.param("int8", R20, 128 * 170, 24, id="int8-2^20-G21760-P24")


@pytest.mark.parametrize("dtype,n,groups,planes", _limb_cases())
def test_limb_kernel_compiles(one_chip, dtype, n, groups, planes):
    assert groups <= mxu_groupby.MAX_GROUPS
    assert planes * max(1, -(-groups // mxu_groupby.LANES)) <= 4096
    specs = tuple(spec(one_chip, (n,), jnp.dtype(dtype))
                  for _ in range(planes))
    compiled = mxu_groupby._pallas_limb_sums.lower(
        specs, spec(one_chip, (n,), jnp.int32),
        num_segments=groups).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- engine programs ---------------------------------------------------------


def _op_count(text: str, op: str) -> int:
    return len(re.findall(rf"= \S+ {op}\(", text))


_GROUP2 = ("SELECT d_year, p_brand, SUM(lo_revenue), COUNT(*) FROM t "
           "WHERE {where} GROUP BY d_year, p_brand LIMIT 10000")


@pytest.mark.parametrize("where", [
    pytest.param("lo_quantity BETWEEN 10 AND 30", id="interval"),
    pytest.param("s_region IN ('ASIA', 'EUROPE')", id="in-list"),
])
def test_fused_kernel_compiles(one_chip, ssb, where):
    _, text = compile_program(one_chip, ssb, _GROUP2.format(where=where),
                               R24, fused="tpu")
    assert "tpu_custom_call" in text


def test_fused_kernel_bf16_limbs_compiles(one_chip, ssb, monkeypatch):
    """The fused kernel with the bf16 limb planes PINOT_TPU_MXU_INT8=0
    selects (Mosaic has no u32 -> bf16 cast; the limbs hop through i32).
    Another row count than the int8 cases, so the jit does not hand back
    their trace."""
    monkeypatch.setattr(mxu_groupby, "_INT8", False)
    monkeypatch.setattr(mxu_groupby, "PLANE_DTYPE", jnp.bfloat16)
    monkeypatch.setattr(mxu_groupby, "LIMB_BITS", 8)
    monkeypatch.setattr(mxu_groupby, "MAX_PLANES", _WIDEST["bfloat16"])
    _, text = compile_program(
        one_chip, ssb, _GROUP2.format(where="lo_quantity BETWEEN 10 AND 30"),
        R22, fused="tpu")
    assert "tpu_custom_call" in text
    assert "bf16" in text and "s8[" not in text


def test_fused_kernel_three_sums_compiles(one_chip, ssb):
    """1 count + 3 x 6 signed-width limb planes: the widest fused shape the
    SSB queries reach."""
    _, text = compile_program(
        one_chip, ssb,
        "SELECT d_year, p_brand, SUM(lo_revenue), SUM(lo_extendedprice), "
        "SUM(lo_quantity) FROM t WHERE lo_discount BETWEEN 1 AND 3 "
        "GROUP BY d_year, p_brand LIMIT 10000", R24, fused="tpu")
    assert "tpu_custom_call" in text


def test_dense_group_by_compiles(one_chip, ssb, monkeypatch):
    """The two-step dense path: XLA mask/gid/limb planes feeding the Pallas
    limb kernel, plus the scatters the MXU cannot do (MIN/MAX, DOUBLE sum,
    DISTINCTCOUNT)."""
    monkeypatch.setattr(mxu_groupby, "backend_platform", lambda: "tpu")
    program, text = compile_program(
        one_chip, ssb,
        "SELECT d_year, SUM(lo_revenue), MIN(lo_revenue), MAX(lo_revenue), "
        "SUM(lo_tax), DISTINCTCOUNT(lo_discount) FROM t "
        "WHERE s_region = 'ASIA' GROUP BY d_year LIMIT 100", R24)
    assert program.mode == "group_by"
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    """A small segment of TPC-H Q1's seven columns at the cell's domains
    (benchmark/configs/tpch-lineitem-sf10-16seg.json): plans come from
    it, shapes are scaled to the real rows."""
    import numpy as np

    from pinot_tpu.segment.builder import SegmentBuilder
    from pinot_tpu.segment.device_cache import SegmentDeviceView
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.spi.data_types import Schema
    from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

    rng = np.random.default_rng(36)
    n = 1 << 15
    schema = Schema.build(
        "t", dimensions=[("l_returnflag", "STRING"),
                         ("l_linestatus", "STRING"), ("l_shipdate", "INT")],
        metrics=[("l_quantity", "INT"), ("l_discount", "INT"),
                 ("l_tax", "INT"), ("l_extendedprice", "INT")])
    cols = {"l_returnflag": np.asarray(["A", "N", "R"], object)[
                rng.integers(0, 3, n)],
            "l_linestatus": np.asarray(["F", "O"], object)[
                rng.integers(0, 2, n)],
            "l_shipdate": rng.integers(19920102, 19981201, n).astype(
                np.int32),
            "l_quantity": rng.integers(1, 51, n).astype(np.int32),
            "l_discount": rng.integers(0, 11, n).astype(np.int32),
            "l_tax": rng.integers(0, 9, n).astype(np.int32),
            "l_extendedprice": np.r_[90_000, 10_495_000, rng.integers(
                90_000, 10_495_001, n - 2)].astype(np.int32)}
    path = str(tmp_path_factory.mktemp("tpu_compile_q1") / "li")
    SegmentBuilder(schema, TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["l_extendedprice"])), "li0").build(cols, path)
    segment = load_segment(path)
    return segment, SegmentDeviceView(segment)


_TPCH_Q1 = (
    "SELECT SUM(l_quantity), l_returnflag, l_linestatus, "
    "SUMPRECISION(l_extendedprice), "
    "SUMPRECISION(l_extendedprice * (100 - l_discount)), "
    "SUMPRECISION(l_extendedprice * (100 - l_discount) * (100 + l_tax)), "
    "AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(*) "
    "FROM t WHERE l_shipdate <= 19980902 GROUP BY l_returnflag, "
    "l_linestatus ORDER BY l_returnflag, l_linestatus LIMIT 10")


def test_q1_sums_past_int32_ride_one_limb_pass(one_chip, lineitem,
                                               monkeypatch):
    """TPC-H Q1 over a 16 x 2^22-row family (the cell `tpch16.q1`): every
    SUM, the 37-bit charge among them, rides ONE limb pass of 18 int8
    planes, with no scatter left (a SUM past int32 took a float64
    segment_sum before PR 36). The compiler leaves no s64 op in the
    optimised HLO either way (it splits them into u32 pairs); what the
    32-bit forms save is multiplies a row: 11 here (`_eval_ring32`,
    `_mul_words`), 20 where the charge's words come from its int64
    plane (PERF.md, PR 36)."""
    monkeypatch.setattr(mxu_groupby, "backend_platform", lambda: "tpu")
    program, text = compile_program(one_chip, lineitem, _TPCH_Q1, R22,
                                     batch=16, dict_len=64)
    assert program.mode == "group_by"
    assert kernels.limb_forms(program, R22, None) == (18, 0)
    assert text.count("tpu_custom_call") == 1
    assert _op_count(text, "scatter") == 0 and _op_count(text, "while") == 0
    assert not re.search(r"= s64\[[^\]]*\]\S* multiply\(", text)
    rows = re.findall(r"= [su]32\[16,4194304\]\S* multiply\(", text)
    assert len(rows) <= 11, len(rows)


@pytest.fixture(scope="module")
def flight4(tmp_path_factory):
    """A small segment of SSB flight 4's columns at their published
    cardinalities (7 years, 5 regions and 25 nations a side, 250 supplier
    cities, 5 manufacturers, 25 categories, 1,000 brands) and the two
    money columns raw, their difference's bounds straddling zero as in
    benchmark/configs/ssb-flat-sf10-16seg-flight4.json."""
    import numpy as np

    from pinot_tpu.segment.builder import SegmentBuilder
    from pinot_tpu.segment.device_cache import SegmentDeviceView
    from pinot_tpu.segment.loader import load_segment
    from pinot_tpu.spi.data_types import Schema
    from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

    n = 1 << 15
    i = np.arange(n)

    def names(fmt, k):
        return np.asarray([fmt % j for j in range(k)], dtype=object)

    keys = ["c_region", "c_nation", "s_region", "s_nation", "s_city",
            "p_mfgr", "p_category", "p_brand1"]
    schema = Schema.build(
        "t", dimensions=[("d_year", "INT")] + [(k, "STRING") for k in keys],
        metrics=[("lo_revenue", "INT"), ("lo_supplycost", "INT")])
    city, brand, nation = (i // 7) % 250, (i * 13) % 1000, i % 25
    cols = {"d_year": (1992 + i % 7).astype(np.int32),
            "c_nation": names("CN%02d", 25)[nation],
            "c_region": names("R%d", 5)[nation // 5],
            "s_city": names("SC%03d", 250)[city],
            "s_nation": names("SN%02d", 25)[city // 10],
            "s_region": names("R%d", 5)[city // 50],
            "p_brand1": names("MFGR#%04d", 1000)[brand],
            "p_category": names("MFGR#%02d", 25)[brand // 40],
            "p_mfgr": names("MFGR#%d", 5)[brand // 200],
            "lo_revenue": (81_000 + i * 37 % 10_000_000).astype(np.int32),
            "lo_supplycost": (54_000 + i % 71_941).astype(np.int32)}
    path = str(tmp_path_factory.mktemp("tpu_compile_f4") / "s")
    SegmentBuilder(schema, TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["lo_revenue", "lo_supplycost"])), "s0").build(
        cols, path)
    segment = load_segment(path)
    return segment, SegmentDeviceView(segment)


def test_signed_profit_rides_the_limb_pass(one_chip, flight4, monkeypatch):
    """SSB Q4.2 (the cell `ssb16.flight4`) over a 16 x 2^22-row family: the
    signed SUM(lo_revenue - lo_supplycost) on the limb kernel at 4,375
    slots, the count plane, five 7-bit digits of the 32-bit value and its
    negative-count plane, one Pallas call, no scatter; another LIMIT plans
    the same program."""
    from pinot_tpu.engine.plan import SegmentPlanner
    from pinot_tpu.query.parser.sql import parse_sql

    monkeypatch.setattr(mxu_groupby, "backend_platform", lambda: "tpu")
    sql = ("SELECT SUM(lo_revenue - lo_supplycost), d_year, s_nation, "
           "p_category FROM t WHERE c_region = 'R1' AND s_region = 'R1' AND "
           "(d_year = 1997 OR d_year = 1998) AND (p_mfgr = 'MFGR#1' OR "
           "p_mfgr = 'MFGR#2') GROUP BY d_year, s_nation, p_category "
           "ORDER BY d_year, s_nation, p_category LIMIT 900")
    program, text = compile_program(one_chip, flight4, sql, R22, batch=16)
    assert program.mode == "group_by" and program.num_groups == 7 * 25 * 25
    assert kernels.limb_forms(program, R22, None) == (7, 0)
    assert kernels.signed_sums(program) == 1
    assert text.count("tpu_custom_call") == 1
    assert _op_count(text, "scatter") == 0
    other = SegmentPlanner(parse_sql(sql.replace("LIMIT 900", "LIMIT 100")),
                           flight4[0]).plan().program
    assert other == program


# `dd_distinct_by_year` of the benchmark's `ssb16.drilldown`
_DISTINCT_BY_YEAR = (
    "SELECT d_year, DISTINCTCOUNT(lo_discount), MIN(lo_revenue), "
    "MAX(lo_revenue) FROM t WHERE lo_quantity BETWEEN 12 AND 37 "
    "GROUP BY d_year LIMIT 10")
_MIN_MAX_BY_BRAND = (
    "SELECT p_brand, MIN(lo_revenue), MAX(lo_revenue), MIN(lo_tax) FROM t "
    "WHERE lo_quantity BETWEEN 12 AND 37 GROUP BY p_brand LIMIT 10000")


@pytest.mark.parametrize("sql,groups,label,scatters", [
    pytest.param(_DISTINCT_BY_YEAR, 0,
                 "gby_rng_c0_by1_distinctbitmap_i2_min_c3_max_c3", False,
                 id="dd_distinct_by_year"),
    pytest.param(_MIN_MAX_BY_BRAND, kernels.MINMAX_REDUCE_MAX_GROUPS, None,
                 False, id="at-the-constant"),
    pytest.param(_MIN_MAX_BY_BRAND, kernels.MINMAX_REDUCE_MAX_GROUPS + 1,
                 None, True, id="above-the-constant"),
])
def test_min_max_over_few_groups_compiles_without_a_scatter(
        one_chip, ssb, monkeypatch, sql, groups, label, scatters):
    """A dense group-by's MIN and MAX over a 16 x 2^22-row family: up to
    `MINMAX_REDUCE_MAX_GROUPS` groups they are masked reductions (int32
    and float64 value paths here) with no scatter in the optimised HLO
    (two of 0.6 s a dispatch on the chip for 7 years, PERF.md, PR 32) and
    no loop in its place; one group more keeps the scatters as they were.
    The reduction is ONE broadcast compare whatever the groups: the time
    bound holds it to that (a chain of selects a group would not)."""
    monkeypatch.setattr(mxu_groupby, "backend_platform", lambda: "tpu")
    t0 = time.perf_counter()
    program, text = compile_program(one_chip, ssb, sql, R22, batch=16,
                                     dense_groups=groups)
    took = time.perf_counter() - t0
    print(f"{ir.program_label(program)} x {program.num_groups} groups: "
          f"compiled in {took:.1f} s")
    assert took < 60  # 1-3 s alone; the issue's 10 s is the chip's host
    assert program.mode == "group_by"
    n = sum(agg.kind in ("min", "max") for agg in program.aggs)
    assert kernels.min_max_forms(program) == (
        f"reduce:0,scatter:{n}" if scatters else f"reduce:{n},scatter:0")
    if label:
        assert ir.program_label(program) == label
    assert (_op_count(text, "scatter") > 0) == scatters
    assert _op_count(text, "while") == 0
    assert "tpu_custom_call" in text  # the COUNT column's limb kernel
    # the reductions' fusion is named by a reduction, under the scope the
    # benchmark's `kernel_groupby_ms` reads
    reductions = re.findall(r'op_name="([^"]+/reduce_m(?:in|ax))"', text)
    assert bool(reductions) != scatters
    assert all("/vmap(group_by_dense)/" in n for n in reductions)


def test_sparse_batch_family_compiles(one_chip, ssb):
    """The multi-segment form: one vmapped dispatch over 16 x 2^22 rows."""
    program, _ = compile_program(
        one_chip, ssb,
        "SET sparseGroupBy = true; SELECT lo_orderkey, SUM(lo_revenue), "
        "COUNT(*) FROM t GROUP BY lo_orderkey ORDER BY lo_orderkey "
        "LIMIT 100000", R22, batch=16, sparse_groups=1 << 19)
    assert program.mode == "group_by_sparse"


def test_selection_compiles(one_chip, ssb):
    program, _ = compile_program(
        one_chip, ssb,
        "SELECT lo_orderkey, lo_revenue FROM t WHERE lo_discount = 3 AND "
        "lo_quantity < 5 AND s_region = 'ASIA' LIMIT 50", R24)
    assert program.mode == "selection"


@pytest.mark.parametrize("sql,pallas", [
    pytest.param("SELECT SUM(lo_extendedprice) FROM t WHERE d_year = 1993 "
                 "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25",
                 False, id="filter-sum"),
    pytest.param("SELECT d_year, p_brand, SUM(lo_revenue) FROM t "
                 "WHERE s_region = 'ASIA' GROUP BY d_year, p_brand "
                 "LIMIT 10000", True, id="group-by-2-keys"),
])
def test_batch_family_compiles(one_chip, ssb, monkeypatch, sql, pallas):
    """One vmapped dispatch over a 16 x 2^22-row family (SSB SF10)."""
    monkeypatch.setattr(mxu_groupby, "backend_platform", lambda: "tpu")
    _, text = compile_program(one_chip, ssb, sql, R22, batch=16)
    assert ("tpu_custom_call" in text) == pallas


_Q1_1 = ("SELECT SUM(lo_extendedprice * lo_discount) FROM t WHERE d_year = 1993 "
         "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25")
_Q1_3 = ("SELECT SUM(lo_extendedprice * lo_discount) FROM t WHERE p_brand = 6 "
         "AND d_year = 1994 AND lo_discount BETWEEN 5 AND 7 "
         "AND lo_quantity BETWEEN 26 AND 35")


@pytest.mark.parametrize("sql", [pytest.param(_Q1_1, id="q1.1"),
                                 pytest.param(_Q1_3, id="q1.3")])
def test_dictionary_product_sum_compiles_without_a_gather(one_chip, ssb, sql):
    """SSB flight 1 over a 16 x 2^22-row family: `lo_discount` is
    dictionary-encoded and SUM(lo_extendedprice * lo_discount) reads its
    VALUES. At the family's plane of 16 entries the decode is a select
    chain in the reduction's fusion: no gather (653 of 674.5 ms a dispatch
    on the chip, PERF.md), and none of the loop the compiler expands a
    gather into. A plane of 2 * DICT_SELECT_MAX keeps the gather, as
    before. The time bound guards the cliff: one fusion of 256 selects
    takes the chip's compiler 97 s, which is why the chain is cut into
    fusions of `_DICT_SELECT_FUSE`."""
    t0 = time.perf_counter()
    program, text = compile_program(one_chip, ssb, sql, R22, batch=16,
                                     dict_len=16)
    assert time.perf_counter() - t0 < 60  # about a second alone
    assert program.mode == "aggregation"
    assert len(ir.dict_gathers(program)) == 1
    assert _op_count(text, "gather") == 0 and _op_count(text, "while") == 0
    t0 = time.perf_counter()
    _, text = compile_program(one_chip, ssb, sql, R22, batch=16,
                               dict_len=kernels.DICT_SELECT_MAX)
    assert time.perf_counter() - t0 < 60  # 2 s alone, in fusions of 32
    assert _op_count(text, "gather") == 0
    _, text = compile_program(one_chip, ssb, sql, R22, batch=16,
                               dict_len=2 * kernels.DICT_SELECT_MAX)
    assert _op_count(text, "gather") == 1


# -- the output pack ---------------------------------------------------------


@pytest.mark.parametrize("outs", [
    pytest.param([((16, 7001), "int64"), ((16, 7001), "float64"),
                  ((16, 7001), "uint32")], id="batch-16x7001"),
    pytest.param([((1_600_001,), "int64"), ((1_600_001,), "float64"),
                  ((1_600_000,), "int64")], id="sparse-merged-1600001"),
    # narrow planes beside 64-bit ones: selection bitmaps of a 16 x 2^22
    # family, DISTINCTCOUNT bitmaps, a PTDP block with an odd row count
    pytest.param([((16, 1 << 19), "uint8")], id="selection-16x2^19"),
    pytest.param([((16, 8), "int64"), ((16, 7, 11), "bool"),
                  ((16, 8), "float64")], id="distinct-bitmap"),
    pytest.param([((1_000_003,), "int64"), ((1_000_003,), "bool"),
                  ((1_000_003,), "int16"), ((1_000_003,), "int8")],
                 id="ptdp-odd-rows"),
])
def test_output_pack_compiles_fast(one_chip, outs):
    """Every dispatch ends in kernels._pack_flat (one D2H fetch per query).
    Interleaving words on device (a 64-bit bitcast, a (.., 2) or (.., 4)
    minor dim flattened) cost the chip's compiler 18-145 s for these
    shapes — the broker's default timeout is 60 s; the planar pack takes
    about one. The bound is generous: it guards the cliff, not the
    seconds."""
    specs = tuple(spec(one_chip, shape, jnp.dtype(dt)) for shape, dt in outs)
    t0 = time.perf_counter()
    kernels._pack_flat.lower(specs).compile()
    assert time.perf_counter() - t0 < 60


def test_f64_bitcast_still_unimplemented(one_chip):
    """Why _encode_f64 exists: the TPU compiler's x64 rewrite cannot
    bitcast f64. When this starts compiling, the arithmetic encoding can
    go (ROADMAP queue 3)."""
    planes = spec(one_chip, (16, 7001), jnp.float64)
    with pytest.raises(Exception, match="(?i)x64|unimplemented"):
        jax.jit(lambda x: jax.lax.bitcast_convert_type(
            x, jnp.uint32)).lower(planes).compile()
