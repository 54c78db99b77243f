"""Compile the main path's programs for a described TPU v5e, without a chip.

The TPU compiler is installed beside JAX and compiles for a topology that
is described, not attached. Interpret-mode and CPU-branch tests cannot see
what it refuses (an i64 inside a Pallas kernel, a vector op the chip lacks,
VMEM at the widest accumulator), so these cases guard the kernels of the
served path at real shapes. Nothing runs: a compile that passes says
nothing about results or speed.

One file on purpose: only one process may load the TPU library, and
pytest-xdist (--dist loadfile) keeps a file on one worker. The topology is
described inside a fixture — never at import — so every worker collects the
same tests.
"""

import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from pinot_tpu.engine import ir
from pinot_tpu.engine.plan import SegmentPlanner
from pinot_tpu.ops import fused_groupby, kernels, mxu_groupby
from pinot_tpu.query.parser.sql import parse_sql
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.device_cache import SegmentDeviceView
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

R20, R22, R24 = 1 << 20, 1 << 22, 1 << 24


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture(scope="module")
def ssb(tmp_path_factory):
    """A small SSB-shaped segment: plans come from it, shapes are then
    scaled to the real row counts (a compile needs shapes, not data)."""
    rng = np.random.default_rng(7)
    n = 1 << 15
    schema = Schema.build(
        "t",
        dimensions=[("d_year", "INT"), ("p_brand", "INT"),
                    ("s_region", "STRING"), ("lo_discount", "INT"),
                    ("lo_quantity", "INT"), ("lo_orderkey", "INT")],
        metrics=[("lo_extendedprice", "INT"), ("lo_revenue", "INT"),
                 ("lo_tax", "DOUBLE")])
    cfg = TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["lo_extendedprice", "lo_revenue",
                               "lo_quantity", "lo_tax"]))
    regions = np.asarray(["AMERICA", "ASIA", "EUROPE", "AFRICA",
                          "MIDDLE EAST"], dtype=object)
    cols = {
        "d_year": rng.integers(1992, 1999, n).astype(np.int32),
        "p_brand": rng.integers(0, 1000, n).astype(np.int32),
        "s_region": regions[rng.integers(0, 5, n)],
        "lo_discount": rng.integers(0, 11, n).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
        "lo_orderkey": np.sort(rng.integers(0, n // 4, n)).astype(np.int32),
        "lo_extendedprice": rng.integers(1, 55_001, n).astype(np.int32),
        "lo_revenue": rng.integers(1, 600_000, n).astype(np.int32),
        "lo_tax": rng.random(n) * 8,
    }
    path = str(tmp_path_factory.mktemp("tpu_compile") / "s")
    SegmentBuilder(schema, cfg, "s0").build(cols, path)
    segment = load_segment(path)
    return segment, SegmentDeviceView(segment)


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)


def _compile_program(one_chip, ssb, sql, padded, *, batch=0, fused="",
                     sparse_groups=0, dict_len=0, whole_table=False):
    """Plan ``sql`` against the small segment, then lower run_program /
    run_program_batch with every row plane scaled to ``padded`` rows (and
    an [S] batch dim when ``batch``). ``sparse_groups`` scales a sparse
    program's key space, output groups and dictionary plane to a real
    high-cardinality segment (cut at numGroupsLimit's default, or under
    ``whole_table`` with a slot for every key, as the planner sizes a table
    it sorts by its own rule); ``dict_len`` sets the dictionary planes'
    length (a family's `executor._dict_pad` bucket)."""
    segment, view = ssb
    plan = SegmentPlanner(parse_sql(sql), segment).plan()
    arrays, packed = plan.gather_arrays_packed(view)
    params = tuple(np.asarray(p) for p in plan.params)
    program = plan.program
    lut_meta = ()
    if fused:
        extra, lut_meta = fused_groupby.lut_run_params(program, params)
        assert fused_groupby.plan(program, arrays, lut_meta) is not None
        params += extra
    if sparse_groups:
        assert program.mode == "group_by_sparse"
        program = dataclasses.replace(
            program, key_space=sparse_groups,
            num_groups=sparse_groups if whole_table
            else min(sparse_groups, 100_000))
    lead = [batch] if batch else []

    def plane(a, kind):
        shape = list(a.shape)
        if kind == "dict":
            if sparse_groups or dict_len:
                shape[0] = sparse_groups or dict_len
        else:
            assert shape[0] == view.padded
            shape[0] = padded
        return _spec(one_chip, lead + shape, a.dtype)

    a_s = tuple(plane(a, kind) for a, (_c, kind) in zip(arrays, plan.slots))
    p_s = tuple(_spec(one_chip, lead + list(p.shape), p.dtype)
                for p in params)
    if batch:
        lowered = kernels.run_program_batch.lower(
            program, a_s, p_s, _spec(one_chip, (batch,), jnp.int32),
            padded=padded, packed=packed)
    else:
        lowered = kernels.run_program.lower(
            program, a_s, p_s, _spec(one_chip, (), jnp.int32), padded=padded,
            packed=packed, fused=fused, fused_lut_meta=lut_meta)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert resident < 16 * 10 ** 9, f"{resident} bytes do not fit one v5e"
    return program, compiled.as_text()


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
    specs = tuple(_spec(one_chip, (n,), jnp.dtype(dtype))
                  for _ in range(planes))
    compiled = mxu_groupby._pallas_limb_sums.lower(
        specs, _spec(one_chip, (n,), jnp.int32),
        num_segments=groups).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- engine programs ---------------------------------------------------------

_GROUP2 = ("SELECT d_year, p_brand, SUM(lo_revenue), COUNT(*) FROM t "
           "WHERE {where} GROUP BY d_year, p_brand LIMIT 10000")


@pytest.mark.parametrize("where", [
    pytest.param("lo_quantity BETWEEN 10 AND 30", id="interval"),
    pytest.param("s_region IN ('ASIA', 'EUROPE')", id="in-list"),
])
def test_fused_kernel_compiles(one_chip, ssb, where):
    _, text = _compile_program(one_chip, ssb, _GROUP2.format(where=where),
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
    _, text = _compile_program(
        one_chip, ssb, _GROUP2.format(where="lo_quantity BETWEEN 10 AND 30"),
        R22, fused="tpu")
    assert "tpu_custom_call" in text
    assert "bf16" in text and "s8[" not in text


def test_fused_kernel_three_sums_compiles(one_chip, ssb):
    """1 count + 3 x 6 signed-width limb planes: the widest fused shape the
    SSB queries reach."""
    _, text = _compile_program(
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
    program, text = _compile_program(
        one_chip, ssb,
        "SELECT d_year, SUM(lo_revenue), MIN(lo_revenue), MAX(lo_revenue), "
        "SUM(lo_tax), DISTINCTCOUNT(lo_discount) FROM t "
        "WHERE s_region = 'ASIA' GROUP BY d_year LIMIT 100", R24)
    assert program.mode == "group_by"
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sql", [
    pytest.param("SELECT lo_orderkey, SUM(lo_revenue), COUNT(*) FROM t "
                 "GROUP BY lo_orderkey ORDER BY lo_orderkey LIMIT 100000",
                 id="presorted-sum-count"),
    pytest.param("SELECT lo_orderkey, DISTINCTCOUNT(lo_discount), "
                 "SUM(lo_revenue) FROM t GROUP BY lo_orderkey "
                 "ORDER BY lo_orderkey LIMIT 100000",
                 id="presorted-distinct"),
    pytest.param("SELECT p_brand, lo_discount, SUM(lo_revenue), "
                 "MIN(lo_quantity) FROM t GROUP BY p_brand, lo_discount "
                 "LIMIT 100000", id="sort-gather"),
])
def test_sparse_group_by_compiles(one_chip, ssb, sql):
    """High-cardinality (sort/scan-based) group-by at a 16M-row segment
    with a 4M-key dictionary. Guards compile TIME as much as acceptance:
    the chip's compiler needs minutes for jnp.cumsum / associative_scan at
    this n, seconds for the shift scans kernels._prefix_sum uses (a
    lax.sort costs it 20-60 s whatever n is). The bound is wide because
    five other workers share the host; it guards the cliff."""
    t0 = time.perf_counter()
    program, _ = _compile_program(
        one_chip, ssb, "SET sparseGroupBy = true; " + sql, R24,
        sparse_groups=1 << 22)
    assert program.mode == "group_by_sparse"
    assert time.perf_counter() - t0 < 300  # did not end in 300 s with cumsum


@pytest.mark.parametrize("sql,slots", [
    # dd_top_customers: an unsorted key, 291 thousand entries a segment
    pytest.param("SELECT p_brand, SUM(lo_revenue) FROM t WHERE lo_discount "
                 "BETWEEN 1 AND 3 GROUP BY p_brand ORDER BY SUM(lo_revenue) "
                 "DESC, p_brand LIMIT 20", 1 << 19, id="sort-2^19"),
    # dd_top_orders: the key ascends in doc order, 1.05 million a segment
    pytest.param("SELECT lo_orderkey, SUM(lo_quantity), SUM(lo_extendedprice)"
                 " FROM t WHERE lo_discount BETWEEN 1 AND 3 GROUP BY "
                 "lo_orderkey ORDER BY SUM(lo_quantity) DESC, lo_orderkey "
                 "LIMIT 100", 1 << 20, id="presorted-2^20"),
])
def test_whole_table_sparse_family_compiles(one_chip, ssb, sql, slots):
    """The sort-based scan with a slot for every key of the dictionary (the
    planner's rule above the limb kernel's table), as one dispatch over
    16 x 2^22 rows (`lax.map` over the members): the drill-down's two top-N
    programs. On the chip's machine (PR 30): 34.7 and 47.6 s; here 48-56
    and 80-85."""
    t0 = time.perf_counter()
    program, _ = _compile_program(
        one_chip, ssb, "SET sparseGroupBy = true; " + sql, R22, batch=16,
        sparse_groups=slots, whole_table=True)
    took = time.perf_counter() - t0
    print(f"sorted scan at {slots} slots x 16: compiled in {took:.1f} s")
    assert program.mode == "group_by_sparse" and program.num_groups == slots
    assert program.keys_presorted == (slots == 1 << 20)
    assert took < 300


def test_sparse_batch_family_compiles(one_chip, ssb):
    """The multi-segment form: one vmapped dispatch over 16 x 2^22 rows."""
    program, _ = _compile_program(
        one_chip, ssb,
        "SET sparseGroupBy = true; SELECT lo_orderkey, SUM(lo_revenue), "
        "COUNT(*) FROM t GROUP BY lo_orderkey ORDER BY lo_orderkey "
        "LIMIT 100000", R22, batch=16, sparse_groups=1 << 19)
    assert program.mode == "group_by_sparse"


@pytest.mark.parametrize(
    "keys,how,key32,states,kinds,order,cut,table,seconds,temp_bytes", [
    # dd_top_orders at the issue's size: 16 tables of 2^20 slots (1.05
    # million orders a segment, consecutive integers), two sums, cut to
    # 5,000 by SUM DESC, then the key
    pytest.param(1 << 20, "base", True, (jnp.float64, jnp.float64),
                 ("add", "add"), (1, True, False), 1 << 13, 0, 300, 4e9,
                 id="cut-16x2^20"),
    # dd_top_customers: 16 tables of 9 * 2^15 slots, never cut (300,000
    # customers are under the threshold): the whole merged table crosses;
    # a min and a max beside the sum (the count column rides every merge)
    pytest.param(9 << 15, "plane", True,
                 (jnp.float64, jnp.float64, jnp.float64),
                 ("add", "min", "max"), None, 0, 19 << 14, 600, 4e9,
                 id="whole-16x294912"),
    # what `SET sparseGroupBy` and tables above 2^21 keys still send: 16
    # tables cut at numGroupsLimit's 100,000 slots, int64 keys in value
    # space already (tables kept on the device), merged whole (no branch
    # at this size) into a table for every slot; a sum and a count (every
    # 64-bit column the sort carries costs the compiler a minute here: the
    # case above has the four kinds)
    pytest.param(100_000, "values", False, (jnp.float64, jnp.int64),
                 ("add", "add"), None, 0, 1 << 21, 400, 4e9,
                 id="values-16x100000"),
])
def test_sparse_device_combine_compiles(one_chip, keys, how, key32, states,
                                        kinds, order, cut, table, seconds,
                                        temp_bytes):
    """The server-level merge of 16 segments' sparse tables, cut on the
    device (kernels.merge_group_tables): dictionary ids to values, one sort
    that carries the columns, shift-pass scans, the bisection for the k-th
    value and the f64 -> int64 ranking, at the drill-down's sizes, both
    sides of the branch on how full the tables are. This file run on the
    chip's machine (its host compiles; PR 30): 52.7 s, 125.5 s and 128.0 s
    (the last two with four state columns each; three and two here), and
    1.79 GB, 0.60 GB and 0.09 GB of temporaries; the sandbox's host takes
    76 s, then 244-278 and 300-526 with four columns.
    The seconds allowed are for the sandbox under six workers."""
    s = 16
    source = {"base": _spec(one_chip, (s,), jnp.int64),
              "plane": _spec(one_chip, (s, keys), jnp.int32),
              "values": None}[how]
    tables = ((_spec(one_chip, (s, keys), jnp.int64), source,
               _spec(one_chip, (s, keys + 1), jnp.int64),
               tuple(_spec(one_chip, (s, keys + 1), dt) for dt in states)),)
    t0 = time.perf_counter()
    compiled = kernels.merge_group_tables.lower(
        tables, _spec(one_chip, (), jnp.int64),
        _spec(one_chip, (), jnp.int64), how=(how,), key32=key32, kinds=kinds,
        order=order, cut_slots=cut, table_slots=table).compile()
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    print(f"merge_group_tables {keys} x {s} ({how}): compiled in {took:.1f} s, "
          f"temp {mem.temp_size_in_bytes} bytes")
    assert took < seconds
    assert mem.temp_size_in_bytes < temp_bytes


def test_selection_compiles(one_chip, ssb):
    program, _ = _compile_program(
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
    _, text = _compile_program(one_chip, ssb, sql, R22, batch=16)
    assert ("tpu_custom_call" in text) == pallas


_Q1_1 = ("SELECT SUM(lo_extendedprice * lo_discount) FROM t WHERE d_year = 1993 "
         "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25")
_Q1_3 = ("SELECT SUM(lo_extendedprice * lo_discount) FROM t WHERE p_brand = 6 "
         "AND d_year = 1994 AND lo_discount BETWEEN 5 AND 7 "
         "AND lo_quantity BETWEEN 26 AND 35")


def _op_count(text: str, op: str) -> int:
    return len(re.findall(rf"= \S+ {op}\(", text))


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
    program, text = _compile_program(one_chip, ssb, sql, R22, batch=16,
                                     dict_len=16)
    assert time.perf_counter() - t0 < 60  # about a second alone
    assert program.mode == "aggregation"
    assert len(ir.dict_gathers(program)) == 1
    assert _op_count(text, "gather") == 0 and _op_count(text, "while") == 0
    t0 = time.perf_counter()
    _, text = _compile_program(one_chip, ssb, sql, R22, batch=16,
                               dict_len=kernels.DICT_SELECT_MAX)
    assert time.perf_counter() - t0 < 60  # 2 s alone, in fusions of 32
    assert _op_count(text, "gather") == 0
    _, text = _compile_program(one_chip, ssb, sql, R22, batch=16,
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
    specs = tuple(_spec(one_chip, shape, jnp.dtype(dt)) for shape, dt in outs)
    t0 = time.perf_counter()
    kernels._pack_flat.lower(specs).compile()
    assert time.perf_counter() - t0 < 60


def test_f64_bitcast_still_unimplemented(one_chip):
    """Why _encode_f64 exists: the TPU compiler's x64 rewrite cannot
    bitcast f64. When this starts compiling, the arithmetic encoding can
    go (ROADMAP queue 3)."""
    spec = _spec(one_chip, (16, 7001), jnp.float64)
    with pytest.raises(Exception, match="(?i)x64|unimplemented"):
        jax.jit(lambda x: jax.lax.bitcast_convert_type(
            x, jnp.uint32)).lower(spec).compile()
