"""The LUT filter (`ir.Lut`: an IN list, NOT IN, LIKE, an OR of EQs over a
dictionary column; `ops/kernels._lut_mask`): a LUT of up to `LUT_BITS_MAX`
entries is folded into uint32 words and tested bit by bit in registers, a
longer one is gathered, and both give the gather's mask bit for bit.

The helper alone over LUT lengths around a word's edges and the limit, with
all-false, all-true and NOT-IN contents, narrow and widened id planes, MV
id matrices with the pad sentinel, and a stacked family of 16 members with
a LUT each under the batch path's vmap; a jaxpr guard that the bit form
leaves no gather in the filter; the counter `lutGathers` against the rule;
and queries through the engine against the host engine on each side of
the limit.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pinot_tpu.engine import ir
from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.ops import kernels
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema

L = kernels.LUT_BITS_MAX
LENGTHS = [1, 2, 31, 32, 33, 251, 256, L, L + 1]
NOCACHE = "SET resultCache = false; SET segmentCache = false; "


def _lut(n: int, kind: str, rng) -> np.ndarray:
    """A LUT of `n` entries (the last is a dictionary's pad entry, false):
    random, all false, all true, or a NOT IN of two values."""
    if kind == "random":
        lut = rng.random(n) < 0.4
    elif kind == "none":
        lut = np.zeros(n, dtype=bool)
    elif kind == "all":
        lut = np.ones(n, dtype=bool)
    else:
        lut = np.ones(n, dtype=bool)
        lut[rng.choice(n, min(2, n), replace=False)] = False
    if kind != "all" and n > 1:
        lut[-1] = False
    return lut


def _mask(lut, ids):
    return np.asarray(jax.jit(kernels._lut_mask)(lut, ids))


@pytest.mark.parametrize("n", LENGTHS)
def test_bits_equal_the_gather(n):
    """Every id of [0, n) reads its LUT entry, in either form (a LUT over
    the limit is gathered), in a plane with every id and random ones."""
    rng = np.random.default_rng(n)
    lut = _lut(n, "random", rng)
    ids = np.concatenate([np.arange(n), rng.integers(0, n, 3000)]) \
        .astype(np.int32)
    got = _mask(lut, ids)
    assert got.dtype == np.bool_
    assert np.array_equal(got, lut[ids])
    assert kernels.lut_filter_form(n) == ("bits" if n <= L else "gather")


@pytest.mark.parametrize("n", [1, 33, 251, L])
@pytest.mark.parametrize("kind", ["none", "all", "not-in"])
def test_all_false_all_true_and_not_in(kind, n):
    rng = np.random.default_rng(n + 7)
    lut = _lut(n, kind, rng)
    ids = np.arange(n, dtype=np.int32)
    assert np.array_equal(_mask(lut, ids), lut)


@pytest.mark.parametrize("width", [8, 16])
@pytest.mark.parametrize("widened", [False, True], ids=["raw", "widened"])
def test_narrow_id_planes(width, widened):
    """Ids held as uint8 or uint16 planes, tested as stored or after
    `_apply_packed` widens them as the scan does."""
    n = 251 if width == 8 else L
    rng = np.random.default_rng(width)
    lut = _lut(n, "random", rng)
    dtype = np.uint8 if width == 8 else np.uint16
    ids = np.concatenate([np.arange(n), rng.integers(0, n, 3000)]) \
        .astype(dtype)
    if widened:
        (ids_in,) = kernels._apply_packed((ids,), ((0, width),))
    else:
        ids_in = ids
    assert np.array_equal(_mask(lut, ids_in), lut[ids.astype(np.int64)])


@pytest.mark.parametrize("card", [5, 250, L - 1])
def test_mv_matrix_with_the_pad_sentinel(card):
    """A multi-value plane: (rows, max_mv) ids where empty slots hold the
    pad sentinel `card`, whose entry is false; a row passes where any of
    its values does, as the filter's `mv=True` branch says."""
    rng = np.random.default_rng(card)
    lut = np.zeros(card + 1, dtype=bool)
    lut[:card] = rng.random(card) < 0.3
    ids = rng.integers(0, card, (2000, 4)).astype(np.int32)
    ids[rng.random(ids.shape) < 0.4] = card
    ids[:5] = card  # rows with no value at all
    node = ir.Lut(ids_slot=0, lut_param=0, mv=True)
    got = np.asarray(jax.jit(lambda a, p: kernels._eval_filter(
        node, (a,), (p,), a.shape[0]))(ids, lut))
    assert np.array_equal(got, lut[ids].any(axis=1))
    assert not got[:5].any()


def test_stacked_family_of_sixteen_members():
    """16 members, a LUT each (the family's params stack), under the batch
    path's vmap of the filter: row s is member s's own mask."""
    n, rows = 251, 1000
    rng = np.random.default_rng(16)
    luts = np.stack([_lut(n, kind, rng) for kind in
                     ["random", "none", "all", "not-in"] * 4])
    ids = rng.integers(0, n, (16, rows)).astype(np.uint8)
    program = ir.Program(mode="aggregation",
                         filter=ir.Lut(ids_slot=0, lut_param=0))
    num_docs = np.full(16, rows - 3, dtype=np.int32)

    @jax.jit
    def masks(a, p, nd):
        a = kernels._apply_packed(a, ((0, 8),))
        return jax.vmap(lambda a_s, p_s, k: kernels._filter_mask(
            program, a_s, p_s, k, rows))(a, p, nd)

    got = np.asarray(masks((ids,), (luts,), num_docs))
    want = np.take_along_axis(luts, ids.astype(np.int64), axis=1)
    want[:, rows - 3:] = False
    assert np.array_equal(got, want)


def _primitives(jaxpr) -> set:
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= _primitives(sub)
    return found


@pytest.mark.parametrize("n", [251, L, L + 1])
def test_no_gather_in_the_filter_within_the_limit(n):
    """The filter of a program with two LUTs and a range, as the batch
    path traces it: no gather where the LUTs are within the limit, the
    gather where they are not."""
    program = ir.Program(mode="aggregation", filter=ir.FAnd((
        ir.Lut(ids_slot=0, lut_param=0), ir.Lut(ids_slot=1, lut_param=1),
        ir.Interval(ir.IdsCol(2), lo_param=2, hi_param=3))))
    rows = 256
    arrays = tuple(jax.ShapeDtypeStruct((4, rows), jnp.int32)
                   for _ in range(3))
    params = (jax.ShapeDtypeStruct((4, n), jnp.bool_),) * 2 + \
        (jax.ShapeDtypeStruct((4,), jnp.int32),) * 2
    jaxpr = jax.make_jaxpr(lambda a, p, nd: jax.vmap(
        lambda a_s, p_s, k: kernels._filter_mask(program, a_s, p_s, k, rows))(
            a, p, nd))(arrays, params, jax.ShapeDtypeStruct((4,), jnp.int32))
    assert ("gather" in _primitives(jaxpr.jaxpr)) == (n > L)
    assert kernels.lut_gathers(program, params) == (2 if n > L else 0)


def test_form_and_count_share_one_rule():
    assert [kernels.lut_filter_form(n) for n in (0, 1, L, L + 1)] \
        == ["gather", "bits", "bits", "gather"]
    program = ir.Program(mode="aggregation", filter=ir.FOr((
        ir.Lut(ids_slot=0, lut_param=0), ir.Lut(ids_slot=1, lut_param=1))))
    for a, b in [(1, L), (L, L + 1), (L + 1, 5 * L)]:
        params = (np.zeros(a, bool), np.zeros(b, bool))
        assert kernels.lut_gathers(program, params) == sum(
            kernels.lut_filter_form(k) == "gather" for k in (a, b))


# -- through the engine --------------------------------------------------------

ROWS = 3000


@pytest.fixture(scope="module", params=[40, L], ids=["card40", "card-limit"])
def engines(request, tmp_path_factory):
    """Two segments of a table whose STRING column `c` has `card`
    values, so that its LUT (card + 1 entries) is within the limit or just
    above it."""
    card = request.param
    d = tmp_path_factory.mktemp(f"lut{card}")
    schema = Schema.build("lt", dimensions=[("c", "STRING"), ("y", "INT")],
                          metrics=[("v", "INT")])
    rng = np.random.default_rng(card)
    names = np.asarray([f"city{k:05d}" for k in range(card)], dtype=object)
    segs = []
    for i in range(2):
        k = np.concatenate([np.arange(card), rng.integers(0, card, ROWS)])
        cols = {"c": names[k], "y": rng.integers(0, 7, len(k)).astype(
            np.int32), "v": rng.integers(0, 1000, len(k)).astype(np.int32)}
        SegmentBuilder(schema, segment_name=f"lt_{i}").build(cols, d / f"s{i}")
        segs.append(load_segment(d / f"s{i}"))
    device, host = QueryExecutor(backend="tpu"), QueryExecutor(backend="host")
    device.add_table(schema, segs)
    host.add_table(schema, segs)
    return card, device, host


def _rows(resp):
    assert not resp.exceptions, resp.exceptions
    return resp.result_table.rows


@pytest.mark.parametrize("where", [
    "c IN ('city00003', 'city00017') AND y < 6",
    "c NOT IN ('city00003', 'city00017')",
    "(c = 'city00001' OR c = 'city00030') AND y BETWEEN 1 AND 5",
    "c LIKE 'city0001%'",
])
def test_queries_equal_the_host_engine(engines, where):
    """Each LUT predicate, solo and as one family of two, against the host
    engine; the dispatch span's `lutGathers` says which form ran."""
    card, device, host = engines
    sql = f"SELECT y, COUNT(*), SUM(v) FROM lt WHERE {where} GROUP BY y " \
          "ORDER BY y LIMIT 10"
    want = _rows(host.execute_sql(NOCACHE + sql))
    assert want, "the case selects no row"
    for opts in ("", "SET segmentBatch = false; "):
        got = device.execute_sql("SET trace = true; " + NOCACHE + opts + sql)
        assert _rows(got) == want, (opts, sql)
        spans = [s["attributes"] for s in got.trace_info
                 if s["operator"] == "family_dispatch"]
        assert spans and len(spans) == got.num_device_dispatches
        # one LUT each: the planner takes an OR of EQs on one column as
        # an IN list
        for attrs in spans:
            assert attrs["lutGathers"] == (1 if card + 1 > L else 0)


def test_sweep_tool_rehearses(tmp_path, capsys):
    """The tool behind LUT_BITS_MAX (PERF.md has its chip run): both forms,
    at toy size, give the same masks, and the constant it sets to force a
    form is put back."""
    from pinot_tpu.tools import lut_filter_sweep

    before = kernels.LUT_BITS_MAX
    out = tmp_path / "sweep.jsonl"
    assert lut_filter_sweep.main(["--rehearse", "--luts", "16,300", "--reps",
                                  "1", "--out", str(out)]) == 0
    capsys.readouterr()
    cases = [json.loads(line) for line in out.read_text().splitlines()]
    assert [(c["lut"], c["form"]) for c in cases] == [
        (16, "gather"), (16, "bits"), (300, "gather"), (300, "bits")]
    assert [c["words"] for c in cases] == [1, 1, 10, 10]
    assert all(c["equal"] for c in cases)
    assert kernels.LUT_BITS_MAX == before
