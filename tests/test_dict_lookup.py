"""The dictionary decode (`ir.DictGather`, `ops/kernels._dict_lookup`): a
plane of up to `DICT_SELECT_MAX` entries is looked up by a select chain, a
longer one by the gather, and both give the host engine's answers exactly.

Dictionary dtype x cardinality x dispatch form (solo, and a batch family
whose members' dictionaries differ in size, so that `executor._dict_pad`'s
zero pads are in play), in value contexts: SUM(d), SUM(r * d), MIN/MAX(d)
and a filter over d * 2. Values are chosen so that every sum is exact in
float64 in any order of addition: equality is exact, not within a tolerance.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from pinot_tpu.engine import executor
from pinot_tpu.engine.executor import _dict_pad
from pinot_tpu.engine.query_executor import QueryExecutor, parse_sql
from pinot_tpu.ops import kernels
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

L = kernels.DICT_SELECT_MAX
ROWS = 700
NOCACHE = "SET resultCache = false; SET segmentCache = false; "
AGGS = "SELECT SUM(d), SUM(r * d), MIN(d), MAX(d) FROM {t} WHERE r < 9"
FILTER = "SELECT COUNT(*), SUM(r) FROM {t} WHERE d * 2 >= {x} AND r < 9"


def _values(dtype: str, card: int) -> np.ndarray:
    """`card` distinct values of the column's type, negatives among them:
    LONGs beyond 32 bits, FLOAT/DOUBLEs with a fraction."""
    k = np.arange(card) - card // 2
    if dtype == "INT":
        return (k * 1_000_003 + 7).astype(np.int32)
    if dtype == "LONG":
        return (k * 5_000_000_011 + 7).astype(np.int64)
    if dtype == "FLOAT":
        return (k * 0.5 + 0.25).astype(np.float32)
    return (k * 0.25 + 1e6).astype(np.float64)


def _family_cards(card: int, members: int) -> list:
    """Cardinalities of one batch family: `card`, the least and the most
    that share its `_dict_pad` bucket."""
    if members == 1:
        return [card]
    bucket = _dict_pad(card)
    return [card, bucket // 2 + 1, bucket]


def _engines(tmp_path, dtype: str, card: int, members: int,
             cards: list = None):
    table = f"dl_{dtype.lower()}_{card}_{members}"
    schema = Schema.build(table, dimensions=[("d", dtype)],
                          metrics=[("r", "INT")])
    cfg = TableConfig(table_name=table, indexing=IndexingConfig(
        no_dictionary_columns=["r"]))
    rng = np.random.default_rng(card * 10 + members)
    segs = []
    cards = cards or _family_cards(card, members)
    universe = _values(dtype, max(cards))
    for i, c in enumerate(cards):
        # members share the least and the greatest value: the planner puts
        # a column's bounds into the program, and one family is one program
        pick = rng.permutation(np.arange(1, len(universe) - 1))[:max(0, c - 2)]
        vals = universe[np.unique(np.concatenate(
            [pick, [0, len(universe) - 1]]).astype(np.int64))]
        assert len(vals) == c
        ids = np.concatenate([np.arange(c), rng.integers(0, c, ROWS)])[:ROWS]
        cols = {"d": vals[rng.permutation(ids)],
                "r": rng.integers(0, 10, ROWS).astype(np.int32)}
        SegmentBuilder(schema, cfg, f"{table}_{i}").build(
            cols, tmp_path / f"s{i}")
        segs.append(load_segment(tmp_path / f"s{i}"))
        assert segs[-1].column_metadata("d").cardinality == min(c, ROWS)
    device, host = QueryExecutor(backend="tpu"), QueryExecutor(backend="host")
    device.add_table(schema, segs)
    host.add_table(schema, segs)
    return table, device, host


def _rows(resp):
    assert not resp.exceptions, resp.exceptions
    return resp.result_table.rows


def _dispatch_spans(resp) -> list:
    return [s["attributes"] for s in resp.trace_info
            if s["operator"] == "family_dispatch"]


@pytest.mark.parametrize("members", [1, 3], ids=["solo", "family"])
@pytest.mark.parametrize("card", [1, 2, 11, 16, 17, L, L + 1])
@pytest.mark.parametrize("dtype", ["INT", "LONG", "FLOAT", "DOUBLE"])
def test_dictionary_values_equal_the_host_engine(tmp_path, dtype, card,
                                                 members):
    table, device, host = _engines(tmp_path, dtype, card, members)
    x = float(_values(dtype, card)[card // 3]) * 2
    for sql in (AGGS.format(t=table), FILTER.format(t=table, x=x)):
        got = device.execute_sql("SET trace = true; " + NOCACHE + sql)
        want = host.execute_sql(NOCACHE + sql)
        assert _rows(got) == _rows(want), sql
        assert _rows(want)[0][0] not in (None, 0), "the case selects no row"
        # one dispatch, on the device, and its span says which form ran
        assert got.num_device_dispatches == 1
        spans = _dispatch_spans(got)
        assert len(spans) == 1 and spans[0]["numSegments"] == members
        plane = card if members == 1 else _dict_pad(card)
        nodes = 4 if sql.startswith("SELECT SUM(d)") else 1
        assert spans[0]["dictLookups"] == (
            f"select:{nodes},gather:0" if plane <= L
            else f"select:0,gather:{nodes}")


# -- the family's pads: metadata checks, pads only on a stack miss -----------

# five members, every dictionary below the family's bucket of 16: five is
# ragged on a mesh of four, so the mesh stack repeats its last member
PAD_CARDS = [13, 9, 11, 10, 12]
ONE_DEVICE = "SET meshExecution = false; "


class _CountingPad:
    """Counts the eager `jnp.pad` calls the executor makes."""

    def __init__(self, monkeypatch):
        self.calls = 0
        real = executor.jnp.pad

        def counting(*a, **kw):
            self.calls += 1
            return real(*a, **kw)

        monkeypatch.setattr(executor.jnp, "pad", counting)


def _mesh_path(monkeypatch, path: str) -> str:
    """The options that send a family down `path`: one device, or the
    mesh's sharded dispatch over 4 of the 8 virtual devices."""
    if path == "mesh":
        monkeypatch.setenv("PINOT_TPU_MESH_DEVICES", "4")
        return ""
    return ONE_DEVICE


def _dict_slots(device, table: str, sql: str) -> int:
    seg = device.tables[table].segments[0]
    return sum(kind == "dict"
               for _col, kind in device.tpu.plan(parse_sql(sql), seg).slots)


@pytest.mark.parametrize("path", ["one-device", "mesh"])
def test_warm_family_launches_no_pad(tmp_path, monkeypatch, path):
    """The first dispatch of a family pads every member's dictionary plane
    while it builds the stack; the warm one finds the stack cached and
    launches no pad. The rows are the per-segment path's, bit for bit."""
    table, device, host = _engines(tmp_path, "LONG", 13, len(PAD_CARDS),
                                   cards=PAD_CARDS)
    opts = _mesh_path(monkeypatch, path)
    sql = AGGS.format(t=table)
    slots = _dict_slots(device, table, sql)
    assert slots == 1
    pads = _CountingPad(monkeypatch)
    spans, rows = [], []
    for _ in range(2):
        before = pads.calls
        got = device.execute_sql("SET trace = true; " + NOCACHE + opts + sql)
        rows.append(_rows(got))
        (span,) = _dispatch_spans(got)
        spans.append((span, pads.calls - before))
        assert span["numSegments"] == len(PAD_CARDS)
        assert span.get("meshDevices") == (4 if path == "mesh" else None)
    (first, first_calls), (warm, warm_calls) = spans
    assert first["dictPads"] == len(PAD_CARDS) * slots == first_calls
    assert first["stackMisses"] > 0
    assert warm["dictPads"] == 0 and warm_calls == 0
    assert warm["stackHits"] > 0 and warm["stackMisses"] == 0
    solo = _rows(device.execute_sql(NOCACHE + "SET segmentBatch = false; "
                                    + sql))
    assert rows[0] == rows[1] == solo == _rows(host.execute_sql(NOCACHE + sql))


class _SkewedPlan:
    """A member's plan whose dictionary plane reads as `skew` of the real
    one: a metadata-only stand-in, which no device op could take."""

    def __init__(self, plan, skew):
        self._plan, self._skew = plan, skew

    def __getattr__(self, name):
        return getattr(self._plan, name)

    def gather_arrays_packed(self, view, allow_packed=True):
        arrs, packed = self._plan.gather_arrays_packed(view, allow_packed)
        return tuple(self._skew(a) if kind == "dict" else a
                     for a, (_col, kind) in zip(arrs, self._plan.slots)), \
            packed


_SKEWS = {
    "dtype": lambda a: jax.ShapeDtypeStruct(a.shape, np.float32),
    "trailing-dims": lambda a: jax.ShapeDtypeStruct(a.shape + (2,), a.dtype),
}


@pytest.mark.parametrize("skew", sorted(_SKEWS))
def test_mismatch_is_found_from_metadata(tmp_path, monkeypatch, skew):
    """A member whose dictionary plane differs in dtype or trailing
    dimensions still raises BatchFamilyMismatch, with no pad launched:
    the check reads shapes and dtypes only. The dispatch then falls back
    to one per member, with the host engine's rows."""
    table, device, host = _engines(tmp_path, "LONG", 13, len(PAD_CARDS),
                                   cards=PAD_CARDS)
    sql = NOCACHE + ONE_DEVICE + AGGS.format(t=table)
    real = device.tpu._gather_batch
    seen = []
    pads = _CountingPad(monkeypatch)

    def skewed(segments, plans, ndev=1):
        plans = [plans[0], _SkewedPlan(plans[1], _SKEWS[skew]), *plans[2:]]
        before = pads.calls
        try:
            return real(segments, plans, ndev)
        except executor.BatchFamilyMismatch as e:
            seen.append((str(e), pads.calls - before))
            raise

    monkeypatch.setattr(device.tpu, "_gather_batch", skewed)
    got = device.execute_sql(sql)
    assert len(seen) == 1 and "unequal plane shapes/dtypes" in seen[0][0]
    assert seen[0][1] == 0
    assert got.num_device_dispatches == len(PAD_CARDS)
    assert _rows(got) == _rows(host.execute_sql(sql))


# -- the helper alone ---------------------------------------------------------

_SPECIALS = {
    "int32": [np.iinfo(np.int32).min, -1, 0, np.iinfo(np.int32).max],
    "int64": [np.iinfo(np.int64).min, -(1 << 40), 0, np.iinfo(np.int64).max],
    "float32": [-np.inf, -0.0, 0.0, np.float32(1e-45), np.inf],
    "float64": [-np.inf, -0.0, 0.0, 5e-324, np.inf],
}


@pytest.mark.parametrize("n", [5, 33, 2 * kernels._DICT_SELECT_FUSE + 1, L + 1])
@pytest.mark.parametrize("dtype", sorted(_SPECIALS))
def test_lookup_is_bit_exact(dtype, n):
    """Every entry of a table comes back bit for bit (a one-hot product
    would read 0 * inf as NaN and lose the sign of -0.0), through the
    fusion barriers and under the family's vmap; an id outside the table
    (row padding) reads some entry and raises nothing."""
    special = np.asarray(_SPECIALS[dtype], dtype=dtype)
    table = np.resize(special, n)
    table[len(special):] = np.arange(n - len(special)).astype(dtype)
    tables = np.stack([table, table[::-1]])
    ids = np.stack([np.arange(n, dtype=np.int32)] * 2)
    out = np.asarray(jax.jit(jax.vmap(kernels._dict_lookup))(tables, ids))
    assert out.dtype == tables.dtype
    assert out.tobytes() == tables.tobytes()
    beyond = np.asarray(jax.jit(kernels._dict_lookup)(
        table, np.asarray([n, 255, -1], dtype=np.int32)))
    assert beyond.shape == (3,)


def test_form_and_count_share_one_rule():
    assert [kernels.dict_lookup_form(n) for n in (0, 1, L, L + 1)] \
        == ["gather", "select", "select", "gather"]


def test_sweep_tool_rehearses(tmp_path, capsys):
    """The tool behind DICT_SELECT_MAX (PERF.md has its chip run): both
    forms, at toy size, give one SUM, and the constants it sets to force
    a form are put back."""
    import json

    from pinot_tpu.tools import dict_lookup_sweep

    before = kernels.DICT_SELECT_MAX, kernels._DICT_SELECT_FUSE
    out = tmp_path / "sweep.jsonl"
    assert dict_lookup_sweep.main(["--rehearse", "--planes", "64", "--fuse",
                                   "32", "--reps", "1", "--out", str(out)]) == 0
    capsys.readouterr()
    cases = [json.loads(line) for line in out.read_text().splitlines()]
    assert [c["form"] for c in cases] == ["gather", "select", "select-narrow"]
    assert all(c["equal"] and c["plane"] == 64 for c in cases)
    assert (kernels.DICT_SELECT_MAX, kernels._DICT_SELECT_FUSE) == before
