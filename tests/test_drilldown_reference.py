"""The drill-down deployment (`benchmark/configs/ssb-flat-sf10-16seg-keys`)
at toy size on the CPU: the served path against the benchmark's plain
references, which are what decides `correct` for `ssb16.drilldown` on the
chip.

- each of the cell's four classes through `Broker.execute_sql` on a
  4-segment table, the top-N classes by the dense table (the planner's own
  choice) and under `SET sparseGroupBy = true`: both paths are held to one
  answer before a later change moves traffic from one to the other;
- ties planted at the LIMIT's edge: the key in the ORDER BY decides;
- a customer whose lines lie in every segment and who leads only in the
  total: no group may be trimmed before the table-wide combine;
- a key space above the limb kernel's table (`ddwide`: 130,000 to 132,000
  keys a segment, 262,144 rows each), where the planner sorts the table by
  its own rule and the server merges and cuts on the device: ties across
  the cut's edge, sums above 2^31 and 2^53, one family for four segments
  whose dictionaries straddle a power of two;
- each reference against a loop over the rows, and the generator's order
  structure (what `lo_orderkey` and `lo_custkey` are).
"""

from __future__ import annotations

import functools
import importlib.util
import tempfile
from collections import defaultdict
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"


@functools.lru_cache(maxsize=None)
def _module(path: Path):
    spec = importlib.util.spec_from_file_location(f"dd_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


traffic = _module(BENCH / "traffic.py")
table = _module(BENCH / "table.py")

SEED, ROWS, SEGMENTS = 2147483659, 8192, 4
CLASSES = ("dd_top_customers", "dd_top_orders", "dd_distinct_by_year",
           "dd_order_lines")
TOPN = CLASSES[:2]
NOCACHE = "SET resultCache = false; SET segmentCache = false; "
SPARSE = "SET sparseGroupBy = true; "


def _config(name: str) -> dict:
    """The cell's configuration, cut to a test's size: 4 segments, and 500
    customers so that a customer has orders in every segment."""
    config = traffic.load("configs", "ssb-flat-sf10-16seg-keys")
    return dict(config, table=name, segments=SEGMENTS,
                scale=dict(config["scale"], customers=500))


def _blocks(config: dict) -> list:
    generator = table.generator_of(config)
    return [generator.segment_columns(config, ROWS, SEED, seg)
            for seg in range(SEGMENTS)]


def _reference(cls: str, config: dict, blocks: list):
    qclass = traffic.load("queries", cls)
    generator = table.generator_of(config)
    ref = _module(BENCH / "references" / f"{qclass['reference']}.py") \
        .Reference(qclass, config, generator.dictionaries(config), "exact")
    for block in blocks:
        ref.add(block)
    return qclass, ref


class Cluster:
    """One server (backend tpu, here the CPU) behind one broker, as
    benchmark/run.py sets it up; tables are built from column blocks."""

    def __init__(self):
        from pinot_tpu.cluster import (Broker, ClusterController,
                                       PropertyStore, ServerInstance)

        self.dir = Path(tempfile.mkdtemp(prefix="ddref_"))
        store = PropertyStore()
        self.controller = ClusterController(store)
        self.server = ServerInstance(store, "Server_0", backend="tpu")
        self.server.start()
        self.broker = Broker(store)

    def deploy(self, config: dict, blocks: list) -> None:
        schema, table_config = table.table_schema(config)
        names = table.generator_of(config).dictionaries(config)
        self.deploy_columns(schema, table_config, [
            {c: np.asarray(names[c], dtype=object)[v]
             if c in names else v for c, v in block.items()}
            for block in blocks])

    def deploy_columns(self, schema, table_config, segments: list) -> None:
        from pinot_tpu.segment.builder import SegmentBuilder

        self.controller.add_schema(schema.to_json())
        t = self.controller.create_table(table_config.to_json())
        for seg, cols in enumerate(segments):
            name = f"{schema.schema_name}_{seg}"
            path = str(self.dir / schema.schema_name / name)
            SegmentBuilder(schema, table_config, name).build(cols, path)
            self.controller.add_segment(
                t, name, {"location": path,
                          "numDocs": len(next(iter(cols.values())))})

    def response(self, sql: str):
        resp = self.broker.execute_sql(NOCACHE + sql)
        assert not resp.exceptions, resp.exceptions
        assert not resp.partial_result
        return resp

    def rows(self, sql: str) -> list:
        return [tuple(r) for r in self.response(sql).result_table.rows]


@pytest.fixture(scope="module")
def cluster():
    c = Cluster()
    yield c
    c.server.stop()


@pytest.fixture(scope="module")
def generated(cluster):
    config = _config("ddref")
    blocks = _blocks(config)
    cluster.deploy(config, blocks)
    return config, blocks


def _some_literals(qclass: dict, n: int) -> list:
    space = traffic.space(qclass)
    picks = np.random.default_rng(SEED).choice(space, n, replace=False)
    return [traffic.literals(qclass, int(i)) for i in picks]


@pytest.mark.parametrize("cls,options", [(c, "") for c in CLASSES]
                         + [(c, SPARSE) for c in TOPN],
                         ids=lambda v: {"": "default", SPARSE: "sparse"}
                         .get(v, v))
def test_served_path_equals_the_reference(cluster, generated, cls, options):
    config, blocks = generated
    qclass, ref = _reference(cls, config, blocks)
    for params in _some_literals(qclass, 3):
        sql = qclass["sql"].format(table=config["table"], **params)
        want = ref.answer(params)
        assert want, (cls, params)
        assert cluster.rows(options + sql) == want, (cls, params)


@pytest.mark.parametrize("options", ["", SPARSE], ids=["default", "sparse"])
def test_ties_at_the_limits_edge_fall_by_the_key(cluster, generated,
                                                 options):
    """Every line's quantity is 1, so an order's sum is the count of its
    lines that pass: a few values shared by thousands of orders, and the
    LIMIT cuts inside a run of equal sums."""
    config = dict(generated[0], table="ddties")
    blocks = [dict(b, lo_quantity=np.ones_like(b["lo_quantity"]))
              for b in generated[1]]
    if not options:
        cluster.deploy(config, blocks)
    qclass, ref = _reference("dd_top_orders", config, blocks)
    params = {"Y0": 1992, "Y1": 1997, "D0": 2, "D1": 9, "L": 101}
    want = ref.answer(params)
    edge = want[-1][1]
    beyond = ref.answer(dict(params, L=8 * params["L"]))
    assert sum(r[1] == edge for r in beyond) > sum(r[1] == edge for r in want)
    keys = [r[0] for r in want if r[1] == edge]
    assert keys == sorted(keys)
    # the orders above the run come from every segment; the run is cut by
    # the key, so what is kept of it lies in the first
    firsts = [int(b["lo_orderkey"][0]) for b in blocks[1:]]
    segment_of = {r[0]: int(np.searchsorted(firsts, r[0], "right"))
                  for r in want}
    assert {segment_of[r[0]] for r in want if r[1] > edge} \
        == set(range(SEGMENTS))
    assert 1 < len(keys) < params["L"]
    sql = qclass["sql"].format(table=config["table"], **params)
    assert cluster.rows(options + sql) == want


HERO, LOCALS = 100_001, 25


def _planted(blocks: list, params: dict) -> list:
    """In every segment: 25 customers of its own with one line of
    1,000,000,000 each, and one line of 600,000,000 of customer HERO, all
    inside the request's months and discounts. HERO is 26th in every
    segment and first in the table, with a sum above 2**31."""
    out = []
    for seg, block in enumerate(blocks):
        block = {c: v.copy() for c, v in block.items()}
        n = LOCALS + 1
        block["d_yearmonthnum"][:n] = params["M0"]
        block["lo_discount"][:n] = params["D0"]
        block["lo_custkey"][:LOCALS] = 100_100 + 100 * seg + np.arange(LOCALS)
        block["lo_revenue"][:LOCALS] = 1_000_000_000
        block["lo_custkey"][LOCALS] = HERO
        block["lo_revenue"][LOCALS] = 600_000_000
        out.append(block)
    return out


@pytest.mark.parametrize("options", ["", SPARSE], ids=["default", "sparse"])
def test_a_customer_in_every_segment_leads_only_in_the_total(
        cluster, generated, options):
    config = dict(generated[0], table="ddhero")
    params = {"M0": 199402, "M1": 199404, "D0": 3, "D1": 5, "L": 24}
    blocks = _planted(generated[1], params)
    if not options:
        cluster.deploy(config, blocks)
    qclass, ref = _reference("dd_top_customers", config, blocks)
    want = ref.answer(params)
    assert want[0] == (HERO, SEGMENTS * 600_000_000)
    assert want[1] == (100_100, 1_000_000_000)
    for block in blocks:  # in no segment is HERO among the first L
        _, alone = _reference("dd_top_customers", config, [block])
        assert HERO not in [r[0] for r in alone.answer(params)]
    sql = qclass["sql"].format(table=config["table"], **params)
    assert cluster.rows(options + sql) == want


def _server_combine(resp) -> dict:
    spans = [s for s in resp.trace_info if s["operator"] == "SERVER_COMBINE"]
    assert len(spans) == 1
    return spans[0]["attributes"]


@pytest.mark.parametrize("options", ["", SPARSE], ids=["default", "sparse"])
def test_a_traced_top_n_says_how_many_groups_reached_the_combine(
        cluster, generated, options):
    """SERVER_COMBINE's counters. `groupsFetched` (benchmark metric
    `groups_fetched_per_query`) is what crosses to the host: every
    segment's groups where the host merges (the dense tables of this toy
    size), the merged groups where the device does; `groupsCombined` then
    says that every segment's groups, none trimmed, entered that merge."""
    config, blocks = generated
    qclass = traffic.load("queries", "dd_top_customers")
    params = _some_literals(qclass, 1)[0]
    sql = qclass["sql"].format(table=config["table"], **params)
    resp = cluster.response("SET trace = true; " + options + sql)
    kept = []
    for b in blocks:
        keep = ((b["d_yearmonthnum"] >= params["M0"])
                & (b["d_yearmonthnum"] <= params["M1"])
                & (b["lo_discount"] >= params["D0"])
                & (b["lo_discount"] <= params["D1"]))
        kept.append(np.unique(b["lo_custkey"][keep]))
    groups = sum(len(k) for k in kept)
    merged = len(np.unique(np.concatenate(kept)))
    assert params["L"] < merged < groups
    attrs = _server_combine(resp)
    if options:
        assert attrs["groupsCombined"] == groups
        assert attrs["groupsFetched"] == merged
        assert attrs["deviceCut"] == 0  # 500 customers: under the threshold
    else:
        assert attrs["groupsFetched"] == groups
        assert "groupsCombined" not in attrs


# -- a key space the planner sorts by its own rule ---------------------------

WIDE_ROWS = 1 << 18
WIDE_KEYS = (130_000, 131_000, 131_500, 132_000)  # 2^17 = 131,072 between
TRIM = "SET groupTrimThreshold = 1000; SET minServerGroupTrimSize = 50; "
BIG = 1 << 50


@pytest.fixture(scope="module")
def wide(cluster):
    """`ddwide`: four segments, each with a dictionary of its own drawn
    from 1..400,000 (every key of it in some row, in no order). `q` is 1 in
    every row, so SUM(q) is shared by thousands of keys (`half` is 0.5:
    its sums are fractions); `rev` reaches
    1,000,000,000 in planted rows (sums above 2^31); `big` is 2^50 + 4j in
    the rows of three planted keys, sixteen rows each, so that their sums
    lie above 2^54, 4 and 8 apart (float64 holds them, float32 cannot tell
    them apart), and the key order runs against the sums'."""
    from pinot_tpu.spi.data_types import Schema
    from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

    rng = np.random.default_rng(SEED)
    schema = Schema.build(
        "ddwide", dimensions=[("k", "INT")],
        metrics=[("f", "INT"), ("q", "INT"), ("rev", "INT"),
                 ("big", "LONG"), ("half", "DOUBLE")])
    table_config = TableConfig(table_name="ddwide", indexing=IndexingConfig(
        no_dictionary_columns=["f", "q", "rev", "big", "half"]))
    segments = []
    for seg, card in enumerate(WIDE_KEYS):
        keys = rng.choice(np.arange(1000, 400_000), card, replace=False)
        k = np.concatenate([keys, rng.choice(keys, WIDE_ROWS - card)])
        rng.shuffle(k)
        cols = {"k": k.astype(np.int32),
                "f": rng.integers(0, 100, WIDE_ROWS).astype(np.int32),
                "q": np.ones(WIDE_ROWS, np.int32),
                "rev": rng.integers(1, 1000, WIDE_ROWS).astype(np.int32),
                "big": rng.integers(1, 1000, WIDE_ROWS).astype(np.int64),
                "half": np.full(WIDE_ROWS, 0.5)}
        # planted in every segment, in rows of their own: keys 7, 8, 9
        # (below every drawn key), four rows each
        at = np.arange(12)
        cols["k"][at] = np.repeat([7, 8, 9], 4)
        cols["f"][at] = 0
        cols["rev"][at] = 1_000_000_000 - np.repeat([2, 1, 0], 4)
        # sums over 16 rows: key 7: 2^54 + 4*16, 8: + 4*18, 9: + 4*17
        cols["big"][at] = BIG + 4 * np.repeat([1, 1, 1], 4)
        cols["big"][4] += 8 if seg == 0 else 0   # key 8
        cols["big"][8] += 4 if seg == 0 else 0   # key 9
        segments.append(cols)
    cluster.deploy_columns(schema, table_config, segments)
    return segments


def _wide_reference(segments: list, metric: str, flo: int, fhi: int):
    """(keys, exact int64 sums, every segment's groups) of SUM(metric)
    GROUP BY k over the rows with flo <= f <= fhi."""
    keys, vals, groups = [], [], 0
    for cols in segments:
        keep = (cols["f"] >= flo) & (cols["f"] <= fhi)
        keys.append(cols["k"][keep])
        vals.append(cols[metric][keep].astype(np.int64))
        groups += len(np.unique(keys[-1]))
    uniq, inv = np.unique(np.concatenate(keys), return_inverse=True)
    sums = np.zeros(len(uniq), np.int64)
    np.add.at(sums, inv, np.concatenate(vals))
    return uniq, sums, groups


def _fallbacks() -> int:
    from pinot_tpu.engine.perf_ledger import PERF_LEDGER

    return PERF_LEDGER.snapshot()["fallbackEvents"]["total"].get(
        "sparse-combine-host", 0)


@pytest.mark.parametrize("metric,order,fhi", [
    pytest.param("q", "SUM(q) DESC, k", 99, id="ties-fall-to-the-lower-key"),
    pytest.param("q", "SUM(q) DESC, k DESC", 99,
                 id="ties-fall-to-the-higher-key"),
    pytest.param("q", "SUM(q), k", 49, id="ascending-sum"),
    pytest.param("q", "SUM(q) DESC", 99, id="no-key-in-the-order"),
    pytest.param("rev", "SUM(rev) DESC, k", 99, id="sum-above-2^31"),
    pytest.param("big", "SUM(big) DESC, k DESC", 99, id="sum-above-2^53"),
])
def test_a_large_key_space_is_merged_and_cut_on_the_device(
        cluster, wide, metric, order, fhi):
    """The planner's own choice (no SET but the trim's sizes): one dispatch
    for four segments whose dictionaries straddle 2^17, every group of
    every segment in the merge (more than 100,000 a segment, no
    numGroupsLimit), the merged table cut on the device to the trim's own
    size in the whole ORDER BY's order, exact sums."""
    uniq, sums, groups = _wide_reference(wide, metric, 0, fhi)
    assert len(uniq) > 250_000 and groups > (300_000, 400_000)[fhi == 99]
    sql = (f"SELECT k, SUM({metric}) FROM ddwide WHERE f BETWEEN 0 AND "
           f"{fhi} GROUP BY k ORDER BY {order} LIMIT 20")
    by_sum = -sums if "DESC" in order.split(",")[0] else sums
    by_key = -uniq if order.endswith("k DESC") else uniq
    ranked = np.lexsort((by_key, by_sum))
    want = [(int(uniq[i]), int(sums[i])) for i in ranked[:20]]
    edge = sums[ranked[99]]  # the cut's last value: 5 x LIMIT = 100
    if metric == "q":  # the cut ends inside a run of equal sums
        assert sums[ranked[100]] == edge
        assert (sums == edge).sum() > (sums[ranked[:100]] == edge).sum() > 1
    elif metric == "rev":
        assert want[0][1] > 2 ** 31 and want[0][0] == 9
    else:  # 8, 9, 7: against both key orders, 4 apart above 2^54
        assert [w[0] for w in want[:3]] == [8, 9, 7]
        assert [w[1] - (1 << 54) for w in want[:3]] == [72, 68, 64]
        assert len({np.float32(w[1]) for w in want[:3]}) == 1
    before = _fallbacks()
    resp = cluster.response("SET trace = true; " + TRIM + sql)
    assert [tuple(r) for r in resp.result_table.rows] == want
    assert resp.num_device_dispatches == 1
    assert not resp.num_groups_limit_reached
    assert _fallbacks() == before
    attrs = _server_combine(resp)
    assert attrs["groupsCombined"] == groups
    assert attrs["deviceCut"] == attrs["groupsFetched"] == 100
    # the cut itself: what the server keeps is the first 100 of the ORDER
    # BY, ties included, not only the LIMIT's 20
    from pinot_tpu.query.parser.sql import parse_sql

    segments = cluster.server.executor.tables["ddwide_OFFLINE"].segments
    kept, _ = cluster.server.executor.execute_segments(
        parse_sql(NOCACHE + TRIM + sql), segments)
    assert sorted(kept.key_cols[0].tolist()) \
        == sorted(int(uniq[i]) for i in ranked[:100])


@pytest.mark.parametrize("where,order,groups_left", [
    pytest.param("rev > 999999000", "SUM(q) DESC, k", 3,
                 id="fewer-groups-than-the-threshold"),
    pytest.param("f < 50", "SUM(half) DESC, k", None, id="a-sum-of-halves"),
])
def test_what_the_device_does_not_cut_the_host_trims(
        cluster, wide, where, order, groups_left):
    """The cut is decided on the device, after the merge: fewer merged
    groups than groupTrimThreshold, or a ranked column that holds a
    fraction, and the merged table crosses whole in a second fetch (at the
    size the first one's header gave), for the host to trim as before."""
    keys, vals = [], []
    for cols in wide:
        keep = (cols["rev"] > 999999000) if groups_left else (cols["f"] < 50)
        keys.append(cols["k"][keep])
        vals.append(cols["q"][keep].astype(np.int64))
    uniq, inv = np.unique(np.concatenate(keys), return_inverse=True)
    sums = np.zeros(len(uniq), np.int64)
    np.add.at(sums, inv, np.concatenate(vals))
    assert len(uniq) == (groups_left or len(uniq)) and len(uniq) != 100
    ranked = np.lexsort((uniq, -sums))[:20]
    scale = 1 if groups_left else 0.5
    want = [(int(uniq[i]), sums[i] * scale) for i in ranked]
    metric = order.split(" ")[0]
    before = _fallbacks()
    resp = cluster.response(
        "SET trace = true; " + TRIM + f"SELECT k, {metric} FROM ddwide WHERE "
        f"{where} GROUP BY k ORDER BY {order} LIMIT 20")
    assert [tuple(r) for r in resp.result_table.rows] == want
    assert resp.num_device_dispatches == 1 and _fallbacks() == before
    attrs = _server_combine(resp)
    assert attrs["deviceCut"] == 0
    assert attrs["groupsFetched"] == len(uniq)
    fetch = [s["attributes"] for s in resp.trace_info
             if s["operator"] == "DEVICE_FETCH"]
    assert [a["hostFetches"] for a in fetch] == [2]


def test_every_group_of_a_large_key_space_comes_back_untrimmed(cluster, wide):
    """More than 100,000 groups in every segment (numGroupsLimit's default)
    and nothing to order by: no trim, no cut, every key of the table."""
    uniq, sums, groups = _wide_reference(wide, "q", 0, 99)
    before = _fallbacks()
    resp = cluster.response(
        "SET trace = true; SELECT k, SUM(q) FROM ddwide GROUP BY k "
        "LIMIT 1000000")
    assert sorted(tuple(r) for r in resp.result_table.rows) \
        == list(zip(uniq.tolist(), sums.tolist()))
    assert not resp.num_groups_limit_reached
    assert resp.num_device_dispatches == 1 and _fallbacks() == before
    attrs = _server_combine(resp)
    assert attrs["groupsCombined"] == groups
    assert attrs["groupsFetched"] == len(uniq) and attrs["deviceCut"] == 0


# -- the references against a loop over the rows ----------------------------


def _passes(qclass: dict, row: dict, params: dict) -> bool:
    for f in qclass["reference_params"]["filters"]:
        v = row[f["column"]]
        if "eq" in f and v != params[f["eq"]]:
            return False
        if "between" in f and not (params[f["between"][0]] <= v
                                   <= params[f["between"][1]]):
            return False
    return True


def _row_loop(qclass: dict, blocks: list, params: dict) -> list:
    spec = qclass["reference_params"]
    rows = [dict(zip(b, (int(x) for x in vals)))
            for b in blocks for vals in zip(*b.values())]
    rows = [r for r in rows if _passes(qclass, r, params)]
    if qclass["reference"] == "grouped_topn":
        sums = defaultdict(lambda: [0] * len(spec["sums"]))
        for r in rows:
            for i, c in enumerate(spec["sums"]):
                sums[r[spec["key"]]][i] += r[c]
        by = spec["sums"].index(spec["order_by_sum"])
        out = sorted(((k, *s) for k, s in sums.items()),
                     key=lambda t: (-t[1 + by], t[0]))
        return out[:params[spec["limit"]]]
    if qclass["reference"] == "distinct_min_max":
        groups = defaultdict(list)
        for r in rows:
            groups[tuple(r[g] for g in spec["group_by"])].append(r)
        return sorted(
            g + (len({r[spec["distinct"]] for r in rs}),
                 min(r[spec["min_max"]] for r in rs),
                 max(r[spec["min_max"]] for r in rs))
            for g, rs in groups.items())[:params[spec["limit"]]]
    out = sorted(tuple(r[c] for c in spec["select"]) for r in rows)
    return out[:spec["limit"]]


@pytest.mark.parametrize("cls", CLASSES)
def test_reference_equals_a_loop_over_the_rows(generated, cls):
    config, blocks = generated
    small = [{c: v[:2048] for c, v in b.items()} for b in blocks]
    qclass, ref = _reference(cls, config, small)
    if cls == "dd_order_lines":  # its ORDER BY is the SELECT list, in order
        spec = qclass["reference_params"]
        assert spec["order_by"] == spec["select"]
    for params in _some_literals(qclass, 2):
        assert ref.answer(params) == _row_loop(qclass, small, params), params


def test_float32_control_differs_where_a_sum_passes_2_to_the_24(generated):
    config, blocks = generated
    qclass = traffic.load("queries", "dd_top_customers")
    mod = _module(BENCH / "references" / "grouped_topn.py")
    names = table.generator_of(config).dictionaries(config)
    exact, rounded = (mod.Reference(qclass, config, names, acc)
                      for acc in ("exact", "float32"))
    for block in blocks:
        exact.add(block)
        rounded.add(block)
    params = _some_literals(qclass, 1)[0]
    want = exact.answer(params)
    assert max(r[1] for r in want) > 2 ** 24
    assert rounded.answer(params) != want


# -- the generator ----------------------------------------------------------


def test_orders_are_whole_numbered_through_the_table_and_have_one_customer(
        generated):
    config, blocks = generated
    last = 0
    for block in blocks:
        key = block["lo_orderkey"]
        assert key[0] == last + 1  # follows the segment before it
        assert set(np.diff(key).tolist()) <= {0, 1}  # no key left out
        last = int(key[-1])
        edges = np.flatnonzero(np.diff(key)) + 1
        lines = np.diff(np.r_[0, edges, len(key)])
        assert lines[:-1].min() >= 1 and lines.max() <= 7
        assert sorted(set(lines[:-1].tolist())) == list(range(1, 8))
        for c in ("lo_custkey", "d_yearmonthnum", "d_year"):
            firsts = block[c][np.r_[0, edges]]
            assert np.array_equal(np.repeat(firsts, lines), block[c]), c
        assert block["lo_custkey"].min() >= 1
        assert block["lo_custkey"].max() <= config["scale"]["customers"]
    # every customer buys, and nearly every one in every segment (500
    # customers, 2,000 orders a segment: 500 x e^-4 = 9 are missed)
    assert len(np.unique(np.concatenate(
        [b["lo_custkey"] for b in blocks]))) == 500
    assert all(len(np.unique(b["lo_custkey"])) > 480 for b in blocks)
    # the ten columns of ssb-flat-sf10-16seg are that generator's own
    flat = table.generator_of({"generator": "ssb_flat"})
    ten = {c: v for c, v in config["columns"].items()
           if c not in ("lo_orderkey", "lo_custkey")}
    same = flat.segment_columns(dict(config, columns=ten), ROWS, SEED, 2)
    assert all(np.array_equal(same[c], blocks[2][c]) for c in ten)
