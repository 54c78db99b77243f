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
        from pinot_tpu.segment.builder import SegmentBuilder

        schema, table_config = table.table_schema(config)
        names = table.generator_of(config).dictionaries(config)
        self.controller.add_schema(schema.to_json())
        t = self.controller.create_table(table_config.to_json())
        for seg, block in enumerate(blocks):
            cols = {c: np.asarray(names[c], dtype=object)[v]
                    if c in names else v for c, v in block.items()}
            name = f"{config['table']}_{seg}"
            path = str(self.dir / config["table"] / name)
            SegmentBuilder(schema, table_config, name).build(cols, path)
            self.controller.add_segment(
                t, name, {"location": path, "numDocs": len(cols["d_year"])})

    def rows(self, sql: str) -> list:
        resp = self.broker.execute_sql(NOCACHE + sql)
        assert not resp.exceptions, resp.exceptions
        assert not resp.partial_result
        return [tuple(r) for r in resp.result_table.rows]


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


def test_a_traced_top_n_says_how_many_groups_reached_the_combine(
        cluster, generated):
    """`groupsFetched` on SERVER_COMBINE (benchmark metric
    `groups_fetched_per_query`): every segment's groups, none trimmed."""
    config, blocks = generated
    qclass = traffic.load("queries", "dd_top_customers")
    params = _some_literals(qclass, 1)[0]
    sql = qclass["sql"].format(table=config["table"], **params)
    resp = cluster.broker.execute_sql("SET trace = true; " + NOCACHE + sql)
    assert not resp.exceptions, resp.exceptions
    combine = [s for s in resp.trace_info
               if s["operator"] == "SERVER_COMBINE"]
    groups = 0
    for b in blocks:
        keep = ((b["d_yearmonthnum"] >= params["M0"])
                & (b["d_yearmonthnum"] <= params["M1"])
                & (b["lo_discount"] >= params["D0"])
                & (b["lo_discount"] <= params["D1"]))
        groups += len(np.unique(b["lo_custkey"][keep]))
    assert [s["attributes"]["groupsFetched"] for s in combine] == [groups]
    assert groups > params["L"]


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
