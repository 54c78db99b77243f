"""SSB's flight 4 over the flat row with the part's manufacturer and the
line's supply cost (`benchmark/configs/ssb-flat-sf10-16seg-flight4`) at
the configuration's rehearsal size on the CPU: the served path against the
benchmark's plain reference, which is what decides `correct` for
`ssb16.flight4` on the chip, and against the host engine.

- each of flight 4's three classes on two seeds' literals through
  `Broker.execute_sql` on a 4-segment table: one dispatch, the rows of
  `grouped_profit_ordered.Reference` and of the host engine, the group
  table (`groupSlots`, `groupTable` of the dispatch span: the limb
  kernel's 175 and 4,375 slots; for Q4.3 the sorted table trimmed in key
  order to the LIMIT's power of two, 1,024) and the profit's signed sum
  (`signedSums` 1; 0 where the same query sums the revenue alone);
- rows planted whose supply cost exceeds their revenue, so that the limb
  pass's signed digits and the sorted table's signed payload meet negative
  values and groups whose total is negative;
- one executable a class across LIMITs 800, 900 and 1,000 and across
  OFFSETs (the benchmark's `window_compiles`), and the key-ordered trim
  exact at a LIMIT and an OFFSET below the answer: Q4.3's at most 1,024
  passing rows a segment are gathered and sorted alone
  (`kernels._passing_rows`), a LIMIT of 5 or 17 sorts the segment;
- the reference against a loop over the rows;
- the generator: the shared columns are `ssb_flat_flight3`'s, mfgr ⊃
  category ⊃ brand and region ⊃ nation ⊃ city row by row, cardinalities,
  the supply-cost rule, the seed reproduces the rows.
"""

from __future__ import annotations

import numpy as np
import pytest

# the drill-down test's cluster (one server, backend tpu, behind one broker,
# as benchmark/run.py sets it up) and its loader of the benchmark's modules
from test_drilldown_reference import BENCH, NOCACHE, Cluster, _module, \
    table, traffic

CONFIG = traffic.load("configs", "ssb-flat-sf10-16seg-flight4")
ROWS, SEGMENTS, TABLE_SEED = CONFIG["rehearse"]["rows_per_segment"], 4, \
    2147483659
SEEDS = (11, 2 ** 31 + 12345)
CLASSES = ["ssb_q4_1", "ssb_q4_2", "ssb_q4_3"]
MIX = traffic.load("traffic", "flight4-streams3")
# the limb kernel's dense tables (d_year x c_nation; d_year x s_nation x
# p_category), and the sorted table of d_year x s_city x p_brand1 trimmed
# in key order to OFFSET + LIMIT (800 to 1,000) rounded up to 2^10
SLOTS = {"ssb_q4_1": 7 * 25, "ssb_q4_2": 7 * 25 * 25, "ssb_q4_3": 1024}
TABLES = {"ssb_q4_1": "limb", "ssb_q4_2": "limb", "ssb_q4_3": "sorted"}
PLANTED = 48  # lines a segment for each planted set of literals
LOSS = (81_000, 125_940)  # the least revenue, the largest supply cost


def _config() -> dict:
    return dict(CONFIG, table="f4ref", segments=SEGMENTS)


def _literals(cls: str, seed: int) -> dict:
    """The first set of a class's literals in a seed's shuffle: what the
    benchmark's first request of the class carries."""
    return traffic.Workload(MIX, "f4ref", seed).request(cls, 0)[1]


def _sql(cls: str, params: dict) -> str:
    return traffic.load("queries", cls)["sql"].format(table="f4ref",
                                                      **params)


def _planted_keys(cls: str, p: dict, names: dict, i: np.ndarray) -> tuple:
    """(d_year, c_nation, s_city, p_brand1) codes of planted lines that pass
    a class's literals; line ``i`` of Q4.3's falls into group ``i`` of 48
    (16 brands x 3 cities x 2 years), the others into one group."""
    if cls == "ssb_q4_3":
        region = names["c_region"].index(p["CR"])
        nation = names["s_nation"].index(p["SN"])
        category = names["p_category"].index(p["CAT"])
        return (p["Y0"] + i % 2, region * 5, nation * 10 + i % 3,
                category * 40 + i % 16)
    region = names["c_region"].index(p["R"])
    mfgr = names["p_mfgr"].index(p["MA"])
    return p.get("Y0", 1992), region * 5, region * 50, mfgr * 200


def _passes(block: dict, spec: dict, params: dict, names: dict):
    """Rows of a block that pass a class's filters (EQs and OR pairs)."""
    keep = np.ones(len(block["d_year"]), bool)
    for f in spec["filters"]:
        literals = [params[f["eq"]]] if "eq" in f else \
            [params[x] for x in f["in"]]
        codes = [names[f["column"]].index(v) if f["column"] in names else v
                 for v in literals]
        keep &= np.isin(block[f["column"]], codes)
    return keep


def _plant(blocks: list, names: dict) -> None:
    """In every segment, lines that pass each class's literals of both
    seeds, with every column of a hierarchy moved together and a profit of
    81,000 - 125,940 < 0; in Q4.1's and Q4.2's group of them every other
    line's profit is made negative too, so that the group's total is."""
    at = 0
    for cls in CLASSES:
        spec = traffic.load("queries", cls)["reference_params"]
        for seed in SEEDS:
            p = _literals(cls, seed)
            year, c_nation, s_city, brand = _planted_keys(
                cls, p, names, np.arange(PLANTED))
            for block in blocks:
                rows = slice(at, at + PLANTED)
                block["d_year"][rows] = year
                block["c_nation"][rows] = c_nation
                block["c_region"][rows] = c_nation // 5
                block["s_city"][rows] = s_city
                block["s_nation"][rows] = s_city // 10
                block["s_region"][rows] = s_city // 50
                block["p_brand1"][rows] = brand
                block["p_category"][rows] = brand // 40
                block["p_mfgr"][rows] = brand // 200
                block["lo_revenue"][rows], block["lo_supplycost"][rows] = LOSS
                if cls != "ssb_q4_3":
                    group = _passes(block, spec, p, names)
                    for g in spec["group_by"]:
                        group &= block[g] == block[g][at]
                    block["lo_revenue"][group] = LOSS[0]
                    block["lo_supplycost"][group] = LOSS[1]
            at += PLANTED
    assert at <= ROWS


class Deployment:
    """The table at the rehearsal's size on the cluster, and the same
    segments under the host engine."""

    def __init__(self):
        from pinot_tpu.engine.query_executor import QueryExecutor
        from pinot_tpu.segment.loader import load_segment

        self.config = _config()
        generator = table.generator_of(self.config)
        self.names = generator.dictionaries(self.config)
        self.blocks = [generator.segment_columns(self.config, ROWS,
                                                 TABLE_SEED, seg)
                       for seg in range(SEGMENTS)]
        _plant(self.blocks, self.names)
        self.cluster = Cluster()
        self.cluster.deploy(self.config, self.blocks)
        schema, _ = table.table_schema(self.config)
        self.host = QueryExecutor(backend="host")
        self.host.add_table(schema, [
            load_segment(self.cluster.dir / "f4ref" / f"f4ref_{seg}")
            for seg in range(SEGMENTS)])
        self.refs = {}

    def reference(self, cls: str, acc: str = "exact"):
        """The class's reference with every segment taken in."""
        if (cls, acc) not in self.refs:
            qclass = traffic.load("queries", cls)
            ref = _module(BENCH / "references"
                          / f"{qclass['reference']}.py").Reference(
                qclass, self.config, self.names, acc)
            for block in self.blocks:
                ref.add(block)
            self.refs[cls, acc] = ref
        return self.refs[cls, acc]

    def served(self, sql: str):
        resp = self.cluster.response("SET trace = true; " + sql)
        spans = [s["attributes"] for s in resp.trace_info
                 if s["operator"] == "family_dispatch"]
        return [tuple(r) for r in resp.result_table.rows], resp, spans


@pytest.fixture(scope="module")
def deployment():
    d = Deployment()
    yield d
    d.cluster.server.stop()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cls", CLASSES)
def test_served_path_equals_the_reference_and_the_host_engine(
        deployment, cls, seed):
    params = _literals(cls, seed)
    sql = _sql(cls, params)
    want = deployment.reference(cls).answer(params)
    assert want and isinstance(want[0][0], int), (cls, params)
    # the planted lines: a group whose profit total is negative
    assert min(row[0] for row in want) < 0, (cls, params)
    rows, resp, spans = deployment.served(sql)
    assert rows == want, (cls, params)
    host = deployment.host.execute_sql(NOCACHE + sql)
    assert not host.exceptions, host.exceptions
    assert [tuple(r) for r in host.result_table.rows] == want
    # one batch dispatch over the four segments, into the limb kernel's
    # table or the sorted table trimmed in key order; the profit is the
    # program's one signed SUM
    assert resp.num_device_dispatches == 1 and len(spans) == 1
    assert spans[0]["numSegments"] == SEGMENTS
    assert spans[0]["groupTable"] == TABLES[cls]
    assert spans[0]["groupSlots"] == SLOTS[cls]
    assert spans[0]["signedSums"] == 1


def test_a_sum_of_the_revenue_alone_is_not_signed(deployment):
    params = _literals("ssb_q4_3", SEEDS[0])
    sql = _sql("ssb_q4_3", params).replace(
        "SUM(lo_revenue - lo_supplycost)", "SUM(lo_revenue)")
    _, _, spans = deployment.served(sql)
    assert spans[0]["signedSums"] == 0 and spans[0]["groupSlots"] == 1024


def test_the_limb_pass_and_the_sort_see_negative_profits(deployment):
    # every segment holds planted lines whose profit is below zero, and
    # the served rows keep the negative groups' totals exactly
    for block in deployment.blocks:
        loss = block["lo_revenue"].astype(np.int64) - block["lo_supplycost"]
        assert (loss < 0).sum() >= PLANTED * len(CLASSES) * len(SEEDS)
    for cls in CLASSES:
        params = _literals(cls, SEEDS[1])
        rows, _, _ = deployment.served(_sql(cls, params))
        assert any(r[0] < 0 for r in rows), cls


@pytest.mark.parametrize("cls", CLASSES)
def test_one_executable_a_class_across_limits_and_offsets(deployment, cls):
    """LIMITs 800, 900 and 1,000 and OFFSETs below them compile nothing the
    first statement did not: the key-ordered trim keeps OFFSET + LIMIT
    rounded up to a power of two, so Q4.3's table has 1,024 slots for all
    of them; the rows are the reference's, cut after the OFFSET."""
    params = _literals(cls, SEEDS[0])
    whole = deployment.reference(cls).answer(dict(params, L=1000))
    compiled = []
    for limit, offset in ((800, 0), (900, 0), (1000, 0), (800, 100),
                          (850, 150)):
        sql = _sql(cls, dict(params, L=limit))
        if offset:
            sql += f" OFFSET {offset}"
        rows, _, spans = deployment.served(sql)
        assert rows == whole[offset:offset + limit], (cls, limit, offset)
        assert spans[0]["groupSlots"] == SLOTS[cls]
        compiled.append(spans[0]["compileMs"])
    assert compiled[1:] == [0.0] * 4, (cls, compiled)


@pytest.mark.parametrize("limit,offset,slots", [(5, 0, 8), (7, 9, 16),
                                                (17, 0, 32)])
def test_the_key_ordered_trim_is_exact_below_the_answer(deployment, limit,
                                                        offset, slots):
    params = _literals("ssb_q4_3", SEEDS[0])
    whole = deployment.reference("ssb_q4_3").answer(dict(params, L=1000))
    assert len(whole) > offset + limit
    sql = _sql("ssb_q4_3", dict(params, L=limit)) + (
        f" OFFSET {offset}" if offset else "")
    rows, resp, spans = deployment.served(sql)
    assert rows == whole[offset:offset + limit]
    assert spans[0]["groupSlots"] == slots
    assert not resp.num_groups_limit_reached


def _by_rows(cls: str, params: dict, blocks: list, names: dict) -> list:
    """The statement over the rows one by one: a dict of Python integers."""
    spec = traffic.load("queries", cls)["reference_params"]
    total = {}
    for block in blocks:
        value = {c: (np.asarray(names[c], dtype=object)[v]
                     if c in names else v).tolist()
                 for c, v in block.items()}
        for i in range(len(value["d_year"])):
            for f in spec["filters"]:
                v = value[f["column"]][i]
                if "eq" in f and v != params[f["eq"]]:
                    break
                if "in" in f and v not in [params[x] for x in f["in"]]:
                    break
            else:
                group = tuple(value[g][i] for g in spec["group_by"])
                a, b = spec["difference"]
                total[group] = total.get(group, 0) + value[a][i] \
                    - value[b][i]
    rows = sorted(((s,) + g for g, s in total.items()),
                  key=lambda r: r[1:])
    limit = spec["limit"]
    return rows[:params[limit] if isinstance(limit, str) else limit]


@pytest.mark.parametrize("cls", CLASSES)
def test_reference_equals_a_loop_over_the_rows(deployment, cls):
    # every class orders by its group keys, in the select list's order
    spec = traffic.load("queries", cls)["reference_params"]
    assert spec["order_by"] == [{"column": g} for g in spec["group_by"]]
    for seed in SEEDS:
        params = _literals(cls, seed)
        want = _by_rows(cls, params, deployment.blocks, deployment.names)
        assert want == deployment.reference(cls).answer(params), \
            (cls, params)


def test_the_float32_control_answers_otherwise(deployment):
    # Q4.1's profit sums lie above 2**24: carried in float32 they come out
    # other
    params = _literals("ssb_q4_1", SEEDS[0])
    exact = deployment.reference("ssb_q4_1").answer(params)
    rounded = deployment.reference("ssb_q4_1", "float32").answer(params)
    assert len(rounded) == len(exact) and rounded != exact


def test_the_generator_keeps_flight3s_columns_and_dbgens_rules():
    config = _config()
    flight3 = traffic.load("configs", "ssb-flat-sf10-16seg-flight3")
    flight3 = dict(flight3, table_id=config["table_id"])
    shared = [c for c in config["columns"] if c in flight3["columns"]]
    assert shared == ["d_year", "c_region", "c_nation", "s_region",
                      "s_nation", "s_city", "lo_revenue"]
    flat = traffic.load("configs", "ssb-flat-sf10-16seg")
    flat = dict(flat, table_id=config["table_id"])
    generator = table.generator_of(config)
    for seed, seg in ((TABLE_SEED, 0), (7, 3)):
        rows = generator.segment_columns(config, ROWS, seed, seg)
        assert list(rows) == list(config["columns"])
        again = generator.segment_columns(config, ROWS, seed, seg)
        other = generator.segment_columns(config, ROWS, seed + 1, seg)
        for c, v in rows.items():
            assert (again[c] == v).all() and (other[c] != v).any(), c
        theirs = table.generator_of(flight3).segment_columns(
            flight3, ROWS, seed, seg)
        for c in shared:
            assert rows[c].dtype == theirs[c].dtype
            assert (rows[c] == theirs[c]).all(), c
        priced = table.generator_of(flat).segment_columns(
            dict(flat, columns={c: flat["columns"][c] for c in (
                "p_category", "p_brand1", "lo_quantity", "lo_extendedprice",
                "lo_revenue")}), ROWS, seed, seg)
        assert (rows["p_brand1"] == priced["p_brand1"]).all()
        assert (rows["p_category"] == priced["p_category"]).all()
        # dbgen: a unit's supply cost is 6 x p_retailprice / 10, of the
        # part the line is priced from
        retail = priced["lo_extendedprice"] // priced["lo_quantity"]
        assert (retail * priced["lo_quantity"]
                == priced["lo_extendedprice"]).all()
        assert (rows["lo_supplycost"] == 6 * retail // 10).all()
        lo, hi = config["columns"]["lo_supplycost"]["domain"]
        assert lo <= rows["lo_supplycost"].min() \
            and rows["lo_supplycost"].max() <= hi
        # by dbgen's rules a line's revenue is at least 0.9 of a unit's
        # price and its cost 0.6 of it, so each generated profit is
        # positive; the columns' bounds say no such thing, and the program
        # plans the SUM signed (the planted lines make negatives real)
        profit = rows["lo_revenue"].astype(np.int64) - rows["lo_supplycost"]
        row_lo, row_hi = config["profit_bound"]["row"]
        assert row_lo < 0 < profit.min() <= profit.max() <= row_hi
        assert config["profit_bound"]["member_total"] \
            == config["rows_per_segment"] * max(-row_lo, row_hi) < 2 ** 49
        # mfgr ⊃ category ⊃ brand and region ⊃ nation ⊃ city, row by row
        assert (rows["p_mfgr"] == rows["p_brand1"] // 200).all()
        assert (rows["p_category"] == rows["p_brand1"] // 40).all()
        assert (rows["c_region"] == rows["c_nation"] // 5).all()
        assert (rows["s_nation"] == rows["s_city"] // 10).all()
        assert (rows["s_region"] == rows["s_nation"] // 5).all()
        for c, v in config["columns"].items():
            if v["role"] == "dimension":
                assert len(np.unique(rows[c])) == v["cardinality"], c


def test_the_dictionaries_are_dbgens_names():
    config = _config()
    names = table.generator_of(config).dictionaries(config)
    assert set(names) == {c for c, v in config["columns"].items()
                          if v["type"] == "STRING"}
    for c, v in names.items():
        assert len(v) == len(set(v)) == config["columns"][c]["cardinality"]
    assert names["p_mfgr"] == [f"MFGR#{m}" for m in range(1, 6)]
    brands, categories = names["p_brand1"], names["p_category"]
    assert all(brands[b].startswith(categories[b // 40])
               and categories[b // 40].startswith(names["p_mfgr"][b // 200])
               for b in range(1000))
    assert names["s_city"][95] == "UNITED ST5"
    assert names["s_nation"][9] == "UNITED STATES"
    assert names["c_region"][1] == names["s_region"][1] == "AMERICA"
