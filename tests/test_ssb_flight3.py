"""SSB's flight 3 over the flat row with the customer's and supplier's
columns (`benchmark/configs/ssb-flat-sf10-16seg-flight3`) at the
configuration's rehearsal size on the CPU: the served path against the
benchmark's plain reference, which is what decides `correct` for
`ssb16.flight3city` on the chip, and against the host engine.

- each of flight 3's four classes (the cell sends Q3.3 and Q3.4; Q3.1 and
  Q3.2 are in no cell yet) on two seeds' literals through
  `Broker.execute_sql` on a 4-segment table: one dispatch, the rows of
  `grouped_sum_ordered.Reference` and of the host engine, and the slots of
  the group table (`groupSlots` of the dispatch span): 4,375 for Q3.1, the
  dense table of 437,500 for Q3.2, Q3.3 and Q3.4;
- a class's first, last and middle set of literals run one executable (the
  benchmark's `window_compiles`), and an IN list plans the program of the
  published OR pair;
- the reference against a loop over the rows;
- the generator: the shared columns are `ssb_flat`'s, region ⊃ nation ⊃
  city row by row, cardinalities 5 / 25 / 250, the seed reproduces the rows.

Q3.3's and Q3.4's literals keep a handful of the table's lines at full
size and fewer at this one, so a few lines that pass them are planted in
every segment.
"""

from __future__ import annotations

import numpy as np
import pytest

from pinot_tpu.engine.plan import table_bucket

# the drill-down test's cluster (one server, backend tpu, behind one broker,
# as benchmark/run.py sets it up) and its loader of the benchmark's modules
from test_drilldown_reference import BENCH, NOCACHE, Cluster, _module, \
    table, traffic

CONFIG = traffic.load("configs", "ssb-flat-sf10-16seg-flight3")
ROWS, SEGMENTS, TABLE_SEED = CONFIG["rehearse"]["rows_per_segment"], 4, \
    2147483659
LOOPED = 8192  # rows a segment that the loop over the rows walks
SEEDS = (11, 2 ** 31 + 12345)
CLASSES = ["ssb_q3_1", "ssb_q3_2", "ssb_q3_3", "ssb_q3_4"]
# the cell's mix with all four classes, for their literals
MIX = dict(traffic.load("traffic", "flight3city-stream1"), deck=4,
           classes=[{"class": c, "share": 1} for c in CLASSES])
# a dense table has the product of its three keys' cardinalities (Q3.1's
# fits the limb kernel); above the limb kernel's slots the planner sorts
# the table (PR 35): a slot for every combination, `table_bucket`'s rounding
SLOTS = {"ssb_q3_1": 25 * 25 * 7,
         "ssb_q3_2": table_bucket(250 * 250 * 7),
         "ssb_q3_3": table_bucket(250 * 250 * 7),
         "ssb_q3_4": table_bucket(250 * 250 * 7)}
PLANTED = 48  # lines a segment for each planted set of literals


def _config() -> dict:
    return dict(CONFIG, table="f3ref", segments=SEGMENTS)


def _literals(cls: str, seed: int) -> dict:
    """The first set of a class's literals in a seed's shuffle: what the
    benchmark's first request of the class carries."""
    return traffic.Workload(MIX, "f3ref", seed).request(cls, 0)[1]


def _plant(blocks: list, names: dict) -> None:
    """In every segment, among its first `LOOPED` rows, lines that pass
    Q3.3's and Q3.4's literals of both seeds, with every column of a
    hierarchy moved together."""
    at = 0
    for cls in ("ssb_q3_3", "ssb_q3_4"):
        for seed in SEEDS:
            p = _literals(cls, seed)
            cities = [names["c_city"].index(p[k])
                      for k in ("CA", "CB", "SA", "SB")]
            month = names["d_yearmonth"].index(p["M"]) if "M" in p \
                else 12 * (p["Y0"] - 1992) + 5
            for block in blocks:
                rows = slice(at, at + PLANTED)
                c = np.resize(cities[:2], PLANTED)
                s = np.resize([cities[2], cities[2], cities[3]], PLANTED)
                block["c_city"][rows], block["s_city"][rows] = c, s
                block["c_nation"][rows] = c // 10
                block["c_region"][rows] = c // 50
                block["s_nation"][rows] = s // 10
                block["s_region"][rows] = s // 50
                block["d_yearmonth"][rows] = month
                block["d_year"][rows] = 1992 + month // 12
            at += PLANTED
    assert at <= LOOPED


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
            load_segment(self.cluster.dir / "f3ref" / f"f3ref_{seg}")
            for seg in range(SEGMENTS)])

    def reference(self, cls: str, rows: int = None, acc: str = "exact"):
        """The class's reference with every segment's first `rows` rows
        taken in (all of them by default)."""
        qclass = traffic.load("queries", cls)
        ref = _module(BENCH / "references" / f"{qclass['reference']}.py") \
            .Reference(qclass, self.config, self.names, acc)
        for block in self.blocks:
            ref.add({c: v[:rows] for c, v in block.items()})
        return qclass, ref

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
    qclass, ref = deployment.reference(cls)
    params = _literals(cls, seed)
    sql = qclass["sql"].format(table="f3ref", **params)
    want = ref.answer(params)
    assert want and isinstance(want[0][0], int), (cls, params)
    rows, resp, spans = deployment.served(sql)
    assert rows == want, (cls, params)
    host = deployment.host.execute_sql(NOCACHE + sql)
    assert not host.exceptions, host.exceptions
    assert [tuple(r) for r in host.result_table.rows] == want
    # the normal path: one batch dispatch over the four segments, into the
    # limb kernel's table (Q3.1) or the sorted table of the three keys
    assert resp.num_device_dispatches == 1 and len(spans) == 1
    assert spans[0]["numSegments"] == SEGMENTS
    assert (spans[0]["mode"], spans[0]["groupTable"]) == (
        ("group_by", "limb") if cls == "ssb_q3_1"
        else ("group_by_sparse", "sorted"))
    assert spans[0]["groupSlots"] == SLOTS[cls]
    # SUM(lo_revenue) is bounded below by 81,000: no signed SUM
    assert spans[0]["signedSums"] == 0


def test_a_class_runs_one_executable_whatever_its_literals(deployment):
    """The first and the last set of a class's space (other regions,
    nations, cities, the other window of years, another LIMIT) compile
    nothing the first request of the class did not."""
    for cls in CLASSES:
        qclass = traffic.load("queries", cls)
        sets, compiled = traffic.space(qclass), []
        for index in (0, sets - 1, sets // 2):
            params = traffic.literals(qclass, index)
            _, _, spans = deployment.served(
                qclass["sql"].format(table="f3ref", **params))
            compiled.append(spans[0]["compileMs"])
        assert compiled[1:] == [0.0, 0.0], (cls, compiled)


def test_an_in_list_plans_the_program_of_the_published_or_pair(deployment):
    qclass = traffic.load("queries", "ssb_q3_3")
    params = _literals("ssb_q3_3", SEEDS[0])
    sql = qclass["sql"].format(table="f3ref", **params)
    rows, _, spans = deployment.served(sql)
    listed = sql
    for column, a, b in (("c_city", "CA", "CB"), ("s_city", "SA", "SB")):
        pair = f"({column} = '{params[a]}' OR {column} = '{params[b]}')"
        assert pair in listed
        listed = listed.replace(
            pair, f"{column} IN ('{params[a]}', '{params[b]}')")
    rows_in, _, spans_in = deployment.served(listed)
    assert rows_in == rows and rows
    assert spans_in[0]["program"] == spans[0]["program"]
    assert "lut" in spans[0]["program"] and spans_in[0]["compileMs"] == 0.0


def _by_rows(qclass: dict, params: dict, blocks: list, names: dict) -> list:
    """The statement over the rows one by one: a dict of Python integers."""
    spec = qclass["reference_params"]
    total = {}
    for block in blocks:
        value = {c: (np.asarray(names[c], dtype=object)[v[:LOOPED]]
                     if c in names else v[:LOOPED]).tolist()
                 for c, v in block.items()}
        for i in range(LOOPED):
            for f in spec["filters"]:
                v = value[f["column"]][i]
                if "eq" in f and v != params[f["eq"]]:
                    break
                if "in" in f and v not in [params[p] for p in f["in"]]:
                    break
                if "between" in f and not (
                        params[f["between"][0]] <= v
                        <= params[f["between"][1]]):
                    break
            else:
                group = tuple(value[g][i] for g in spec["group_by"])
                total[group] = total.get(group, 0) + value[spec["sum"]][i]
    rows = [(s,) + g for g, s in total.items()]
    rows.sort(key=lambda r: tuple(
        -r[0] if "sum" in t else r[1 + spec["group_by"].index(t["column"])]
        for t in spec["order_by"]))
    limit = spec["limit"]
    return rows[:params[limit] if isinstance(limit, str) else limit]


@pytest.mark.parametrize("cls", CLASSES)
def test_reference_equals_a_loop_over_the_rows(deployment, cls):
    qclass, ref = deployment.reference(cls, LOOPED)
    for seed in SEEDS:
        params = _literals(cls, seed)
        want = _by_rows(qclass, params, deployment.blocks, deployment.names)
        assert want and ref.answer(params) == want, (cls, params)


def test_the_float32_control_answers_otherwise(deployment):
    # Q3.1's sums lie above 2**24: carried in float32 they come out other
    qclass, ref = deployment.reference("ssb_q3_1")
    _, ref32 = deployment.reference("ssb_q3_1", acc="float32")
    params = _literals("ssb_q3_1", SEEDS[0])
    exact, rounded = ref.answer(params), ref32.answer(params)
    assert len(rounded) == len(exact) and rounded != exact


def test_the_shared_columns_are_ssb_flats_and_the_hierarchies_hold():
    config = _config()
    flat = traffic.load("configs", "ssb-flat-sf10-16seg")
    flat = dict(flat, table_id=config["table_id"])
    shared = [c for c in config["columns"] if c in flat["columns"]]
    assert shared == ["d_year", "s_region", "lo_revenue"]
    generator = table.generator_of(config)
    for seed, seg in ((TABLE_SEED, 0), (7, 3)):
        rows = generator.segment_columns(config, ROWS, seed, seg)
        assert list(rows) == list(config["columns"])
        again = generator.segment_columns(config, ROWS, seed, seg)
        other = generator.segment_columns(config, ROWS, seed + 1, seg)
        for c, v in rows.items():
            assert (again[c] == v).all() and (other[c] != v).any(), c
        theirs = table.generator_of(flat).segment_columns(flat, ROWS, seed,
                                                          seg)
        for c in shared:
            assert rows[c].dtype == theirs[c].dtype
            assert (rows[c] == theirs[c]).all(), c
        # region ⊃ nation ⊃ city, row by row, on both sides
        assert (rows["c_nation"] == rows["c_city"] // 10).all()
        assert (rows["c_region"] == rows["c_nation"] // 5).all()
        assert (rows["s_nation"] == rows["s_city"] // 10).all()
        assert (rows["s_region"] == rows["s_nation"] // 5).all()
        assert (rows["d_year"]
                == 1992 + rows["d_yearmonth"].astype(np.int64) // 12).all()
        for c, v in config["columns"].items():
            if c != "lo_revenue":
                assert len(np.unique(rows[c])) == v["cardinality"], c
        # a customer's city is one for all the lines of its order: nothing
        # ties it to the supplier's
        assert (rows["c_city"] != rows["s_city"]).any()


def test_the_dictionaries_are_dbgens_names():
    config = _config()
    names = table.generator_of(config).dictionaries(config)
    assert set(names) == {c for c, v in config["columns"].items()
                          if v["type"] == "STRING"}
    for c, v in names.items():
        assert len(v) == len(set(v)) == config["columns"][c]["cardinality"]
    assert [len(names[c]) for c in ("c_region", "c_nation", "c_city")] \
        == [len(names[c]) for c in ("s_region", "s_nation", "s_city")] \
        == [5, 25, 250]
    cities, nations = names["c_city"], names["c_nation"]
    assert names["s_city"] == cities and names["s_nation"] == nations
    assert [nations[i // 10][:9].ljust(9) + str(i % 10)
            for i in range(250)] == cities
    assert cities[199] == "UNITED KI9" and cities[95] == "UNITED ST5"
    assert cities[80] == "PERU     0" and len(set(map(len, cities))) == 1
    assert names["c_region"] == names["s_region"]
    assert nations[9] == "UNITED STATES" and names["c_region"][1] == "AMERICA"
    months = names["d_yearmonth"]
    assert (months[0], months[71], months[-1]) == ("Jan1992", "Dec1997",
                                                   "Aug1998")
