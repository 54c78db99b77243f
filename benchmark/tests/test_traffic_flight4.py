"""The literal spaces of flight 4's three classes and the mix of
`ssb16.flight4` (`flight4-streams3`): their sizes, that every set renders
another statement, that a class's statements keep their filter factors
within a factor of 1.3 of each other, that no set covers a whole
dictionary, that three seeds repeat nothing in 2,000 requests a class, that
the roofline counts both money columns, and what `BENCHMARK.json` lists for
the cell."""

import collections
import itertools
import json
import sys
from fractions import Fraction
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import table  # noqa: E402
import traffic  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIX = traffic.load("traffic", "flight4-streams3")
CONFIG = traffic.load("configs", "ssb-flat-sf10-16seg-flight4")
NAMES = table.generator_of(CONFIG).dictionaries(CONFIG)
SPACES = {"ssb_q4_1": 5 * 10 * 966, "ssb_q4_2": 5 * 6 * 10 * 901,
          "ssb_q4_3": 25 * 6 * 25 * 201}
# the most rows a class can return; its LIMIT is at or above, and at most
# 1,000 (Q4.3's from 800: the trim rounds every one of them to 1,024)
ANSWERS = {"ssb_q4_1": 7 * 5, "ssb_q4_2": 2 * 5 * 10,
           "ssb_q4_3": 2 * 10 * 40}
LIMITS = {"ssb_q4_1": (35, 1000), "ssb_q4_2": (100, 1000),
          "ssb_q4_3": (800, 1000)}
A_CLASS = 2_000
DAYS = {1992: 366, 1993: 365, 1994: 365, 1995: 365, 1996: 366, 1997: 365,
        1998: 214}  # order dates run to 1998-08-02
WHOLE = Fraction(sum(DAYS.values()))


def filter_factor(cls, p):
    """The share of the table's lines a set of literals keeps, from the
    generator's rules: order dates uniform over 2,406 days, regions over 5
    a side, nations over 25, manufacturers over 5, categories over 25."""
    mfgrs = Fraction(len({p["MA"], p["MB"]}), 5) if "MA" in p else 1
    years = Fraction(DAYS[p["Y0"]] + DAYS[p["Y1"]]) / WHOLE \
        if "Y1" in p else 1
    if cls == "ssb_q4_3":
        return Fraction(1, 5) * Fraction(1, 25) * Fraction(1, 25) * years
    return Fraction(1, 25) * mfgrs * years


def test_the_mix_is_three_streams_of_three_equal_classes_with_no_option():
    assert MIX["clients"] == 3 and MIX["set"] == "" \
        and MIX["loop"] == "closed"
    assert [c["class"] for c in MIX["classes"]] == list(SPACES)
    assert {c["share"] for c in MIX["classes"]} == {1}
    assert MIX["deck"] == len(MIX["classes"]) == 3 \
        and MIX["order"] == "deck"
    assert MIX["warm_variants"] == 1 and MIX["warm_concurrent_s"] == 3
    assert MIX["trace_slice"] == {"start_s": 4, "seconds": 10}
    for option in ("sparseGroupBy", "deviceCombine", "numGroupsLimit"):
        assert option not in MIX["set"] + MIX["warm_set"]


@pytest.mark.parametrize("cls", list(SPACES))
def test_space_size_distinct_statements_and_level_filter_factors(cls):
    q = traffic.load("queries", cls)
    n = traffic.space(q)
    assert n == SPACES[cls] >= 25_000
    assert q["reference"] == "grouped_profit_ordered" and q["ordered"]
    # the SUM stands first (tests/test_control.py alters a row's first
    # cell), and the ORDER BY is the group keys, ascending, as published
    assert q["sql"].startswith("SELECT SUM(lo_revenue - lo_supplycost), ")
    keys = q["reference_params"]["group_by"]
    assert f"GROUP BY {', '.join(keys)} ORDER BY {', '.join(keys)} LIMIT " \
        in q["sql"]
    w = traffic.Workload(MIX, "t", 0)
    factors, statements, limits = set(), set(), set()
    for i in range(n):
        p = traffic.literals(q, i)
        statements.add(w.render(cls, p))
        factors.add(filter_factor(cls, p))
        limits.add(p["L"])
    assert len(statements) == n
    assert (min(limits), max(limits)) == LIMITS[cls]
    assert ANSWERS[cls] <= min(limits) and max(limits) <= 1_000
    assert 10 * max(factors) <= 13 * min(factors), (min(factors),
                                                     max(factors))


@pytest.mark.parametrize("cls", list(SPACES))
def test_no_literal_set_covers_a_whole_dictionary(cls):
    """A conjunct that every value of its column passes is folded away by
    the planner (engine/plan.py: FConst(True)): the statement would run
    another program than the one the warm-up compiled. Every value a
    statement names is in its column's dictionary or domain, an OR pair
    names two values, and every value of a filtered column is sent."""
    q = traffic.load("queries", cls)
    filters = q["reference_params"]["filters"]
    seen = collections.defaultdict(set)
    for i in range(0, SPACES[cls], max(1, SPACES[cls] // 20_000)):
        p = traffic.literals(q, i)
        for k, f in enumerate(filters):
            column = f["column"]
            if column in NAMES:
                values = NAMES[column]
            else:
                lo, hi = CONFIG["columns"][column]["domain"]
                values = list(range(lo, hi + 1))
            kept = {p[f["eq"]]} if "eq" in f else {p[x] for x in f["in"]}
            assert len(kept) == (1 if "eq" in f else 2)
            assert kept < set(values), (cls, column, p)
            seen[k] |= kept
        # Q4.3's customers are of the supplier nation's region
        if cls == "ssb_q4_3":
            nation = NAMES["s_nation"].index(p["SN"])
            assert NAMES["c_region"].index(p["CR"]) == nation // 5
        if "Y1" in p:
            assert p["Y1"] == p["Y0"] + 1
    for k, f in enumerate(filters):
        column = f["column"]
        assert seen[k] == (set(NAMES[column]) if column in NAMES
                           else set(range(1992, 1999))), (cls, column)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_no_class_repeats_a_statement_in_2000_requests(seed):
    w = traffic.Workload(MIX, "t", seed)
    classes = len(MIX["classes"])
    seen, sqls = collections.Counter(), set()
    warm = w.warm_picks(MIX["warm_set"])
    a_client = A_CLASS * classes // MIX["clients"]
    for client in range(MIX["clients"]):
        for cls, _, sql in itertools.islice(w.client_sequence(client),
                                            a_client):
            seen[cls] += 1
            sqls.add(sql)
    total = a_client * MIX["clients"]
    assert len(seen) == classes and sum(seen.values()) == total
    assert max(seen.values()) - min(seen.values()) <= MIX["clients"]
    assert len(sqls) == total
    # the warm-up's statements are none of the window's
    assert not {sql.replace(MIX["warm_set"], "") for _, _, sql in warm} \
        & sqls


def test_the_roofline_counts_both_money_columns():
    """`scan_hbm_roofline`'s numerator is blind to the plan: the stored
    width of every schema column in the statement's text, string literals
    dropped (MFGR#12 and UNITED STATES name no column)."""
    import roofline

    want = {"ssb_q4_1": ["d_year", "c_region", "c_nation", "s_region",
                         "p_mfgr", "lo_revenue", "lo_supplycost"],
            "ssb_q4_2": ["d_year", "c_region", "s_region", "s_nation",
                         "p_mfgr", "p_category", "lo_revenue",
                         "lo_supplycost"],
            "ssb_q4_3": ["d_year", "c_region", "s_nation", "s_city",
                         "p_category", "p_brand1", "lo_revenue",
                         "lo_supplycost"]}
    w = traffic.Workload(MIX, CONFIG["table"], 5)
    for cls, columns in want.items():
        _, _, sql = w.request(cls, 0)
        assert roofline.columns_read(sql, CONFIG) == columns, cls
        width = sum(CONFIG["columns"][c]["stored_bytes"] for c in columns)
        assert roofline.query_bytes(sql, CONFIG, 100, 10, 4) \
            == 100 * width + 10 * 4 * 8


def test_the_cell_is_listed_with_its_entries():
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == "ssb16.flight4"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG["name"], MIX["name"], 1)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG["name"]]
    assert CONFIG["reduced"] == entry["reduced"] == [
        "scale", "columns", "query_flights"]
    assert len(CONFIG["columns"]) == 11 and sum(
        c["stored_bytes"] for c in CONFIG["columns"].values()) == 18
    listed = {m["name"] for m in BENCH["per_layer"]
              if "ssb16.flight4" in m.get("workloads", ["ssb16.flight4"])}
    assert {"signed_sums_per_query", "flight4.dense_ms", "flight4.brand_ms",
            "group_slots_per_query", "limb_planes_per_query",
            "sum_scatters_per_query", "fetch_bytes_per_query",
            "groups_fetched_per_query", "lut_gathers_per_query",
            "kernel_groupby_ms", "kernel_filter_ms",
            "scan_hbm_roofline"} <= listed
    # the per-class and ungrouped-aggregate metrics of other cells are not
    assert not {"flight12.q1_ms", "flight2.q_ms", "flight3city.q_ms",
                "kernel_aggregate_ms", "tpch.q1_ms"} & listed
