"""The literal spaces of flight 3's four classes and the two mixes of PR 34
(`flight3city-stream1`, `flight2-streams3`): their sizes, that every set
renders another statement, that a class's statements keep their filter
factors within a factor of 1.3 of each other, that no set ranges over a
whole dictionary, that three seeds repeat nothing in 2,000 requests a class
(a 51 s window of `ssb16.flight3city` sends a class about 5 times; one of
`ssb16.flight2` about 600 times), and what `BENCHMARK.json` lists for the
two cells."""

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
MIX = traffic.load("traffic", "flight3city-stream1")
FLIGHT2 = traffic.load("traffic", "flight2-streams3")
CONFIG = traffic.load("configs", "ssb-flat-sf10-16seg-flight3")
NAMES = table.generator_of(CONFIG).dictionaries(CONFIG)
SPACES = {"ssb_q3_1": 5_000, "ssb_q3_2": 12_500, "ssb_q3_3": 50_000,
          "ssb_q3_4": 987_500}
# all four classes in one mix (a deck of four), to render their statements
ALL = dict(MIX, deck=4, classes=[{"class": c, "share": 1} for c in SPACES])
# the most rows a class can return: its LIMIT lies above, and at most 1,000
ANSWERS = {"ssb_q3_1": 150, "ssb_q3_2": 600, "ssb_q3_3": 24, "ssb_q3_4": 4}
A_CLASS = 2_000
DAYS = {1992: 366, 1993: 365, 1994: 365, 1995: 365, 1996: 366, 1997: 365,
        1998: 214}  # order dates run to 1998-08-02
WHOLE = Fraction(sum(DAYS.values()))


def days_of_month(name):
    month, year = NAMES["d_yearmonth"].index(name) % 12 + 1, int(name[3:])
    if name == "Aug1998":
        return 2
    if month == 2:
        return 29 if year % 4 == 0 else 28
    return 30 if month in (4, 6, 9, 11) else 31


def filter_factor(cls, p):
    """The share of the table's lines a set of literals keeps, from the
    generator's rules: order dates uniform over 2,406 days, cities over
    250, nations over 25, regions over 5."""
    years = lambda lo, hi: sum(DAYS[y] for y in range(lo, hi + 1)) / WHOLE
    if cls == "ssb_q3_1":
        return Fraction(1, 25) * years(p["Y0"], p["Y1"])
    if cls == "ssb_q3_2":
        return Fraction(1, 625) * years(p["Y0"], p["Y1"])
    if cls == "ssb_q3_3":
        return Fraction(2, 250) ** 2 * years(p["Y0"], p["Y1"])
    return Fraction(2, 250) ** 2 * days_of_month(p["M"]) / WHOLE


def test_the_mix_is_one_stream_of_two_equal_classes_with_no_option():
    assert MIX["clients"] == 1 and MIX["set"] == "" and MIX["loop"] == "closed"
    assert [c["class"] for c in MIX["classes"]] == ["ssb_q3_3", "ssb_q3_4"]
    assert {c["share"] for c in MIX["classes"]} == {1}
    assert MIX["deck"] == len(MIX["classes"]) == 2 and MIX["order"] == "deck"
    assert MIX["warm_set"] == ("SET resultCache = false; SET segmentCache = "
                               "false; SET timeoutMs = 600000; ")
    assert MIX["warm_variants"] == 1 and MIX["warm_concurrent_s"] == 0
    assert MIX["trace_slice"] == {"start_s": 4, "seconds": 24}
    for option in ("sparseGroupBy", "deviceCombine"):
        assert option not in MIX["set"] + MIX["warm_set"]


def test_flight2_is_flight12_without_flight_1():
    flight12 = traffic.load("traffic", "flight12-streams3")
    assert [c["class"] for c in FLIGHT2["classes"]] == [
        c["class"] for c in flight12["classes"][3:]] == [
            "ssb_q2_1", "ssb_q2_2", "ssb_q2_3"]
    assert FLIGHT2["deck"] == 3 and {c["share"]
                                     for c in FLIGHT2["classes"]} == {1}
    for key in ("clients", "set", "loop", "order", "warm_variants",
                "warm_set", "warm_concurrent_s", "trace_slice"):
        assert FLIGHT2[key] == flight12[key], key
    for c in FLIGHT2["classes"]:
        assert traffic.space(traffic.load("queries", c["class"])) >= 25_000


def test_month_days_add_up_to_the_generators_calendar():
    assert sum(map(days_of_month, NAMES["d_yearmonth"])) == WHOLE == 2406


@pytest.mark.parametrize("cls", list(SPACES))
def test_space_size_distinct_statements_and_level_filter_factors(cls):
    q = traffic.load("queries", cls)
    n = traffic.space(q)
    assert n == SPACES[cls] >= 5_000
    assert q["reference"] == "grouped_sum_ordered" and q["ordered"] is True
    # the SUM stands first (tests/test_control.py alters a row's first cell)
    assert q["sql"].startswith("SELECT SUM(lo_revenue), ")
    assert "ORDER BY d_year ASC, SUM(lo_revenue) DESC, " in q["sql"]
    w = traffic.Workload(ALL, "t", 0)
    factors, statements = set(), set()
    for i in range(n):
        p = traffic.literals(q, i)
        statements.add(w.render(cls, p))
        factors.add(filter_factor(cls, p))
        assert ANSWERS[cls] < p["L"] <= 1_000
    assert len(statements) == n
    assert 10 * max(factors) <= 13 * min(factors), (min(factors),
                                                     max(factors))


@pytest.mark.parametrize("cls", list(SPACES))
def test_no_literal_set_ranges_over_a_whole_dictionary(cls):
    """A conjunct that every value of its column passes is folded away by
    the planner (engine/plan.py: FConst(True)): the statement would run
    another program than the one the warm-up compiled. Every value a
    statement names is in its column's dictionary or domain."""
    q = traffic.load("queries", cls)
    seen = collections.defaultdict(set)  # filter -> the sets of values kept
    filters = q["reference_params"]["filters"]
    for i in range(0, SPACES[cls], max(1, SPACES[cls] // 20_000)):
        p = traffic.literals(q, i)
        for k, f in enumerate(filters):
            column = f["column"]
            if column in NAMES:
                values = NAMES[column]
            else:
                lo, hi = CONFIG["columns"][column]["domain"]
                values = list(range(lo, hi + 1))
            if "eq" in f:
                kept = {p[f["eq"]]}
            elif "in" in f:
                kept = {p[name] for name in f["in"]}
                assert len(kept) == len(f["in"]) == 2
                # two cities of one nation
                assert len({city[:9] for city in kept}) == 1
            else:
                lo, hi = (p[name] for name in f["between"])
                kept = {v for v in values if lo <= v <= hi}
                assert len(kept) == 6  # six consecutive years of seven
            assert kept and kept < set(values), (cls, column, p)
            seen[k].add(frozenset(kept))
    # and every value of a filtered column is sent by some statement,
    # Aug1998 apart (two days of orders: its filter factor is a fifteenth)
    for k, f in enumerate(filters):
        column = f["column"]
        sent = set().union(*seen[k])
        if column in NAMES:
            assert sent == set(NAMES[column]) - {"Aug1998"}, (cls, column)
        else:
            assert sent == set(range(1992, 1999)), (cls, column)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
@pytest.mark.parametrize("mix", [MIX, FLIGHT2, ALL],
                         ids=["flight3city-stream1", "flight2-streams3",
                              "all-of-flight-3"])
def test_no_class_repeats_a_statement_in_2000_requests(mix, seed):
    w = traffic.Workload(mix, "t", seed)
    classes = len(mix["classes"])
    seen, sqls = collections.Counter(), set()
    warm = w.warm_picks(mix["warm_set"])
    a_client = A_CLASS * classes // mix["clients"]
    for client in range(mix["clients"]):
        for cls, _, sql in itertools.islice(w.client_sequence(client),
                                            a_client):
            seen[cls] += 1
            sqls.add(sql)
    total = a_client * mix["clients"]
    assert len(seen) == classes and sum(seen.values()) == total
    assert max(seen.values()) - min(seen.values()) <= mix["clients"]
    assert len(sqls) == total
    # the warm-up's statements are none of the window's
    assert not {sql.replace(mix["warm_set"], "") for _, _, sql in warm} & sqls


def test_the_roofline_counts_every_column_a_statement_names():
    """`scan_hbm_roofline`'s numerator is blind to the plan: the stored
    width of every schema column in the statement's text, string literals
    dropped (a city's name has a space and a digit). The columns of an OR
    pair and of the IN list that says the same are counted once each."""
    import roofline

    want = {"ssb_q3_1": ["d_year", "c_region", "c_nation", "s_region",
                         "s_nation", "lo_revenue"],
            "ssb_q3_2": ["d_year", "c_nation", "c_city", "s_nation",
                         "s_city", "lo_revenue"],
            "ssb_q3_3": ["d_year", "c_city", "s_city", "lo_revenue"],
            "ssb_q3_4": ["d_year", "d_yearmonth", "c_city", "s_city",
                         "lo_revenue"]}
    w = traffic.Workload(ALL, CONFIG["table"], 5)
    for cls, columns in want.items():
        _, p, sql = w.request(cls, 0, MIX["warm_set"])
        assert roofline.columns_read(sql, CONFIG) == columns, cls
        width = len(columns) - 1 + 4
        assert roofline.query_bytes(sql, CONFIG, 100, 10, 4) \
            == 100 * width + 10 * 4 * 8
        if "CA" in p:
            listed = sql.replace(
                f"(c_city = '{p['CA']}' OR c_city = '{p['CB']}')",
                f"c_city IN ('{p['CA']}', '{p['CB']}')").replace(
                f"(s_city = '{p['SA']}' OR s_city = '{p['SB']}')",
                f"s_city IN ('{p['SA']}', '{p['SB']}')")
            assert listed != sql and " IN (" in listed
            assert roofline.columns_read(listed, CONFIG) == columns


def test_both_cells_are_listed_with_the_entries_the_issue_names():
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells["ssb16.flight3city"]["config"] == CONFIG["name"]
    assert cells["ssb16.flight3city"]["traffic"] == MIX["name"]
    assert cells["ssb16.flight2"]["config"] == "ssb-flat-sf10-16seg"
    assert cells["ssb16.flight2"]["traffic"] == FLIGHT2["name"]
    assert cells["ssb16.flight3city"]["chips"] \
        == cells["ssb16.flight2"]["chips"] == 1
    listed = {cell: {m["name"] for m in BENCH["per_layer"]
                     if cell in m.get("workloads", [cell])} for cell in cells}
    # `kernel_groupby_ms` does not see a dense table's scatters, which are
    # traced under no scope: ssb16.flight3city stays off its list
    assert listed["ssb16.flight3city"] == (
        listed["ssb16.drilldown"] - {"drilldown.topn_ms",
                                     "drilldown.other_ms",
                                     "kernel_groupby_ms"}
        | {"group_slots_per_query", "flight3city.q_ms"})
    # no operation of flight 2's programs is traced under `filter`: the
    # reader of `kernel_filter_ms` finds nothing there
    assert listed["ssb16.flight2"] == (
        listed["ssb16.flight12"] - {"flight12.q1_ms", "flight12.q2_ms",
                                    "kernel_aggregate_ms",
                                    "kernel_filter_ms"}
        | {"flight2.q_ms"})
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG["name"]]
    assert CONFIG["reduced"] == entry["reduced"] == [
        "scale", "columns", "query_flights"]
    assert len(CONFIG["columns"]) == 9 and sum(
        c["stored_bytes"] for c in CONFIG["columns"].values()) == 12
