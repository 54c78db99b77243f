"""The literal spaces of `drilldown-stream1`'s four classes: their sizes,
that every set renders another statement, that a class's statements keep
their filter factors within a factor of two of each other, and that three
seeds repeat nothing in 2,000 requests a class (a 51 s window sends a
class 6 or 7 times today, and 26 at the 2.05 queries/s PR 27's program
change reached)."""

import collections
import itertools
import sys
from fractions import Fraction
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import traffic  # noqa: E402

MIX = traffic.load("traffic", "drilldown-stream1")
SPACES = {"dd_top_customers": 3_510, "dd_top_orders": 2_550,
          "dd_distinct_by_year": 2_754, "dd_order_lines": 3_850}
A_CLASS = 2_000
DAYS = {1992: 366, 1993: 365, 1994: 365, 1995: 365, 1996: 366, 1997: 365,
        1998: 214}  # order dates run to 1998-08-02
MONTH_DAYS = {199808: 2}


def days_of_month(yyyymm):
    y, m = divmod(yyyymm, 100)
    if yyyymm in MONTH_DAYS:
        return MONTH_DAYS[yyyymm]
    if m == 2:
        return 29 if y % 4 == 0 else 28
    return 30 if m in (4, 6, 9, 11) else 31


def filter_factor(cls, p):
    """The share of the table's lines a set of literals keeps, from the
    generator's rules: order dates uniform over 2,406 days, discounts over
    11 values, quantities over 50."""
    whole = Fraction(sum(DAYS.values()))
    if cls == "dd_top_customers":
        months = [m for m in range(p["M0"], p["M1"] + 1)
                  if 1 <= m % 100 <= 12]
        return (sum(map(days_of_month, months)) / whole
                * Fraction(p["D1"] - p["D0"] + 1, 11))
    if cls == "dd_top_orders":
        return (sum(DAYS[y] for y in range(p["Y0"], p["Y1"] + 1)) / whole
                * Fraction(p["D1"] - p["D0"] + 1, 11))
    if cls == "dd_distinct_by_year":
        return Fraction(p["Q1"] - p["Q0"] + 1, 50)
    return DAYS[p["Y"]] / whole / 11 / 50


def test_the_mix_is_one_stream_of_four_equal_classes_with_no_option():
    assert MIX["clients"] == 1 and MIX["set"] == ""
    assert sorted(c["class"] for c in MIX["classes"]) == sorted(SPACES)
    assert {c["share"] for c in MIX["classes"]} == {1}
    assert MIX["deck"] == len(MIX["classes"])
    assert "timeoutMs = 600000" in MIX["warm_set"]
    assert "sparseGroupBy" not in MIX["warm_set"]


def test_month_days_add_up_to_the_generators_calendar():
    months = [y * 100 + m for y in range(1992, 1999) for m in range(1, 13)
              if y * 100 + m <= 199808]
    assert sum(map(days_of_month, months)) == sum(DAYS.values()) == 2406


@pytest.mark.parametrize("cls", sorted(SPACES))
def test_space_size_distinct_statements_and_level_filter_factors(cls):
    q = traffic.load("queries", cls)
    n = traffic.space(q)
    assert n == SPACES[cls] >= 2_500
    w = traffic.Workload(MIX, "t", 0)
    sets = [traffic.literals(q, i) for i in range(n)]
    assert len({w.render(cls, p) for p in sets}) == n
    factors = [filter_factor(cls, p) for p in sets]
    assert max(factors) <= 2 * min(factors), (min(factors), max(factors))


def test_ranges_stay_inside_their_dictionaries():
    """A range over a whole dictionary is folded away by the planner
    (engine/plan.py: FConst(True)): the statement would run another program
    than the one the warm-up compiled."""
    q = traffic.load("queries", "dd_top_orders")
    for p in (traffic.literals(q, i) for i in range(2_550)):
        assert 1992 <= p["Y0"] == p["Y1"] <= 1997
        assert 0 <= p["D0"] and p["D1"] <= 10 and 100 <= p["L"] <= 116
        assert 5 <= p["D1"] - p["D0"] + 1 <= 9
    q = traffic.load("queries", "dd_top_customers")
    for p in (traffic.literals(q, i) for i in range(3_510)):
        assert p["D1"] == p["D0"] + 2 <= 10 and 20 <= p["L"] <= 24
        assert 199201 <= p["M0"] < p["M1"] <= 199808
    q = traffic.load("queries", "dd_distinct_by_year")
    for p in (traffic.literals(q, i) for i in range(2_754)):
        assert 1 <= p["Q0"] < p["Q1"] <= 50 and 7 < p["L"]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_no_class_repeats_a_statement_in_2000_requests(seed):
    w = traffic.Workload(MIX, "t", seed)
    seen, sqls = collections.Counter(), set()
    warm = w.warm_picks(MIX["warm_set"])
    for cls, _, sql in itertools.islice(w.client_sequence(0),
                                        A_CLASS * len(SPACES)):
        seen[cls] += 1
        sqls.add(sql)
    assert set(seen.values()) == {A_CLASS}
    assert len(sqls) == A_CLASS * len(SPACES)
    # the warm-up's statements are none of the window's
    assert not {sql.replace(MIX["warm_set"], "") for _, _, sql in warm} & sqls
