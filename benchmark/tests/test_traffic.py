"""The traffic generator: every seed sends the same work."""

import collections
import itertools
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import traffic  # noqa: E402

MIX = traffic.load("traffic", "flight12-streams3")


def sent(seed, n):
    w = traffic.Workload(MIX, "t", seed)
    out = [r for j in range(MIX["warm_variants"]) for r in w.warm_picks("")]
    for c in range(MIX["clients"]):
        out += itertools.islice(w.client_sequence(c, traffic.WARM, ""), n)
        out += itertools.islice(w.client_sequence(c), n)
    return w, out


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_no_statement_repeats_within_a_run_and_classes_are_level(seed):
    w, out = sent(seed, 36)
    assert len({sql for _, _, sql in out}) == len(out)
    window = collections.Counter(
        cls for c in range(MIX["clients"])
        for cls, _, _ in itertools.islice(w.client_sequence(c), 36))
    assert set(window.values()) == {36 * MIX["clients"] // len(w.classes)}


def test_seeds_differ_and_a_seed_repeats():
    a, b = sent(1, 12)[1], sent(2, 12)[1]
    assert a == sent(1, 12)[1] and a != b


# a window of 51 s at 50 queries/s sends a class about 430 times; the
# spaces are sized for a program more than twice as fast
A_WINDOW = 1_000
SPACES = {"ssb_q1_1": 30_870, "ssb_q1_2": 26_568, "ssb_q1_3": 115_128,
          "ssb_q2_1": 25_000, "ssb_q2_2": 41_250, "ssb_q2_3": 50_000}


@pytest.mark.parametrize("cls", sorted(SPACES))
def test_literals_cover_the_space_once_and_it_outlasts_20_windows(cls):
    q = traffic.load("queries", cls)
    n = traffic.space(q)
    assert n == SPACES[cls] >= 20 * A_WINDOW
    w = traffic.Workload(MIX, "t", 0)
    sqls = {w.render(cls, traffic.literals(q, i)) for i in range(n)}
    assert len(sqls) == n


def test_derived_literals_and_literals_that_go_together():
    q = traffic.load("queries", "ssb_q1_1")
    assert all(p["D2"] == p["D"] + 2 and 2 <= p["Q"] <= 50
               for p in (traffic.literals(q, i) for i in range(0, 30_870, 7)))
    # Q2.2: always eight brands of one category, by the order of the strings
    q = traffic.load("queries", "ssb_q2_2")
    brands = [f"MFGR#23{b}" for b in range(1, 41)]
    for i in range(0, traffic.space(q), 11):
        p = traffic.literals(q, i)
        lo, hi = (p[k].replace(f"MFGR#{p['M']}{p['C']}", "MFGR#23")
                  for k in ("B1", "B2"))
        assert sum(lo <= b <= hi for b in brands) == 8, p


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_no_class_repeats_a_statement_within_20_windows(seed):
    w = traffic.Workload(MIX, "t", seed)
    a_client = -(-20 * A_WINDOW * len(w.classes) // MIX["clients"]
                 // MIX["deck"]) * MIX["deck"]  # whole decks
    seen = collections.Counter()
    sqls = set()
    for c in range(MIX["clients"]):
        for cls, _, sql in itertools.islice(w.client_sequence(c), a_client):
            seen[cls] += 1
            sqls.add(sql)
    assert len(set(seen.values())) == 1 and seen["ssb_q2_1"] >= 20 * A_WINDOW
    assert len(sqls) == a_client * MIX["clients"]


def test_a_used_up_space_is_walked_again():
    w = traffic.Workload(MIX, "t", 3)
    n = traffic.space(w.classes["ssb_q2_1"])
    assert w.request("ssb_q2_1", 5) == w.request("ssb_q2_1", 5 + n)
