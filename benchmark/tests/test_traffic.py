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


def test_literals_cover_the_space_once():
    q = traffic.load("queries", "ssb_q1_1")
    sets = [tuple(traffic.literals(q, i).items())
            for i in range(traffic.space(q))]
    assert len(set(sets)) == traffic.space(q) == 126
    assert all(p["D2"] == p["D"] + 2 for p in map(dict, sets))


def test_a_used_up_space_is_walked_again():
    w = traffic.Workload(MIX, "t", 3)
    n = traffic.space(w.classes["ssb_q2_1"])
    assert w.request("ssb_q2_1", 5) == w.request("ssb_q2_1", 5 + n)
