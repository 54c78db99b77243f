"""The one general traffic generator: a mix is a data file under
``traffic/``, a query class is a data file under ``queries/``; nothing in
here knows a cell by name.

A mix names its classes with their shares, the number of closed-loop
clients and the ``SET`` options every request carries (none: the program's
defaults). A class is an SQL template and the space its literals are taken
from. Each client walks a deck that holds every class by its share and is
reshuffled every time it runs out. The literals of a class are dealt from
one shuffle of its whole space, made from ``--seed``: no two requests of a
run carry the same literals until the space is used up, then it is walked
again. So every seed sends the same classes equally often and the same
number of repeated statements (none, in a window that does not use a space
up), in another order and with other literals: a seed changes which rows a
filter keeps, never how much of the work a cache answers.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
WINDOW, WARM = 2, 3  # streams of a seed's random numbers


def load(kind: str, name: str) -> dict:
    """``benchmark/<kind>/<name>.json``."""
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def space(qclass: dict) -> int:
    """How many sets of literals a class has."""
    n = 1
    for spec in qclass.get("params", {}).values():
        n *= len(spec["values"]) if "values" in spec \
            else spec["range"][1] - spec["range"][0] + 1
    return n


def literals(qclass: dict, index: int) -> dict:
    """Set number ``index`` of a class's literals: every ``params`` entry
    from its ``range`` (whole numbers, both ends) or ``values`` (a value
    that is an object gives several literals that go together, under their
    own names), then the ``derived`` ones in file order: ``{"of": p,
    "plus": k}`` or ``{"format": "..."}`` over the literals so far
    (``"int": true`` reads the text as a number)."""
    p = {}
    for name, spec in qclass.get("params", {}).items():
        if "values" in spec:
            index, i = divmod(index, len(spec["values"]))
            value = spec["values"][i]
            p.update(value if isinstance(value, dict) else {name: value})
        else:
            lo, hi = spec["range"]
            index, i = divmod(index, hi - lo + 1)
            p[name] = lo + i
    for name, rule in qclass.get("derived", {}).items():
        if "format" in rule:
            text = rule["format"].format(**p)
            p[name] = int(text) if rule.get("int") else text
        else:
            p[name] = p[rule["of"]] + rule["plus"]
    return p


class Workload:
    """A cell's traffic for one seed."""

    def __init__(self, mix: dict, table: str, seed: int):
        self.mix, self.table, self.seed = mix, table, seed
        self.classes = {c["class"]: load("queries", c["class"])
                        for c in mix["classes"]}
        # one shuffle of each class's literal space: the window deals from
        # its front, the warm-up from its back
        self.shuffles = {
            cls: np.random.default_rng([seed, i]).permutation(space(q))
            for i, (cls, q) in enumerate(self.classes.items())}

    def render(self, cls: str, params: dict, options: str = None) -> str:
        options = self.mix["set"] if options is None else options
        return options + self.classes[cls]["sql"].format(
            table=self.table, **params)

    def request(self, cls: str, k: int, options: str = None) -> tuple:
        """(class, literals, SQL) of the class's ``k``-th set of literals
        in this seed's shuffle; a negative ``k`` counts from its end."""
        shuffle = self.shuffles[cls]
        params = literals(self.classes[cls], int(shuffle[k % len(shuffle)]))
        return cls, params, self.render(cls, params, options)

    def _deck(self) -> list:
        whole = sum(c["share"] for c in self.mix["classes"])
        deck = []
        for c in self.mix["classes"]:
            deck += [c["class"]] * (self.mix["deck"] * c["share"] // whole)
        return deck

    def client_sequence(self, client: int, stream: int = WINDOW,
                        options: str = None):
        """Endless iterator of (class, literals, SQL) for one client: its
        ``i``-th request of a class takes set ``client + i x clients`` of
        the class's shuffle (the warm-up's clients count from the end,
        behind ``warm_picks``)."""
        deck = self._deck()
        rng = np.random.default_rng([self.seed, stream, client])
        sent = dict.fromkeys(self.classes, 0)
        while True:
            for i in rng.permutation(len(deck)):
                cls = deck[i]
                k = client + sent[cls] * self.mix["clients"]
                sent[cls] += 1
                if stream == WARM:
                    k = -1 - self.mix["warm_variants"] - k
                yield self.request(cls, k, options)

    def warm_picks(self, options: str) -> list:
        """``warm_variants`` requests of every class, with literals of
        their own and ``options`` in place of the mix's."""
        return [self.request(cls, -1 - j, options) for cls in self.classes
                for j in range(self.mix["warm_variants"])]
