"""The plain reference of a filtered, grouped SUM with an ORDER BY and a
LIMIT: ``SELECT SUM(a), keys... WHERE filters GROUP BY keys ORDER BY ...
LIMIT n`` (SSB's flight 3). NumPy over the generated columns, nothing of
the program.

A query class states in ``reference_params`` what its SQL says: the
``filters`` (a column ``eq`` one named literal, ``between`` two, or ``in``
a list of them: the statement's ``x = a OR x = b``), the column summed
(``sum``), the ``group_by`` columns, the whole ``order_by`` (each term a ``column``,
smallest first, strings compared as strings, or ``{"sum": "desc"}``,
largest first) and the ``limit`` (a number, or the name of the literal
that carries it). Rows come back as (sum, group values in ``group_by``'s
order), as ``filtered_sum``'s do.

The reference is that statement computed the long way round: the whole
table is added up into one exact 64-bit sum and one row count for every
combination of values of the class's columns, block by block of rows, and
a request adds the combinations its literals select, orders all its
groups and cuts at the limit. Where a class names two columns of one
hierarchy, the table has the finer one alone: ``carried_by`` says which
column carries which (a city its nation, a nation its region, a month its
year), and which coarse value a fine one carries is read off the rows
themselves, every row checked. So no table has more than ten million cells
(Q3.4: 250 x 250 x 80). A block's rows are added at their own cells:
nothing passes over the whole table for a block of rows.

``acc`` as in ``filtered_sum``: ``"exact"`` is what the configuration
guarantees; ``"float32"`` is the control (tests/control.py): values and
running sums carried in float32.
"""

from __future__ import annotations

import sys
import threading
from pathlib import Path

import numpy as np

if str(Path(__file__).resolve().parent) not in sys.path:
    sys.path.insert(0, str(Path(__file__).resolve().parent))
from _codes import ROWS, domain, limit_of  # noqa: E402

MAX_CELLS = 10_000_000


class Reference:
    def __init__(self, qclass: dict, config: dict, dictionaries: dict,
                 acc: str = "exact"):
        spec = qclass["reference_params"]
        self.spec = spec
        self.number = np.float32 if acc == "float32" else np.int64
        self.carrier = dict(spec.get("carried_by", {}))
        named = [f["column"] for f in spec["filters"]] + spec["group_by"]
        # the table's columns: the finest of every hierarchy the class names
        self.dims = list(dict.fromkeys(self._finest(c) for c in named))
        self.values, self.lows = {}, {}
        for c in dict.fromkeys(named + self.dims):
            self.values[c], self.lows[c] = domain(c, config, dictionaries)
        self.shape = tuple(len(self.values[c]) for c in self.dims)
        cells = int(np.prod(self.shape))
        if cells > MAX_CELLS:
            raise ValueError(f"{qclass['name']}: a table of {cells} cells")
        self.sums = np.zeros(cells, self.number)
        self.counts = np.zeros(cells, np.int64)
        # coarse column -> the coarse code that each code of the table's
        # column of its hierarchy carries (-1: no row seen with that value)
        self.carried = {
            c: np.full(len(self.values[self._finest(c)]), -1, np.int64)
            for c in self.carrier if c in named}
        self._lock = threading.Lock()

    def _finest(self, column: str) -> str:
        while column in self.carrier:
            column = self.carrier[column]
        return column

    def _codes(self, part: dict, column: str) -> np.ndarray:
        return part[column].astype(np.int64) - self.lows[column]

    def add(self, block: dict) -> None:
        """Take in a block of rows (column -> values or codes), ``ROWS`` at
        a time; each part's rows are brought together by cell and added at
        the cells they have."""
        spec = self.spec
        for lo in range(0, len(block[self.dims[0]]), ROWS):
            part = {c: v[lo:lo + ROWS] for c, v in block.items()}
            key = np.zeros(len(part[self.dims[0]]), np.int64)
            for c, n in zip(self.dims, self.shape):
                key = key * n + self._codes(part, c)
            w = part[spec["sum"]].astype(self.number)
            cells, at = np.unique(key, return_inverse=True)
            # bincount adds in float64: exact for ROWS integers under 2**36
            sums = np.bincount(at, weights=w).astype(self.number)
            counts = np.bincount(at)
            with self._lock:
                self.sums[cells] += sums
                self.counts[cells] += counts
            for coarse, table in self.carried.items():
                fine = self._codes(part, self._finest(coarse))
                codes = self._codes(part, coarse)
                table[fine] = codes
                if (table[fine] != codes).any():
                    raise ValueError(
                        f"{self._finest(coarse)} does not carry {coarse}")

    def _of(self, column: str) -> list:
        """The value of ``column`` that each code of the table's column of
        its hierarchy has or carries (None where no row was seen)."""
        if column not in self.carried:
            return list(self.values[column])
        return [self.values[column][k] if k >= 0 else None
                for k in self.carried[column]]

    def _selected(self, dim: str, params: dict) -> list:
        """The codes of ``dim`` that pass every filter on it and on the
        columns it carries."""
        keep = [True] * len(self.values[dim])
        for f in self.spec["filters"]:
            if self._finest(f["column"]) != dim:
                continue
            if "eq" in f:
                test = lambda v, x=params[f["eq"]]: v == x
            elif "in" in f:
                test = lambda v, xs=[params[p] for p in f["in"]]: v in xs
            else:
                lo, hi = (params[p] for p in f["between"])
                test = lambda v, lo=lo, hi=hi: lo <= v <= hi
            keep = [k and v is not None and test(v)
                    for k, v in zip(keep, self._of(f["column"]))]
        return [i for i, k in enumerate(keep) if k]

    def answer(self, params: dict) -> list:
        spec = self.spec
        picked = [self._selected(d, params) for d in self.dims]
        at = np.ix_(*picked)
        sums = self.sums.reshape(self.shape)[at]
        counts = self.counts.reshape(self.shape)[at]
        groups = spec["group_by"]
        of = [self._of(g) for g in groups]
        axis = [self.dims.index(self._finest(g)) for g in groups]
        total = {}  # group values -> sum, of the cells that have a row
        for cell in zip(*np.nonzero(counts)):
            group = tuple(of[i][picked[a][cell[a]]]
                          for i, a in enumerate(axis))
            total[group] = total.get(group, self.number(0)) + sums[cell]
        rows = [(int(total[group]),) + group for group in total]

        def rank(row):
            return tuple(-row[0] if "sum" in term
                         else row[1 + groups.index(term["column"])]
                         for term in spec["order_by"])

        rows.sort(key=rank)
        return rows[:limit_of(spec, params)]
