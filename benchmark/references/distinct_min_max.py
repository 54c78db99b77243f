"""The plain reference of ``SELECT g, DISTINCTCOUNT(d), MIN(v), MAX(v) ...
WHERE filters GROUP BY g ORDER BY g LIMIT n``. NumPy over the generated
columns, nothing of the program.

A query class states in ``reference_params`` what its SQL says: the
``filters`` (``_codes``), the ``group_by`` columns, the ``distinct`` column
and the ``min_max`` column. The reference groups the whole table by every
column the class filters or groups on and by the distinct column (a count
of rows for every combination of values, and the least and the largest
value for every combination but the distinct column's, added up ``ROWS``
rows at a time), and answers a request from the combinations its literals
select: a group is returned if it has a row; its distinct count is the
number of values of the distinct column that have one. Rows come back as
(group values..., distinct count, min, max) in the order of the group
values, cut to the request's ``limit`` literal.

``acc`` is accepted as in ``filtered_sum`` and changes nothing here: the
statement has no sum, and a MIN or MAX of values under 2**24 is the same
number in float32.
"""

from __future__ import annotations

import sys
import threading
from pathlib import Path

import numpy as np

if str(Path(__file__).resolve().parent) not in sys.path:
    sys.path.insert(0, str(Path(__file__).resolve().parent))
from _codes import ROWS, Codes, domain, limit_of  # noqa: E402


class Reference:
    def __init__(self, qclass: dict, config: dict, dictionaries: dict,
                 acc: str = "exact"):
        spec = qclass["reference_params"]
        self.spec = spec
        self.codes = Codes(spec["filters"], config, dictionaries,
                           also=spec["group_by"])
        self.distinct, self.distinct_low = domain(spec["distinct"], config,
                                                  dictionaries)
        cells = self.codes.size
        self.counts = np.zeros((cells, len(self.distinct)), np.int64)
        self.least = np.full(cells, np.iinfo(np.int64).max, np.int64)
        self.largest = np.full(cells, np.iinfo(np.int64).min, np.int64)
        self._lock = threading.Lock()

    def add(self, block: dict) -> None:
        cells, width = self.counts.shape
        code = self.codes.of(block)
        counts = np.zeros(cells * width, np.int64)
        least = np.full(cells, np.iinfo(np.int64).max, np.int64)
        largest = np.full(cells, np.iinfo(np.int64).min, np.int64)
        for lo in range(0, len(code), ROWS):
            cell = code[lo:lo + ROWS]
            d = block[self.spec["distinct"]][lo:lo + ROWS].astype(np.int64)
            counts += np.bincount(
                cell.astype(np.int64) * width + d - self.distinct_low,
                minlength=cells * width)
            # least and largest by cell: the rows in the order of their
            # cells, then one reduction over each cell's run
            order = np.argsort(cell, kind="stable")
            run = cell[order]
            starts = np.flatnonzero(np.r_[True, run[1:] != run[:-1]])
            v = block[self.spec["min_max"]][lo:lo + ROWS].astype(
                np.int64)[order]
            at = run[starts]
            least[at] = np.minimum(least[at],
                                   np.minimum.reduceat(v, starts))
            largest[at] = np.maximum(largest[at],
                                     np.maximum.reduceat(v, starts))
        with self._lock:
            self.counts += counts.reshape(cells, width)
            np.minimum(self.least, least, out=self.least)
            np.maximum(self.largest, largest, out=self.largest)

    def answer(self, params: dict) -> list:
        codes, groups = self.codes, self.spec["group_by"]
        picked = [np.flatnonzero(codes.selected(c, params))
                  for c in codes.columns]
        at = np.ix_(*picked)
        width = self.counts.shape[1]
        counts = self.counts.reshape(codes.shape + (width,))[at]
        least = self.least.reshape(codes.shape)[at]
        largest = self.largest.reshape(codes.shape)[at]
        kept = [i for i, c in enumerate(codes.columns) if c in groups]
        away = tuple(i for i in range(len(codes.columns)) if i not in kept)
        counts = counts.sum(axis=away)       # group dims..., distinct value
        least, largest = least.min(axis=away), largest.max(axis=away)
        order = [kept[[codes.columns[i] for i in kept].index(g)]
                 for g in groups]
        rows = []
        for cell in zip(*np.nonzero(counts.sum(axis=-1))):
            where = dict(zip(kept, cell))
            rows.append(tuple(codes.values[i][picked[i][where[i]]]
                              for i in order)
                        + (int(np.count_nonzero(counts[cell])),
                           int(least[cell]), int(largest[cell])))
        rows.sort()
        return rows[:limit_of(self.spec, params)]
