"""The plain reference of a filtered SUM, grouped or not: NumPy over the
generated columns, nothing of the program.

A query class states in ``reference_params`` what its SQL says: the
``filters`` (a column ``eq``, ``lt`` or ``between`` named literals), the
columns whose product is summed, and the ``group_by`` columns. The
reference is that statement computed the long way round: it groups the
whole table by every column the class filters or groups on (one exact
integer sum and one row count per combination of values, added up block
by block of rows) and answers a request by adding the combinations its
literals select. A group is returned if it has a row; rows come back as
(sum, group values...) in the order of the group values, strings compared
as strings.

``acc`` is the arithmetic of the sums: ``"exact"`` is what the
configuration guarantees (64-bit integers); ``"float32"`` is the control
(tests/control.py): products and running sums carried in float32 (the
sum of 65,536 rows at a time is rounded to it), the step a faster device
path would be tempted to take.
"""

from __future__ import annotations

import threading

import numpy as np

ROWS = 1 << 16


class Reference:
    def __init__(self, qclass: dict, config: dict, dictionaries: dict,
                 acc: str = "exact"):
        spec = qclass["reference_params"]
        self.spec, self.acc = spec, acc
        self.dims = list(dict.fromkeys(
            [f["column"] for f in spec["filters"]] + spec["group_by"]))
        self.values, self.lows = [], []
        for c in self.dims:
            if c in dictionaries:  # strings, generated as codes
                self.values.append(list(dictionaries[c]))
                self.lows.append(0)
            else:
                lo, hi = config["columns"][c]["domain"]
                self.values.append(list(range(lo, hi + 1)))
                self.lows.append(lo)
        self.shape = tuple(len(v) for v in self.values)
        cells = int(np.prod(self.shape))
        self.sums = np.zeros(cells, np.float32 if acc == "float32"
                             else np.int64)
        self.counts = np.zeros(cells, np.int64)
        self._lock = threading.Lock()

    def add(self, block: dict) -> None:
        """Take in a block of rows (column -> values or codes), ``ROWS`` at
        a time: the temporaries stay small and in memory that is used
        again (the chip's host is slow to take back what a process
        unmaps)."""
        cells = len(self.counts)
        sums = np.zeros(cells, self.sums.dtype)
        counts = np.zeros(cells, np.int64)
        number = np.float32 if self.acc == "float32" else np.int64
        for lo in range(0, len(block[self.dims[0]]), ROWS):
            part = {c: v[lo:lo + ROWS] for c, v in block.items()}
            key = np.zeros(len(part[self.dims[0]]), np.int64)
            for c, low, n in zip(self.dims, self.lows, self.shape):
                key = key * n + (part[c].astype(np.int64) - low)
            counts += np.bincount(key, minlength=cells)
            w = part[self.spec["sum_of_product"][0]].astype(number)
            for c in self.spec["sum_of_product"][1:]:
                w = w * part[c].astype(number)
            # bincount adds in float64: exact for ROWS integers under 2**36
            sums += np.bincount(key, weights=w, minlength=cells).astype(
                sums.dtype)
        with self._lock:
            self.sums += sums
            self.counts += counts

    def _selected(self, column: str, params: dict) -> list:
        values = self.values[self.dims.index(column)]
        keep = [True] * len(values)
        for f in self.spec["filters"]:
            if f["column"] != column:
                continue
            if "eq" in f:
                test = lambda v, x=params[f["eq"]]: v == x
            elif "lt" in f:
                test = lambda v, x=params[f["lt"]]: v < x
            else:
                lo, hi = (params[p] for p in f["between"])
                test = lambda v, lo=lo, hi=hi: lo <= v <= hi
            keep = [k and test(v) for k, v in zip(keep, values)]
        return [i for i, k in enumerate(keep) if k]

    def answer(self, params: dict) -> list:
        picked = [self._selected(c, params) for c in self.dims]
        at = np.ix_(*picked)
        sums = self.sums.reshape(self.shape)[at]
        counts = self.counts.reshape(self.shape)[at]
        groups = self.spec["group_by"]
        away = tuple(i for i, c in enumerate(self.dims) if c not in groups)
        sums, counts = sums.sum(axis=away), counts.sum(axis=away)
        if not groups:
            return [(int(sums),)]
        kept = [i for i, c in enumerate(self.dims) if c in groups]
        order = [kept[[self.dims[i] for i in kept].index(g)] for g in groups]
        rows = []
        for at in zip(*np.nonzero(counts)):
            cell = dict(zip(kept, at))
            rows.append((int(sums[at]),) + tuple(
                self.values[i][picked[i][cell[i]]] for i in order))
        rows.sort(key=lambda r: r[1:])
        return rows
