"""The plain reference of a grouped top-N: ``SELECT key, SUM(a)[, SUM(b)]
... WHERE filters GROUP BY key ORDER BY SUM(a) DESC, key LIMIT n``. NumPy
over the generated columns, nothing of the program.

A query class states in ``reference_params`` what its SQL says: the
``filters`` (``_codes``), the ``key`` column, the columns summed (``sums``,
in the SELECT's order), which of them orders the answer
(``order_by_sum``, largest first, then the key, smallest first: the
statement's whole ORDER BY) and the ``limit`` (a number, or the name of
the literal that carries it). The reference is that statement computed
the long way round for every request: every row that passes the filters
adds its values to its key's sums, in a table with a place for every key
of the whole table; a key is a group if a row of it passed; all the groups
are ordered and the first ``limit`` returned as (key, sums...). Nothing is
cut before the whole table has been added up.

The rows are looked through ``ROWS`` at a time and the sums are 64-bit
integers. ``acc`` as in ``filtered_sum``: ``"float32"`` is the control,
every value and every running sum carried in float32.
"""

from __future__ import annotations

import sys
import threading
from pathlib import Path

import numpy as np

if str(Path(__file__).resolve().parent) not in sys.path:
    sys.path.insert(0, str(Path(__file__).resolve().parent))
from _codes import ROWS, Codes, limit_of  # noqa: E402

SLICE = 1 << 20  # keys of the table looked through at a time for the cut


class Reference:
    def __init__(self, qclass: dict, config: dict, dictionaries: dict,
                 acc: str = "exact"):
        spec = qclass["reference_params"]
        self.spec = spec
        self.number = np.float32 if acc == "float32" else np.int64
        self.codes = Codes(spec["filters"], config, dictionaries)
        self.blocks = []  # (filter code, key, summed columns) of the rows
        self.top = 0      # the largest key taken in
        self._tables = None
        self._lock = threading.Lock()

    def add(self, block: dict) -> None:
        """Take in a block of rows: their filter codes are computed now,
        their keys and values are kept as they are."""
        code = self.codes.of(block)
        key = block[self.spec["key"]]
        top = int(key.max()) if len(key) else 0
        with self._lock:
            self.blocks.append((code, key,
                                [block[c] for c in self.spec["sums"]]))
            self.top = max(self.top, top)

    def _sums(self, keep: np.ndarray):
        """(rows that passed, sums...) by key, over the whole table. The
        tables are made once and cleared for every request: the chip's
        host is slow with fresh memory."""
        if self._tables is None:
            self._tables = (np.zeros(self.top + 1, np.int64),
                            [np.zeros(self.top + 1, self.number)
                             for _ in self.spec["sums"]])
        count, sums = self._tables
        count.fill(0)
        for total in sums:
            total.fill(0)
        for code, key, columns in self.blocks:
            for lo in range(0, len(code), ROWS):
                hit = np.flatnonzero(keep[code[lo:lo + ROWS]])
                if not len(hit):
                    continue
                k = key[lo:lo + ROWS][hit]
                np.add.at(count, k, 1)
                for total, column in zip(sums, columns):
                    np.add.at(total, k,
                              column[lo:lo + ROWS][hit].astype(self.number))
        return count, sums

    def answer(self, params: dict) -> list:
        spec = self.spec
        count, sums = self._sums(self.codes.keep(params))
        by = sums[spec["sums"].index(spec["order_by_sum"])]
        limit = limit_of(spec, params)
        # a slice of the keys at a time: of each, the groups that could be
        # among the table's first `limit` (every group whose sum is no less
        # than the slice's `limit`-th largest; ties stay in)
        may = []
        for lo in range(0, len(count), SLICE):
            groups = np.flatnonzero(count[lo:lo + SLICE])
            if len(groups) > limit:
                s = by[lo:lo + SLICE][groups]
                groups = groups[s >= np.partition(s, -limit)[-limit]]
            may.append(groups + lo)
        may = np.concatenate(may) if may else np.zeros(0, np.int64)
        # the statement's ORDER BY: the sum, largest first, then the key
        first = may[np.lexsort((may, -by[may]))[:limit]]
        return [(int(g),) + tuple(int(total[g]) for total in sums)
                for g in first]
