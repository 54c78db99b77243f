"""The plain reference of a filtered selection: ``SELECT a, b ... WHERE
filters ORDER BY a, b LIMIT n``. NumPy over the generated columns, nothing
of the program.

A query class states in ``reference_params`` what its SQL says: the
``filters`` (``_codes``), the columns returned (``select``), the
``order_by`` columns (all ascending; they must be among the returned ones
and decide the order of any two rows that differ) and the ``limit``. The
reference keeps the returned columns of every row and a code of its
filtered columns; for a request it looks through all rows, ``ROWS`` at a
time, takes those that pass, orders them by the whole ORDER BY and returns
the first ``limit``.

``acc`` is accepted as in ``filtered_sum`` and changes nothing: a
selection adds nothing up.
"""

from __future__ import annotations

import sys
import threading
from pathlib import Path

import numpy as np

if str(Path(__file__).resolve().parent) not in sys.path:
    sys.path.insert(0, str(Path(__file__).resolve().parent))
from _codes import ROWS, Codes, limit_of  # noqa: E402


class Reference:
    def __init__(self, qclass: dict, config: dict, dictionaries: dict,
                 acc: str = "exact"):
        spec = qclass["reference_params"]
        self.spec = spec
        self.codes = Codes(spec["filters"], config, dictionaries)
        self.blocks = []  # (filter code, returned columns) of the rows
        self._lock = threading.Lock()

    def add(self, block: dict) -> None:
        code = self.codes.of(block)
        with self._lock:
            self.blocks.append((code, [block[c] for c in self.spec["select"]]))

    def answer(self, params: dict) -> list:
        spec = self.spec
        keep = self.codes.keep(params)
        found = [[] for _ in spec["select"]]
        for code, columns in self.blocks:
            for lo in range(0, len(code), ROWS):
                hit = np.flatnonzero(keep[code[lo:lo + ROWS]])
                if len(hit):
                    for out, column in zip(found, columns):
                        out.append(column[lo:lo + ROWS][hit])
        found = [np.concatenate(f) if f else np.zeros(0, np.int64)
                 for f in found]
        by = [found[spec["select"].index(c)] for c in spec["order_by"]]
        first = np.lexsort(by[::-1])[:limit_of(spec, params)]
        return [tuple(int(f[i]) for f in found) for i in first]
