"""What the plain references of the drill-down classes share: the columns
a class filters on, folded into one small number a row, and the table that
says which of those numbers a request's literals keep. NumPy only, nothing
of the program.

A filter is a column ``eq``, ``lt`` or ``between`` named literals, as in
``filtered_sum``. A filtered column has a small domain (the
configuration's ``domain``, or the dictionary of a string column), so a
row's values in all of them make one code, computed once when the row is
taken in; a request then costs one look-up a row, whatever its filters.
"""

from __future__ import annotations

import numpy as np

ROWS = 1 << 16  # rows passed through NumPy at a time (see filtered_sum)


def domain(column: str, config: dict, dictionaries: dict):
    """(the values a column can take, in the order of their codes; the
    number to take from a generated value to get its code)."""
    if column in dictionaries:  # strings, generated as codes
        return list(dictionaries[column]), 0
    lo, hi = config["columns"][column]["domain"]
    return list(range(lo, hi + 1)), lo


def limit_of(spec: dict, params: dict) -> int:
    """A class's ``limit``: a number, or the name of the literal that
    carries it."""
    limit = spec["limit"]
    return params[limit] if isinstance(limit, str) else limit


class Codes:
    def __init__(self, filters: list, config: dict, dictionaries: dict,
                 also: tuple = ()):
        """The code is made of the filtered columns and of any other the
        caller wants in it (``also``: a group-by column), each once."""
        columns = list(dict.fromkeys([f["column"] for f in filters]
                                     + list(also)))
        self.filters, self.columns = filters, columns
        domains = [domain(c, config, dictionaries) for c in columns]
        self.values = [d[0] for d in domains]
        self.lows = [d[1] for d in domains]
        self.shape = tuple(len(v) for v in self.values)
        self.size = int(np.prod(self.shape))
        self.dtype = (np.uint8 if self.size <= 1 << 8 else
                      np.uint16 if self.size <= 1 << 16 else np.uint32)

    def of(self, block: dict) -> np.ndarray:
        """The code of every row of a block, ``ROWS`` at a time."""
        n = len(block[self.columns[0]])
        out = np.empty(n, self.dtype)
        for lo in range(0, n, ROWS):
            code = np.zeros(min(ROWS, n - lo), np.int64)
            for c, low, size in zip(self.columns, self.lows, self.shape):
                code = code * size + (block[c][lo:lo + ROWS].astype(np.int64)
                                      - low)
            out[lo:lo + ROWS] = code
        return out

    def selected(self, column: str, params: dict) -> np.ndarray:
        """Which values of a column the request's literals keep."""
        values = self.values[self.columns.index(column)]
        keep = np.ones(len(values), bool)
        for f in self.filters:
            if f["column"] != column:
                continue
            if "eq" in f:
                test = [v == params[f["eq"]] for v in values]
            elif "lt" in f:
                test = [v < params[f["lt"]] for v in values]
            else:
                lo, hi = (params[p] for p in f["between"])
                test = [lo <= v <= hi for v in values]
            keep &= np.asarray(test, bool)
        return keep

    def keep(self, params: dict) -> np.ndarray:
        """code -> whether a row of that code passes every filter."""
        table = np.ones(self.shape, bool)
        for axis, c in enumerate(self.columns):
            shape = [1] * len(self.shape)
            shape[axis] = -1
            table &= self.selected(c, params).reshape(shape)
        return table.reshape(-1)
