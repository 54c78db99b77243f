"""The plain reference of a filtered, grouped SUM of a difference of two
columns with an ORDER BY and a LIMIT: ``SELECT SUM(a - b), keys... WHERE
filters GROUP BY keys ORDER BY keys LIMIT n`` (SSB's flight 4, whose
profit ``lo_revenue - lo_supplycost`` is signed). NumPy over the generated
columns, nothing of the program.

A query class states in ``reference_params`` what its SQL says, as for
``grouped_sum_ordered``: the ``filters`` (a column ``eq`` one named literal,
or ``in`` a list of them: the statement's ``x = a OR x = b``), the
``group_by`` columns, the ``order_by`` (each term a ``column``, smallest
first, strings compared as strings), the ``limit`` and ``carried_by``; in
place of the column summed, the ``difference``, [a, b]. A row's a - b is
taken in int64, a block of ``ROWS`` rows at a time, and added up exactly
by ``grouped_sum_ordered.Reference`` as the column it sums: its table of
exact 64-bit sums over the finest columns of each hierarchy, its orderings
and its cut at the limit are the same.

``acc`` as in ``filtered_sum``: ``"exact"`` is what the configuration
guarantees; ``"float32"`` is the control (tests/control.py): the
difference and the running sums carried in float32.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

if str(Path(__file__).resolve().parent) not in sys.path:
    sys.path.insert(0, str(Path(__file__).resolve().parent))
import grouped_sum_ordered  # noqa: E402
from _codes import ROWS  # noqa: E402

DIFFERENCE = "a - b"  # the name the summed column takes in a block


class Reference(grouped_sum_ordered.Reference):
    def __init__(self, qclass: dict, config: dict, dictionaries: dict,
                 acc: str = "exact"):
        spec = qclass["reference_params"]
        self.difference = tuple(spec["difference"])
        super().__init__(
            dict(qclass, reference_params=dict(spec, sum=DIFFERENCE)),
            config, dictionaries, acc)

    def add(self, block: dict) -> None:
        a, b = self.difference
        for lo in range(0, len(block[a]), ROWS):
            part = {c: v[lo:lo + ROWS] for c, v in block.items()}
            part[DIFFERENCE] = part[a].astype(np.int64) - part[b]
            super().add(part)
