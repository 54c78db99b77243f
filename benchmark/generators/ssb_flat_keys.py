"""SSB ``lineorder`` as ``ssb_flat`` generates it, with two more of its
published columns (Star Schema Benchmark rev. 3, section 2; TPC-H 4.2.3
where SSB inherits it):

  lo_orderkey  the order a line belongs to. The orders are the ones
               ``ssb_flat`` draws (1 to 7 lines, one order date, whole
               orders in a segment, the last one cut at the segment's end),
               numbered 1, 2, 3, ... in the order they are generated,
               through the table: a segment's first order follows the
               last order of the segment before it. dbgen's sparse keys
               (8 used of every 32) are not kept.
  lo_custkey   the order's customer: one draw an order, uniform over
               1 .. ``scale.customers`` (SSB keeps SF x 30,000 customers;
               TPC-H's rule that every third customer has no order is not
               kept, so every customer buys).

Every other column, string dictionary and value rule is ``ssb_flat``'s own:
this module calls it and draws nothing of its again. An order's lines are
the rows ``ssb_flat`` gives one order date; the count of lines an order is
the first draw of a segment's random stream there, and is drawn again here
from the same stream.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

_KEYS = ("lo_orderkey", "lo_custkey")


def _ssb_flat():
    path = Path(__file__).resolve().parent / "ssb_flat.py"
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FLAT = _ssb_flat()
dictionaries = FLAT.dictionaries


def _lines(config: dict, rows: int, seed: int, seg: int) -> np.ndarray:
    """Lines of each order ``ssb_flat`` draws for a segment (more orders
    than the segment holds: it is cut at ``rows``)."""
    rng = np.random.default_rng([seed, config["table_id"], seg])
    return rng.integers(1, 8, rows // 3 + 8, dtype=np.int8)


def _orders_in(lines: np.ndarray, rows: int) -> int:
    """Orders with a row among a segment's first ``rows``."""
    ends = np.cumsum(lines, dtype=np.int64)
    return int(np.searchsorted(ends, rows, side="left")) + 1


def segment_columns(config: dict, rows: int, seed: int, seg: int) -> dict:
    flat = dict(config, columns={c: v for c, v in config["columns"].items()
                                 if c not in _KEYS})
    out = FLAT.segment_columns(flat, rows, seed, seg)
    lines = _lines(config, rows, seed, seg)
    held = _orders_in(lines, rows)
    if "lo_orderkey" in config["columns"]:
        first = 1 + sum(_orders_in(_lines(config, rows, seed, s), rows)
                        for s in range(seg))
        out["lo_orderkey"] = np.repeat(
            np.arange(first, first + held, dtype=np.int32),
            lines[:held])[:rows]
    if "lo_custkey" in config["columns"]:
        rng = np.random.default_rng([seed, config["table_id"], seg, 1 << 21])
        customer = rng.integers(1, config["scale"]["customers"] + 1, held,
                                dtype=np.int32)
        out["lo_custkey"] = np.repeat(customer, lines[:held])[:rows]
    return out
