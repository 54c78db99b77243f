"""SSB ``lineorder`` as ``ssb_flat_flight3`` generates it, with the part's
manufacturer and the line's supply cost, the two columns query flight 4
reads that no other generator stores (Star Schema Benchmark rev. 3,
section 2; SSB's dbgen where the specification leaves the rule open):

  p_mfgr         MFGR#1 .. MFGR#5, the part's manufacturer: its brand's
                 code // 200 (a manufacturer has 5 categories of 40 brands,
                 ``ssb_flat.brand_names``' order), so mfgr ⊃ category ⊃
                 brand holds row by row
  lo_supplycost  a unit's supply cost in cents, dbgen's rule
                 6 x p_retailprice / 10, of the part the line's
                 ``lo_extendedprice`` is priced from: p_retailprice is
                 lo_extendedprice / lo_quantity exactly, so the cost comes
                 from the same part draw and no stream is drawn again

Every other column, string dictionary and value rule is ``ssb_flat``'s or
``ssb_flat_flight3``'s own: this module calls ``ssb_flat_flight3`` (which
calls ``ssb_flat``) for every column they draw, and reads the brand, price
and quantity it derives from off their columns. So ``d_year``,
``s_region``, ``p_category``, ``p_brand1`` and ``lo_revenue`` are
``ssb_flat``'s and the customer's and supplier's region, nation and city
``ssb_flat_flight3``'s for the same seed and ``table_id``.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np


def _ssb_flat_flight3():
    path = Path(__file__).resolve().parent / "ssb_flat_flight3.py"
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FLIGHT3 = _ssb_flat_flight3()
FLAT = FLIGHT3.FLAT
MFGRS = [f"MFGR#{m}" for m in range(1, 6)]
BRANDS_A_MFGR = 200

# this module's columns, and what it reads of ssb_flat's to make each
_READS = {"p_mfgr": ("p_brand1",),
          "lo_supplycost": ("lo_extendedprice", "lo_quantity")}


def supply_cost(extendedprice: np.ndarray, quantity: np.ndarray):
    """dbgen's 6 x p_retailprice / 10 (cents, integer division), with the
    retail price read back off the line's price and quantity."""
    retail = extendedprice // quantity.astype(np.int32)  # int32: < 2^18
    return 6 * retail // 10


def dictionaries(config: dict) -> dict:
    """column -> the list a code of that column indexes."""
    names = dict(FLIGHT3.dictionaries(dict(config, columns={
        c: v for c, v in config["columns"].items() if c not in _READS})))
    if "p_mfgr" in config["columns"]:
        names["p_mfgr"] = list(MFGRS)
    return names


def segment_columns(config: dict, rows: int, seed: int, seg: int) -> dict:
    """One segment's columns; only those the configuration names."""
    wanted = config["columns"]
    theirs = {c: v for c, v in wanted.items() if c not in _READS}
    for c in wanted:
        for read in _READS.get(c, ()):
            theirs.setdefault(read, {})
    got = FLIGHT3.segment_columns(dict(config, columns=theirs), rows, seed,
                                  seg)
    if "p_mfgr" in wanted:
        got["p_mfgr"] = (got["p_brand1"] // BRANDS_A_MFGR).astype(np.int8)
    if "lo_supplycost" in wanted:
        got["lo_supplycost"] = supply_cost(got["lo_extendedprice"],
                                           got["lo_quantity"])
    return {c: got[c] for c in wanted}
