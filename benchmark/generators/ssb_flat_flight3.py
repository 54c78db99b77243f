"""SSB ``lineorder`` as ``ssb_flat`` generates it, with the customer,
supplier and date attributes that query flight 3 reads joined into the fact
row (Star Schema Benchmark rev. 3, section 2; TPC-H 4.2.3 where SSB
inherits it):

  customer   one customer an order, uniform over 1 .. ``scale.customers``
             (``ssb_flat_keys``' rule and its draw: the order's customer is
             the ``lo_custkey`` that module gives the order). A customer has
             a city, drawn per customer, uniform over the 250
  supplier   the line's supplier is the ``lo_suppkey`` ``ssb_flat`` draws;
             its nation is the one ``ssb_flat`` draws for its region; its
             city is that nation and a digit 0..9 drawn per supplier
  city       the nation's name cut or padded to 9 characters and a digit
             0..9 (``UNITED KI1``, ``PERU     0``); a city's code // 10 is
             its nation's, a nation's code // 5 its region's: the 25 TPC-H
             nations are listed by region, in ``ssb_flat``'s order of regions
  date       d_yearmonth = three-letter month + year of the order date
             (``Jan1992`` .. ``Aug1998``), code = months since Jan1992

Every other column, string dictionary and value rule is ``ssb_flat``'s own:
this module calls it (through ``ssb_flat_keys``, for the order's customer)
and draws nothing of its again, so ``d_year``, ``s_region`` and
``lo_revenue`` are ``ssb_flat``'s for the same seed and ``table_id``. What
``ssb_flat`` draws and does not return (the supplier's nation, the line's
supplier) is drawn again here from the same streams, as
``ssb_flat_keys._lines`` does. The keys themselves are stored nowhere: no
statement of flight 3 reads them.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np


def _ssb_flat_keys():
    path = Path(__file__).resolve().parent / "ssb_flat_keys.py"
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


KEYS = _ssb_flat_keys()
FLAT = KEYS.FLAT

# five nations a region, in the order of ssb_flat.REGIONS (TPC-H 4.2.3)
NATIONS = ["ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE",
           "ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES",
           "CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM",
           "FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM",
           "EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA"]
CITIES = [f"{nation[:9]:<9}{digit}" for nation in NATIONS
          for digit in range(10)]
MONTH_NAMES = ["Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
               "Oct", "Nov", "Dec"]
MONTHS = [f"{MONTH_NAMES[m % 12]}{1992 + m // 12}" for m in range(80)]

_DTYPES = {"c_region": np.int8, "c_nation": np.int8, "c_city": np.int16,
           "s_nation": np.int8, "s_city": np.int16, "d_yearmonth": np.int8}
# what this module reads of ssb_flat's and ssb_flat_keys' columns
_READ = ("lo_custkey", "d_yearmonthnum")


def dictionaries(config: dict) -> dict:
    """column -> the list a code of that column indexes."""
    names = dict(FLAT.dictionaries(config), c_region=list(FLAT.REGIONS),
                 c_nation=list(NATIONS), c_city=list(CITIES),
                 s_nation=list(NATIONS), s_city=list(CITIES),
                 d_yearmonth=list(MONTHS))
    return {c: v for c, v in names.items() if c in config["columns"]}


def _dimension_tables(config: dict, seed: int):
    """(city code of each supplier, city code of each customer): the same
    for every segment of a seed. The supplier's nation is the second draw
    of ``ssb_flat._dimension_tables``' stream, made again here."""
    scale = config["scale"]
    rng = np.random.default_rng([seed, config["table_id"], 1 << 20])
    rng.integers(0, 1000, scale["parts"] + 1)  # the parts' brands
    supp_nation = rng.integers(0, 25, scale["suppliers"] + 1)
    rng = np.random.default_rng([seed, config["table_id"], (1 << 20) + 1])
    supp_city = supp_nation * 10 + rng.integers(0, 10, len(supp_nation))
    cust_city = rng.integers(0, 250, scale["customers"] + 1)
    return supp_city.astype(np.int16), cust_city.astype(np.int16)


def _suppkeys(config: dict, rows: int, seed: int, seg: int):
    """(first row, the supplier of each line) a chunk at a time, as
    ``ssb_flat.segment_columns`` draws them: the same calls on the same
    stream, in its order."""
    scale = config["scale"]
    rng = np.random.default_rng([seed, config["table_id"], seg])
    orders = len(rng.integers(1, 8, rows // 3 + 8, dtype=np.int8))
    rng.integers(0, FLAT.ORDER_DAYS, orders, dtype=np.int16)
    for lo in range(0, rows, FLAT.CHUNK):
        n = min(FLAT.CHUNK, rows - lo)
        rng.integers(1, scale["parts"] + 1, n)  # lo_partkey
        yield lo, rng.integers(1, scale["suppliers"] + 1, n)
        rng.integers(1, 51, n)  # lo_quantity
        rng.integers(0, 11, n)  # lo_discount


def segment_columns(config: dict, rows: int, seed: int, seg: int) -> dict:
    """One segment's columns; only those the configuration names. A chunk
    at a time into one array per column, as ``ssb_flat`` does: fresh
    memory is the slow part on the chip's host."""
    wanted = config["columns"]
    theirs = {c: wanted.get(c, {}) for c in list(wanted) + list(_READ)
              if c not in _DTYPES}
    got = KEYS.segment_columns(dict(config, columns=theirs), rows, seed, seg)
    supp_city, cust_city = _dimension_tables(config, seed)
    out = {c: np.empty(rows, _DTYPES[c]) for c in wanted if c in _DTYPES}

    def put(name, lo, values):
        if name in out:
            out[name][lo:lo + len(values)] = values

    for lo, suppkey in _suppkeys(config, rows, seed, seg):
        rows_of = slice(lo, lo + len(suppkey))
        c_city = cust_city[got["lo_custkey"][rows_of]]
        s_city = supp_city[suppkey]
        yyyymm = got["d_yearmonthnum"][rows_of].astype(np.int32)
        put("c_city", lo, c_city)
        put("c_nation", lo, c_city // 10)
        put("c_region", lo, c_city // 50)
        put("s_city", lo, s_city)
        put("s_nation", lo, s_city // 10)
        put("d_yearmonth", lo, (yyyymm // 100 - 1992) * 12 + yyyymm % 100 - 1)
    return {c: out[c] if c in out else got[c] for c in wanted}
