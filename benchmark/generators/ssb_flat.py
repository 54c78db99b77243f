"""SSB ``lineorder`` with the date, part and supplier attributes that the
configuration's columns name pre-joined into the fact row, by dbgen's value
rules (Star Schema Benchmark rev. 3, section 2; TPC-H 4.2.3 where SSB
inherits it):

  order      1 to 7 lines, uniform; one order date for all its lines,
             uniform over 1992-01-01 .. 1998-08-02 (2,406 days)
  line       lo_partkey and lo_suppkey uniform over the part and supplier
             tables; lo_quantity 1..50; lo_discount 0..10
  derived    lo_extendedprice = lo_quantity x p_retailprice(lo_partkey), in
             cents, p_retailprice = 90000 + (partkey // 10) mod 20001
             + 100 x (partkey mod 1000);
             lo_revenue = lo_extendedprice x (100 - lo_discount) // 100
  part       p_mfgr 1..5, p_category MFGR#<m><1..5>, p_brand1
             <category><1..40>, drawn per part
  supplier   s_region: the region of a nation drawn uniformly of 25, five
             nations a region
  date       d_year, d_yearmonthnum (yyyymm), d_weeknuminyear
             ((day of year - 1) // 7 + 1, 1..53) of the order date

Counts come from the configuration's ``scale``: parts, suppliers. A
segment holds ``rows_per_segment`` rows: whole orders, the last one cut at
the segment's end. String columns are generated as codes into the sorted
lists ``dictionaries`` returns; the builder is given the strings, the
reference works on the codes.
"""

from __future__ import annotations

import numpy as np

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
DAY0 = np.datetime64("1992-01-01")
ORDER_DAYS = 2406  # 1992-01-01 .. 1998-08-02


def brand_names() -> list:
    """The 1,000 brands in (mfgr, category, brand) order: a brand's code
    // 40 is its category's. (Not the order of the strings: MFGR#1110
    sorts before MFGR#112; the reference compares strings as strings.)"""
    return [f"MFGR#{m}{c}{b}" for m in range(1, 6) for c in range(1, 6)
            for b in range(1, 41)]


def dictionaries(config: dict) -> dict:
    """column -> the list a code of that column indexes."""
    brands = brand_names()
    return {"p_brand1": brands, "p_category": [b[:7] for b in brands[::40]],
            "s_region": list(REGIONS)}


def _dimension_tables(config: dict, seed: int):
    """(brand code of each part, region code of each supplier): the same
    for every segment of a seed."""
    rng = np.random.default_rng([seed, config["table_id"], 1 << 20])
    scale = config["scale"]
    part_brand = rng.integers(0, 1000, scale["parts"] + 1).astype(np.int16)
    supp_region = (rng.integers(0, 25, scale["suppliers"] + 1) // 5).astype(
        np.int8)
    return part_brand, supp_region


def _calendar():
    days = DAY0 + np.arange(ORDER_DAYS)
    years = days.astype("datetime64[Y]")
    months = days.astype("datetime64[M]")
    year = years.astype(np.int32) + 1970
    month = (months - years.astype("datetime64[M]")).astype(np.int32) + 1
    day_of_year = (days - years.astype("datetime64[D]")).astype(np.int32)
    return year, year * 100 + month, day_of_year // 7 + 1


CHUNK = 1 << 17  # rows drawn at a time: temporaries stay in warm memory

_DTYPES = {"d_year": np.int16, "d_yearmonthnum": np.int32,
           "d_weeknuminyear": np.int8, "p_brand1": np.int16,
           "p_category": np.int8, "s_region": np.int8,
           "lo_quantity": np.int8, "lo_discount": np.int8,
           "lo_extendedprice": np.int32, "lo_revenue": np.int32}


def segment_columns(config: dict, rows: int, seed: int, seg: int) -> dict:
    """One segment's columns; only those the configuration names. Fresh
    memory is the slow part on the chip's host, so the rows are drawn a
    chunk at a time into one array per column."""
    rng = np.random.default_rng([seed, config["table_id"], seg])
    part_brand, supp_region = _dimension_tables(config, seed)
    lines = rng.integers(1, 8, rows // 3 + 8, dtype=np.int8)  # 4 an order
    if int(lines.sum()) < rows:
        raise ValueError("drew too few orders for the segment")
    day_of_order = rng.integers(0, ORDER_DAYS, len(lines), dtype=np.int16)
    day_of_row = np.repeat(day_of_order, lines)[:rows]
    year, yearmonth, week = _calendar()
    out = {c: np.empty(rows, dtype=_DTYPES[c]) for c in config["columns"]}

    def put(name, lo, values):
        if name in out:
            out[name][lo:lo + len(values)] = values

    for lo in range(0, rows, CHUNK):
        n = min(CHUNK, rows - lo)
        day = day_of_row[lo:lo + n]
        partkey = rng.integers(1, len(part_brand), n)
        suppkey = rng.integers(1, len(supp_region), n)
        quantity = rng.integers(1, 51, n)
        discount = rng.integers(0, 11, n)
        retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
        extended = quantity * retail
        brand = part_brand[partkey]
        put("d_year", lo, year[day])
        put("d_yearmonthnum", lo, yearmonth[day])
        put("d_weeknuminyear", lo, week[day])
        put("p_brand1", lo, brand)
        put("p_category", lo, brand // 40)
        put("s_region", lo, supp_region[suppkey])
        put("lo_quantity", lo, quantity)
        put("lo_discount", lo, discount)
        put("lo_extendedprice", lo, extended)
        put("lo_revenue", lo, extended * (100 - discount) // 100)
    return out
