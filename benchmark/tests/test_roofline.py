"""The roofline's byte count against a hand sum, for one Q1.1 and one Q2.1
of the 16-segment configuration."""

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import roofline  # noqa: E402
import traffic  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "ssb-flat-sf10-16seg.json")
                    .read_text())
ROWS = 16 * 4_194_304
WORKLOAD = traffic.Workload(traffic.load("traffic", "flight12-streams3"),
                            "lineorder16", 7)


def test_q1_1_reads_four_columns_and_returns_one_cell():
    sql = WORKLOAD.render("ssb_q1_1",
                          {"Y": 1993, "D": 1, "D2": 3, "Q": 25, "L": 10})
    assert sql.endswith("WHERE d_year = 1993 AND lo_discount BETWEEN 1 AND "
                        "3 AND lo_quantity < 25 LIMIT 10")
    assert roofline.columns_read(sql, CONFIG) == [
        "d_year", "lo_discount", "lo_quantity", "lo_extendedprice"]
    # d_year 1 + lo_discount 1 + lo_quantity 4 + lo_extendedprice 4 bytes
    assert roofline.query_bytes(sql, CONFIG, ROWS, 1, 1) \
        == 67_108_864 * 10 + 8 == 671_088_648


def test_q2_1_reads_five_columns_and_a_literal_is_not_a_column():
    sql = WORKLOAD.render("ssb_q2_1", {"CAT": "MFGR#12", "R": "ASIA", "L": 1000},
                          "SET trace = true; ")
    assert roofline.columns_read(sql, CONFIG) == [
        "d_year", "p_category", "p_brand1", "s_region", "lo_revenue"]
    # 1 + 1 + 2 + 1 + 4 bytes a row, 280 groups x 3 cells x 8 bytes back
    assert roofline.query_bytes(sql, CONFIG, ROWS, 280, 3) \
        == 67_108_864 * 9 + 6_720 == 603_986_496


def test_least_seconds_divides_by_the_peak_rate():
    peak = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    assert roofline.least_seconds(819e9, peak) == 1.0
