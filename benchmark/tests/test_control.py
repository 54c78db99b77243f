"""``correct`` has teeth: the controls come out as not correct, and a run
whose timed path is broken underneath reads ``correct`` false. CPU, toy
size; the chip-size readings are in PERF.md."""

import contextlib
import io
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import control  # noqa: E402
import run  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct_and_a_control_is_not(cell):
    code, result = control.read_seed(cell, 2147483801, 3.0, rehearse=True)
    assert code == 0 and result["correct"] is True
    wrong = result["control_wrong_answers"]
    assert set(wrong) == {"float32_sums", "bfloat16_values",
                          "first_segment_missing"}
    assert all(n > 0 for n in wrong.values()), wrong


def _rehearse(cell):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        code = run.main(["--workload", cell, "--seed", "2147483802",
                         "--seconds", "3", "--trace", "0", "--rehearse"])
    return code, json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        cell, monkeypatch):
    from pinot_tpu.cluster.broker import Broker

    real = Broker.execute_sql
    calls = {"n": 0}

    def altered(self, sql, segments=None):
        resp = real(self, sql, segments)
        calls["n"] += 1
        rows = resp.result_table.rows if resp.result_table else None
        if rows and calls["n"] % 7 == 0:  # one sum of one row, off by one
            row = list(rows[-1])
            row[0] = row[0] + 1
            rows[-1] = type(rows[-1])(row)
        return resp

    monkeypatch.setattr(Broker, "execute_sql", altered)
    code, result = _rehearse(cell)
    assert code == 1 and result["correct"] is False
    assert result["compared"]["wrong_answers"]["value"] > 0
    assert result["metrics"] == {}


def test_a_segment_left_out_of_the_table_is_not_correct(monkeypatch):
    """Part of the batch left out: the server holds 15 of 16 segments and
    answers over those."""
    from pinot_tpu.cluster.controller import ClusterController

    real = ClusterController.add_segment

    def skipping(self, table, name, metadata, *a, **kw):
        if name.endswith("_0"):
            return None
        return real(self, table, name, metadata, *a, **kw)

    monkeypatch.setattr(ClusterController, "add_segment", skipping)
    code, result = _rehearse("ssb16.flight12")
    assert code == 1 and result["correct"] is False
    assert result["compared"]["wrong_answers"]["value"] > 0
