"""``correct`` has teeth in `ssb16.flight3city`: a server that holds 15 of
the table's 16 segments and answers over those reads ``correct`` false
(`test_control.py` plants that fault in `ssb16.flight12` alone; its answer
off by one and its three controls run on every listed cell, this one
among them). CPU, the configuration's rehearsal size: there a Q3.3 keeps
about 240 of the table's lines, 15 of them in the segment left out."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from test_control import _rehearse  # noqa: E402


def test_a_segment_left_out_of_the_table_is_not_correct(monkeypatch):
    from pinot_tpu.cluster.controller import ClusterController

    real = ClusterController.add_segment

    def skipping(self, table, name, metadata, *a, **kw):
        if name.endswith("_0"):
            return None
        return real(self, table, name, metadata, *a, **kw)

    monkeypatch.setattr(ClusterController, "add_segment", skipping)
    code, result = _rehearse("ssb16.flight3city")
    assert code == 1 and result["correct"] is False
    assert result["compared"]["wrong_answers"]["value"] > 0
    assert result["compared"]["failed_requests"]["value"] == 0
    assert result["metrics"] == {}
