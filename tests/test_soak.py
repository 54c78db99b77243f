"""Short-profile CI runs of the committed soak harness
(pinot_tpu/tools/soak.py) so every reliability-evidence class in the README
is reproducible from a committed entry point.

Reference pattern: ChaosMonkeyIntegrationTest and the H2-oracle
testQueries harness run inside the normal integration-test suite at reduced
scale; the long profiles are the same code with bigger knobs.
"""

from __future__ import annotations

import pytest

from pinot_tpu.tools.soak import (soak_chaos, soak_realtime, soak_rebalance,
                                  soak_sql)


def test_soak_sql_short_profile():
    out = soak_sql(seconds=8.0, seed=7, rows=600, device_parity=False)
    assert out["checks"] >= 20, out


def test_soak_sql_device_parity_short_profile():
    # ends at its count of checks (30: about 8 s alone), not at a clock
    # that six busy workers run down before ten shapes have compiled; the
    # seconds only bound a hang
    out = soak_sql(seconds=240.0, seed=11, rows=400, device_parity=True,
                   max_checks=30)
    assert out["checks"] >= 10, out


def test_soak_chaos_short_profile():
    out = soak_chaos(seconds=12.0, seed=5, n_servers=3, replication=2,
                     n_segments=4, rows_per_segment=200)
    assert out["queries"] >= 10, out
    # chaos actually happened: at least one kill or rebalance or compaction
    assert out["kills"] + out["rebalances"] + out["compactions"] >= 1, out


@pytest.mark.rebalance
def test_soak_rebalance_short_profile():
    """Elastic-capacity soak at smoke scale, faults armed on the
    ``rebalance.move`` destination-fetch point: server kill/add churn must
    drive the durable actuation loop through at least one completed job
    (dead-server rebuild or server-add spread) while live queries stay
    exact-or-degraded and the end state holds full replication."""
    out = soak_rebalance(seconds=6.0, seed=13, n_segments=6,
                         rows_per_segment=150, fault_rate=0.05)
    assert out["queries"] >= 10, out
    assert out["jobs_done"] >= 1, out
    assert out["server_kills"] + out["server_adds"] >= 1, out
    assert out["moves_completed"] >= 1, out


def test_soak_realtime_one_round():
    out = soak_realtime(rounds=1, seed=3, rows_per_round=40)
    assert out["rounds"] == 1, out


def test_soak_cli_smoke(capsys):
    from pinot_tpu.tools.soak import main
    rc = main(["--suite", "realtime", "--rounds", "1", "--quiet"])
    assert rc == 0
    import json
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["ok"] is True


def test_soak_report_artifact(tmp_path, capsys):
    """--report writes the machine-readable run artifact: per-suite
    results, final per-role metrics snapshots, cost-report aggregates
    from the broker's workload tracker, and the closing anomaly list."""
    import json

    from pinot_tpu.tools.soak import main

    out = tmp_path / "soak_report.json"
    rc = main(["--suite", "chaos", "--seconds", "4", "--quiet",
               "--report", str(out)])
    capsys.readouterr()
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["ok"] is True
    assert report["schemaVersion"] == 1
    assert set(report["metrics"]) == {"server", "broker", "controller"}
    assert report["metrics"]["broker"]["timers"][
        "queryProcessingTimeMs"]["count"] > 0
    # the chaos suite's broker workload rollup made it into the artifact
    assert "stats" in report["costReports"]["chaos"]["tables"]
    assert isinstance(report["anomalies"], list)
    chaos = report["results"][0]
    assert chaos["fleet"]["serversReachable"] >= 1
