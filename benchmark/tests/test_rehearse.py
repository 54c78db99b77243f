"""Every cell of BENCHMARK.json end to end on the CPU at toy size: the
last line parses, says ``correct``, and carries no metric."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_and_prints_no_metric(cell, trace):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         cell, "--seed", "2147483777", "--seconds", "4", "--trace",
         str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["metrics"] == {} and "breakdown" not in result
    assert result["device"]["platform"] == "cpu"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in result["compared"].values())
    # the compared numbers are also the last lines of standard error
    tail = proc.stderr.strip().splitlines()[-len(result["compared"]):]
    assert all(line.startswith("[bench] compared ") for line in tail)


def test_without_an_accelerator_a_real_run_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_json_and_the_files_it_names_agree():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    names = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        spec = json.loads((ROOT / "benchmark" / "metrics"
                           / f"{m['name']}.json").read_text())
        assert m["moves"] in names
        assert (ROOT / "benchmark" / "readers"
                / f"{spec['reader']}.py").is_file()
    for w in BENCH["workloads"]:
        assert (ROOT / "benchmark" / "traffic"
                / f"{w['traffic']}.json").is_file()
