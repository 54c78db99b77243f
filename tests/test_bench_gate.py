"""bench_gate: noise-aware perf-regression comparison of two bench rounds.

Tier-1 acceptance: two identical rounds pass with exit 0; a 30% p50
regression on one config exits nonzero and NAMES the config; a
correctness match-flag flip always fails regardless of timing.
"""

from __future__ import annotations

import json

import pytest

from pinot_tpu.tools.bench_gate import compare, load_round, main


def _payload(**overrides):
    detail = {
        "q1_filter_sum": {"tpu_p50_s": 0.100, "rows_per_sec": 1e9,
                          "match": True, "iters": 10},
        "q2_groupby": {"tpu_p50_s": 0.200, "rows_per_sec": 5e8,
                       "match": True, "iters": 10},
        "q3_highcard": {"tpu_p50_s": 1.500, "rows_per_sec": 9e7,
                        "match": True, "iters": 3},
    }
    out = {"metric": "x", "value": 1.0, "platform": "tpu",
           "detail": detail}
    out.update(overrides)
    return out


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_identical_rounds_pass(tmp_path, capsys):
    a = _write(tmp_path, "a.json", _payload())
    b = _write(tmp_path, "b.json", _payload())
    assert main([a, b]) == 0
    out = capsys.readouterr().out
    assert "GATE: PASS" in out


def test_thirty_percent_regression_fails_naming_config(tmp_path, capsys):
    base = _payload()
    cand = _payload()
    cand["detail"]["q2_groupby"]["tpu_p50_s"] = 0.260  # +30%
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    out = capsys.readouterr().out
    assert "GATE: FAIL" in out
    assert "q2_groupby" in out and "regressed" in out
    # the healthy configs still read PASS in the verdict table
    assert "q1_filter_sum" in out


def test_match_flip_fails_even_when_faster(tmp_path, capsys):
    cand = _payload()
    cand["detail"]["q1_filter_sum"]["tpu_p50_s"] = 0.050  # 2x faster...
    cand["detail"]["q1_filter_sum"]["match"] = False      # ...and wrong
    a = _write(tmp_path, "a.json", _payload())
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    assert "match flipped" in capsys.readouterr().out


def test_missing_config_fails(tmp_path, capsys):
    cand = _payload()
    del cand["detail"]["q3_highcard"]
    a = _write(tmp_path, "a.json", _payload())
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    assert "missing from candidate" in capsys.readouterr().out


def test_min_abs_floor_absorbs_micro_jitter(tmp_path):
    """A 100% ratio regression that is still under the absolute floor is
    scheduler jitter on a microsecond config, not a regression."""
    base = _payload()
    base["detail"]["q1_filter_sum"]["tpu_p50_s"] = 0.0004
    cand = _payload()
    cand["detail"]["q1_filter_sum"]["tpu_p50_s"] = 0.0008
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 0


def test_improvement_passes(tmp_path):
    cand = _payload()
    for cfg in cand["detail"].values():
        cfg["tpu_p50_s"] *= 0.5
    a = _write(tmp_path, "a.json", _payload())
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 0


def test_cross_platform_downgrades_to_warning(tmp_path, capsys):
    cand = _payload(platform="cpu")
    cand["detail"]["q2_groupby"]["tpu_p50_s"] = 40.0  # cpu is slower, fine
    a = _write(tmp_path, "a.json", _payload())
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 0
    out = capsys.readouterr().out
    assert "platform mismatch" in out and "GATE: PASS" in out


def test_wrapper_with_embedded_payload(tmp_path):
    """Driver wrapper shape: parsed=null, payload as the tail's last
    JSON object (how BENCH rounds actually land)."""
    inner = _payload()
    wrapper = {"cmd": "python bench.py", "rc": 0, "parsed": None,
               "tail": "[bench] log line {noise}\n" + json.dumps(inner)}
    p = _write(tmp_path, "w.json", wrapper)
    assert load_round(p)["detail"] == inner["detail"]


def test_wrapper_with_truncated_tail_salvages_configs(tmp_path):
    """BENCH_r04/r05 regression shape: the tail keeps only the last 2000
    chars, beheading the payload — whole config objects still recover."""
    inner = _payload()
    full = json.dumps(inner)
    wrapper = {"cmd": "python bench.py", "rc": 0, "parsed": None,
               "tail": full[len(full) // 2:]}  # behead the payload
    p = _write(tmp_path, "w.json", wrapper)
    got = load_round(p)
    assert got.get("salvaged") is True
    assert "q3_highcard" in got["detail"]  # the tail-end config survives


def test_unparseable_round_is_usage_error(tmp_path, capsys):
    a = _write(tmp_path, "a.json", {"cmd": "x", "tail": "no json here"})
    b = _write(tmp_path, "b.json", _payload())
    assert main([a, b]) == 2
    assert "bench_gate:" in capsys.readouterr().err


def test_shuffled_bytes_regression_fails(tmp_path, capsys):
    """MSE configs record summed cross-stage bytes; a blow-up (lost
    pushdown, widened exchange schema) fails even when p50 held steady."""
    base = _payload()
    base["detail"]["q2_groupby"]["shuffled_bytes"] = 100_000
    cand = _payload()
    cand["detail"]["q2_groupby"]["shuffled_bytes"] = 600_000
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    out = capsys.readouterr().out
    assert "q2_groupby" in out and "shuffled bytes regressed" in out


def test_shuffled_bytes_small_abs_delta_passes(tmp_path):
    """A big ratio under the 4096-byte absolute floor is a fixture-sized
    run, not a plan regression."""
    base = _payload()
    base["detail"]["q2_groupby"]["shuffled_bytes"] = 1000
    cand = _payload()
    cand["detail"]["q2_groupby"]["shuffled_bytes"] = 3000  # 3x but tiny
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 0


def test_shuffled_bytes_cross_platform_warns(tmp_path, capsys):
    base = _payload()
    base["detail"]["q2_groupby"]["shuffled_bytes"] = 100_000
    cand = _payload(platform="cpu")
    cand["detail"]["q2_groupby"]["shuffled_bytes"] = 600_000
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 0
    out = capsys.readouterr().out
    assert "shuffled bytes" in out and "GATE: PASS" in out


def test_shuffled_bytes_missing_sides(tmp_path, capsys):
    """Improvement passes; candidate dropping the metric only warns
    (coverage drift, same rule as the mesh round); a baseline without the
    metric never compares."""
    base = _payload()
    base["detail"]["q2_groupby"]["shuffled_bytes"] = 600_000
    cand = _payload()
    cand["detail"]["q2_groupby"]["shuffled_bytes"] = 100_000  # 6x better
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 0
    cand2 = _payload()  # no shuffled_bytes at all
    c = _write(tmp_path, "c.json", cand2)
    assert main([a, c]) == 0
    assert "exchange telemetry dropped" in capsys.readouterr().out


def test_compare_is_pure():
    base = _payload()
    cand = _payload()
    cand["detail"]["q1_filter_sum"]["tpu_p50_s"] = 99.0
    report = compare(base, cand, threshold=0.25)
    assert report["pass"] is False
    assert any("q1_filter_sum" in f for f in report["failures"])
    verdicts = {r["config"]: r["verdict"] for r in report["rows"]}
    assert verdicts["q1_filter_sum"] == "FAIL"
    assert verdicts["q2_groupby"] == "PASS"


def _truncated_wrapper(payload: dict) -> dict:
    """A driver wrapper whose ``tail`` kept only the END of the printed
    payload: the head (metric, opening of ``detail``, part of the first
    config) is cut off, whole per-config objects survive."""
    line = json.dumps(payload)
    cut = line.index('"q2_groupby"') - 40  # mid-way through q1's object
    return {"n": 1, "cmd": "python bench.py", "rc": 0, "tail": line[cut:]}


@pytest.mark.parametrize("form", ["payload", "truncated-wrapper"])
def test_round_files_self_compare_pass(tmp_path, form):
    """Both on-disk forms of a round must load (wrapper salvage for the
    truncated form) and self-compare clean."""
    doc = _payload()
    if form == "truncated-wrapper":
        doc = _truncated_wrapper(doc)
    a = _write(tmp_path, "a.json", doc)
    if form == "truncated-wrapper":
        loaded = load_round(a)
        assert loaded.get("salvaged") is True
        # q1's object lost its head to the cut; the other two survive whole
        assert set(loaded["detail"]) == {"q2_groupby", "q3_highcard"}
    assert main([a, a]) == 0


@pytest.mark.gate
def test_two_consecutive_rounds_no_correctness_flip(tmp_path):
    """Gate smoke over two consecutive rounds recorded on different
    machines: pure timing deltas only warn — but a correctness ``match``
    flip (any config returning different rows than the oracle) fails."""
    base = _payload(runner={"logicalCores": 8, "physicalCores": 4})
    cand = _payload(runner={"logicalCores": 1, "physicalCores": 1})
    cand["detail"]["q3_highcard"]["tpu_p50_s"] = 3.0  # 2x slower: noise
    rounds = [_write(tmp_path, "BENCH_r01.json", _truncated_wrapper(base)),
              _write(tmp_path, "BENCH_r02.json", cand)]
    report = compare(load_round(rounds[0]), load_round(rounds[1]),
                     threshold=0.30)
    assert not [f for f in report["failures"] if "flip" in f]
    cand["detail"]["q2_groupby"]["match"] = False
    flipped = compare(load_round(rounds[0]), cand, threshold=0.30)
    assert [f for f in flipped["failures"] if "flip" in f]


def test_warm_p50_regression_fails(tmp_path, capsys):
    """Tiered round: a warm (resident-path) p50 blow-up fails even when
    the headline cold p50 held steady — the warm path is the hot path."""
    base = _payload()
    base["detail"]["q2_groupby"].update(
        {"cold_p50_s": 0.200, "warm_p50_s": 0.010, "warm_match": True})
    cand = _payload()
    cand["detail"]["q2_groupby"].update(
        {"cold_p50_s": 0.200, "warm_p50_s": 0.040, "warm_match": True})
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    out = capsys.readouterr().out
    assert "q2_groupby" in out and "warm p50 regressed" in out


def test_cold_p50_regression_fails(tmp_path, capsys):
    base = _payload()
    base["detail"]["q1_filter_sum"].update(
        {"cold_p50_s": 0.050, "warm_p50_s": 0.010, "warm_match": True})
    cand = _payload()
    cand["detail"]["q1_filter_sum"].update(
        {"cold_p50_s": 0.500, "warm_p50_s": 0.010, "warm_match": True})
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    assert "cold p50 regressed" in capsys.readouterr().out


def test_warm_match_flip_always_fails(tmp_path, capsys):
    """warm_match true -> false is a correctness regression on the
    resident path; it fails even when every timing improved."""
    base = _payload()
    base["detail"]["q1_filter_sum"].update(
        {"cold_p50_s": 0.100, "warm_p50_s": 0.020, "warm_match": True})
    cand = _payload()
    cand["detail"]["q1_filter_sum"].update(
        {"cold_p50_s": 0.010, "warm_p50_s": 0.002, "warm_match": False})
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    assert "warm_match flipped" in capsys.readouterr().out


def test_tiered_cross_platform_warns_and_missing_side_rules(tmp_path,
                                                            capsys):
    """Cross-platform tiered regressions downgrade to WARN (same rule as
    mesh); a candidate that dropped the tiered round only warns; a
    baseline without it never compares."""
    base = _payload()
    base["detail"]["q2_groupby"].update(
        {"cold_p50_s": 0.200, "warm_p50_s": 0.010, "warm_match": True})
    cand = _payload(platform="cpu")
    cand["detail"]["q2_groupby"].update(
        {"cold_p50_s": 2.000, "warm_p50_s": 0.100, "warm_match": True})
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 0
    out = capsys.readouterr().out
    assert "warm p50" in out and "GATE: PASS" in out
    cand2 = _payload()  # same platform, tiered round dropped entirely
    c = _write(tmp_path, "c.json", cand2)
    assert main([a, c]) == 0
    assert "tiered coverage dropped" in capsys.readouterr().out


def test_rt_delta_reaching_full_snapshot_fails(tmp_path, capsys):
    """q11r invariant: the post-append query must upload only the new
    tail. delta >= full means every query re-ships the whole snapshot —
    a candidate-only check, no baseline delta needed."""
    base = _payload()
    cand = _payload()
    cand["detail"]["q2_groupby"].update(
        {"rt_full_bytes": 524_288, "rt_delta_bytes": 524_288,
         "rt_warm_bytes": 0})
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    out = capsys.readouterr().out
    assert "q2_groupby" in out and "incremental upload path lost" in out


def test_rt_delta_fails_even_cross_platform(tmp_path, capsys):
    """Upload bytes measure the plan, not the machine: the full-snapshot
    check stays a FAIL across platforms."""
    base = _payload()
    cand = _payload(platform="cpu")
    cand["detail"]["q2_groupby"].update(
        {"rt_full_bytes": 524_288, "rt_delta_bytes": 600_000})
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    assert "incremental upload path lost" in capsys.readouterr().out


def test_rt_warm_upload_fails(tmp_path, capsys):
    """A warm repeat on an unchanged generation must upload 0 bytes."""
    cand = _payload()
    cand["detail"]["q2_groupby"].update(
        {"rt_full_bytes": 524_288, "rt_delta_bytes": 4096,
         "rt_warm_bytes": 2048})
    a = _write(tmp_path, "a.json", _payload())
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    assert "unchanged generation uploaded" in capsys.readouterr().out


def test_rt_healthy_delta_passes_and_growth_vs_baseline_fails(tmp_path,
                                                              capsys):
    """Proportional delta passes; a delta-bytes blow-up vs the baseline
    (past the ratio AND the 4096-byte floor) fails like shuffled bytes."""
    base = _payload()
    base["detail"]["q2_groupby"].update(
        {"rt_full_bytes": 524_288, "rt_delta_bytes": 4096,
         "rt_warm_bytes": 0})
    cand = _payload()
    cand["detail"]["q2_groupby"].update(
        {"rt_full_bytes": 524_288, "rt_delta_bytes": 4096,
         "rt_warm_bytes": 0})
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 0
    cand2 = _payload()
    cand2["detail"]["q2_groupby"].update(
        {"rt_full_bytes": 524_288, "rt_delta_bytes": 65_536,
         "rt_warm_bytes": 0})
    c = _write(tmp_path, "c.json", cand2)
    assert main([a, c]) == 1
    assert "realtime delta bytes regressed" in capsys.readouterr().out


def test_rt_missing_candidate_telemetry_warns(tmp_path, capsys):
    base = _payload()
    base["detail"]["q2_groupby"].update(
        {"rt_full_bytes": 524_288, "rt_delta_bytes": 4096})
    cand = _payload()  # no rt_* keys at all
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 0
    assert "delta telemetry dropped" in capsys.readouterr().out


def test_runner_shape_diff_downgrades_timing_to_warning(tmp_path, capsys):
    """Same platform, but the runner changed shape (core count): a p50
    blow-up downgrades to a WARN that names the shape diff — the timing
    moved with the hardware, not the code."""
    base = _payload(runner={"physicalCores": 8, "logicalCores": 16})
    cand = _payload(runner={"physicalCores": 1, "logicalCores": 2})
    cand["detail"]["q2_groupby"]["tpu_p50_s"] = 0.800  # 4x slower
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 0
    out = capsys.readouterr().out
    assert "GATE: PASS" in out
    assert "runner shape differs" in out
    assert "physicalCores 8 -> 1" in out, (
        "the warning must name the shape change it excused")


def test_runner_shape_diff_never_excuses_match_flip(tmp_path, capsys):
    """Plan properties ignore the runner shape: a correctness flip fails
    no matter what the hardware did."""
    base = _payload(runner={"physicalCores": 8, "logicalCores": 16})
    cand = _payload(runner={"physicalCores": 1, "logicalCores": 2})
    cand["detail"]["q1_filter_sum"]["match"] = False
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    assert "match flipped" in capsys.readouterr().out


def test_same_runner_shape_still_fails_timing(tmp_path, capsys):
    """Identical runner blocks add no noise excuse: regressions fail."""
    base = _payload(runner={"physicalCores": 8, "logicalCores": 16})
    cand = _payload(runner={"physicalCores": 8, "logicalCores": 16})
    cand["detail"]["q2_groupby"]["tpu_p50_s"] = 0.800
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    out = capsys.readouterr().out
    assert "GATE: FAIL" in out and "regressed" in out


def test_missing_runner_block_keeps_old_behavior(tmp_path, capsys):
    """Rounds that predate the runner block compare exactly as before —
    no spurious shape warnings, timing checks stay armed."""
    base = _payload()  # no runner key
    cand = _payload(runner={"physicalCores": 8})
    cand["detail"]["q2_groupby"]["tpu_p50_s"] = 0.800
    a = _write(tmp_path, "a.json", base)
    b = _write(tmp_path, "b.json", cand)
    assert main([a, b]) == 1
    out = capsys.readouterr().out
    assert "runner shape differs" not in out
