"""Bench-history regression gate tests (dcnn_tpu/obs/regress.py +
benchmarks/compare.py).

Contracts:

- a five-capture fixture history (``benchmarks/compare.py``) passes the
  gate (no false alarm, including its 3x-noisy h2d series);
- a planted ≥20% img/s regression appended to that same trajectory is
  flagged, by name, with a nonzero CLI exit code;
- direction (lower-is-better compile_s), the compile-cache-warmth
  comparability guard, missing-metric skips, and window bounds behave as
  documented;
- ``benchmarks/compare.py --self-test`` (the fixture run CI executes)
  passes — the gate is itself regression-tested.
"""

import copy
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from dcnn_tpu.obs import regress

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPARE = os.path.join(REPO, "benchmarks", "compare.py")


def _fixture_files(tmp_path):
    """benchmarks/compare.py's fixture history written under tmp_path."""
    spec = importlib.util.spec_from_file_location("bench_compare", COMPARE)
    compare = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(compare)
    return compare.write_fixture_history(str(tmp_path))


# ----------------------------------------------------------- unit: compare

def _hist(*values, extra=()):
    out = [{"value": v} for v in values]
    for i, d in enumerate(extra):
        out[i].update(d)
    return out


def test_improvement_and_in_tolerance_pass():
    report = regress.compare(_hist(100.0, 110.0, 120.0))
    assert report["ok"] and report["regressions"] == []
    # 15% below the window best at 20% tolerance: pass
    report = regress.compare(_hist(100.0, 120.0, 102.0))
    assert report["ok"]


def test_regression_past_tolerance_flagged():
    report = regress.compare(_hist(100.0, 120.0, 90.0))  # -25% vs best
    assert not report["ok"] and report["regressions"] == ["img_per_sec"]
    row = next(r for r in report["metrics"] if r["metric"] == "img_per_sec")
    assert row["verdict"] == "REGRESSED" and row["best"] == 120.0


def test_baseline_is_window_best_not_mean():
    # a weak early capture must not dilute the baseline: best-of-window
    # is 120, and 90 regresses against it even though the mean is ~103
    report = regress.compare(_hist(90.0, 100.0, 120.0, 90.0))
    assert not report["ok"]


def test_lower_is_better_direction():
    hist = [{"phases": {"compile_s": 100.0, "compile_cache_hit": None}},
            {"phases": {"compile_s": 160.0, "compile_cache_hit": None}}]
    report = regress.compare(hist)  # +60% past the 50% tolerance
    assert "compile_s" in report["regressions"]
    hist[1]["phases"]["compile_s"] = 140.0  # +40%: within tolerance
    assert regress.compare(hist)["ok"]


def test_cache_warmth_guard_blocks_comparison():
    hist = [{"phases": {"compile_s": 3.0, "compile_cache_hit": True}},
            {"phases": {"compile_s": 150.0, "compile_cache_hit": False}}]
    report = regress.compare(hist)
    row = next(r for r in report["metrics"] if r["metric"] == "compile_s")
    assert row["verdict"].startswith("skipped")
    assert report["ok"]


def test_missing_metric_and_empty_window_skip():
    report = regress.compare([{"value": 10.0}, {"mfu": 0.4}])
    rows = {r["metric"]: r["verdict"] for r in report["metrics"]}
    assert rows["img_per_sec"].startswith("skipped")  # absent from newest
    # mfu_formula reads the legacy `mfu` key via its fallback, but the
    # prior capture carries neither -> still no comparable window
    assert rows["mfu_formula"].startswith("skipped")
    assert rows["mfu_analytic"].startswith("skipped")
    assert report["ok"]


def test_window_bounds_lookback():
    # the ancient 1000.0 capture is outside window=2 and must not gate
    report = regress.compare(_hist(1000.0, 100.0, 105.0, 103.0), window=2)
    assert report["ok"]
    report = regress.compare(_hist(1000.0, 100.0, 105.0, 103.0), window=3)
    assert not report["ok"]


def test_compare_input_validation():
    with pytest.raises(ValueError):
        regress.compare([])
    with pytest.raises(ValueError):
        regress.compare(_hist(1.0, 2.0), window=0)
    with pytest.raises(ValueError):
        regress.compare(_hist(1.0, 2.0), tolerance=1.5)


def test_get_path_and_load_capture(tmp_path):
    assert regress.get_path({"a": {"b": 3}}, "a.b") == 3
    assert regress.get_path({"a": 1}, "a.b") is None
    wrapped = tmp_path / "BENCH_r01.json"
    wrapped.write_text(json.dumps({"parsed": {"value": 5}}))
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps({"metric": "m", "value": 7}))
    junk = tmp_path / "junk.json"
    junk.write_text("{nope")
    assert regress.load_capture(str(wrapped)) == {"value": 5}
    assert regress.load_capture(str(bare))["value"] == 7
    assert regress.load_capture(str(junk)) is None


# ------------------------------------------------ a whole capture history

def test_fixture_trajectory_passes(tmp_path):
    report = regress.compare_files(_fixture_files(tmp_path))
    assert report["ok"], regress.format_report(report)
    assert report["unparseable_files"] == []


def test_planted_regression_on_fixture_trajectory_flagged(tmp_path):
    """The acceptance shape: a five-capture history, one planted ≥20%
    img/s drop appended — the gate must name it."""
    files = _fixture_files(tmp_path)
    newest = regress.load_capture(files[-1])
    planted = copy.deepcopy(newest)
    planted["value"] = round(newest["value"] * 0.75, 1)  # -25%
    n = len(files) + 1
    (tmp_path / f"BENCH_r{n:02d}.json").write_text(
        json.dumps({"n": n, "parsed": planted}))
    report = regress.compare_files(regress.find_bench_files(str(tmp_path)))
    assert not report["ok"]
    assert "img_per_sec" in report["regressions"]

    # CLI twin: nonzero exit on the planted file
    rc = subprocess.run(
        [sys.executable, COMPARE, "--json"]
        + regress.find_bench_files(str(tmp_path)),
        capture_output=True, text=True, timeout=120)
    assert rc.returncode == 1, rc.stdout + rc.stderr
    assert "img_per_sec" in json.loads(rc.stdout)["regressions"]


def test_gate_current_embeds_report(tmp_path):
    files = _fixture_files(tmp_path)
    current = regress.load_capture(files[-1])
    report = regress.gate_current(current, str(tmp_path))
    assert report is not None and "error" not in report
    # the newest capture re-gated against history incl. itself: ok
    assert report["ok"]
    assert report["baseline_files"] == files
    assert regress.gate_current({"value": 1.0}, str(os.path.join(
        REPO, "nonexistent-dir"))) is None  # no history -> None, no raise


# ------------------------------------------------------------------- CLI

def test_cli_self_test_passes():
    rc = subprocess.run([sys.executable, COMPARE, "--self-test"],
                        capture_output=True, text=True, timeout=120)
    assert rc.returncode == 0, rc.stdout + rc.stderr
    assert "self-test: PASS" in rc.stdout


def test_cli_clean_history_exit_zero(tmp_path):
    rc = subprocess.run([sys.executable, COMPARE] + _fixture_files(tmp_path),
                        capture_output=True, text=True, timeout=120)
    assert rc.returncode == 0, rc.stdout + rc.stderr
    assert "OK: no regressions" in rc.stdout


def test_cli_usage_errors():
    rc = subprocess.run([sys.executable, COMPARE, "one.json"],
                        capture_output=True, text=True, timeout=120)
    assert rc.returncode == 2


# ------------------------------------------- autoscale.* gate keys (PR 11)

def _autoscale_cap(availability=1.0, slo_min=0.2, reaction=1.0,
                   cooldown=5.0, **extra):
    return {"value": 100.0, "autoscale": {
        "availability": availability,
        "slo_violation_minutes": slo_min,
        "scale_up_reaction_s": reaction,
        "up_cooldown_s": cooldown, **extra}}


def test_autoscale_keys_skip_for_pre_pr11_captures():
    """Skips-not-lies: a history of captures without the autoscale block
    neither gates nor fails the new keys."""
    report = regress.compare([{"value": 100.0}, {"value": 101.0},
                              _autoscale_cap()])
    assert report["ok"]
    rows = {r["metric"]: r for r in report["metrics"]}
    assert rows["autoscale.availability"]["verdict"] \
        == "skipped: no comparable prior capture"
    # and a newest capture WITHOUT the block skips against one that has it
    report = regress.compare([_autoscale_cap(), {"value": 100.0}])
    assert report["ok"]
    rows = {r["metric"]: r for r in report["metrics"]}
    assert "absent from newest" in rows["autoscale.availability"]["verdict"]


def test_autoscale_availability_regression_flagged():
    report = regress.compare([_autoscale_cap(availability=1.0),
                              _autoscale_cap(availability=0.97)])
    assert "autoscale.availability" in report["regressions"]
    # within the 1% tolerance: passes
    report = regress.compare([_autoscale_cap(availability=1.0),
                              _autoscale_cap(availability=0.995)])
    assert report["ok"]


def test_autoscale_reaction_guarded_on_cooldown_budget():
    """A different up_cooldown_s budget is a config change, not a
    regression — the guard refuses the comparison."""
    slow = _autoscale_cap(reaction=12.0, cooldown=15.0)
    fast = _autoscale_cap(reaction=3.0, cooldown=5.0)
    report = regress.compare([fast, slow])
    rows = {r["metric"]: r for r in report["metrics"]}
    assert rows["autoscale.scale_up_reaction_s"]["verdict"] \
        == "skipped: no comparable prior capture"
    assert report["ok"]
    # same budget: a 4x reaction blowup IS flagged
    report = regress.compare([fast, _autoscale_cap(reaction=12.0,
                                                   cooldown=5.0)])
    assert "autoscale.scale_up_reaction_s" in report["regressions"]


def test_autoscale_slo_minutes_lower_is_better():
    report = regress.compare([_autoscale_cap(slo_min=0.2),
                              _autoscale_cap(slo_min=0.1)])
    assert report["ok"]   # improvement always passes
    report = regress.compare([_autoscale_cap(slo_min=0.2),
                              _autoscale_cap(slo_min=2.0)])
    assert "autoscale.slo_violation_minutes" in report["regressions"]


def test_autoscale_zero_best_window_uses_absolute_slack():
    """A perfect capture (0.0 minutes, un-delayed reaction) in the window
    must not flag every later legitimate nonzero forever — the relative
    band collapses at best=0, so the absolute slack (the soak's own
    budget) carries the verdict."""
    perfect = _autoscale_cap(slo_min=0.0, reaction=0.0)
    report = regress.compare([perfect,
                              _autoscale_cap(slo_min=0.75, reaction=4.0)])
    assert report["ok"]   # inside the budget = operating as designed
    report = regress.compare([perfect,
                              _autoscale_cap(slo_min=3.0, reaction=30.0)])
    assert "autoscale.slo_violation_minutes" in report["regressions"]
    assert "autoscale.scale_up_reaction_s" in report["regressions"]


# ------------------------------------------- decode.* gate keys (PR 20)

def _decode_cap(tps=5000.0, ttft=50.0, occ=0.8, slots=8, **extra):
    return {"value": 100.0, "decode": {
        "tokens_per_sec": tps,
        "ttft_p99_ms": ttft,
        "slot_occupancy": occ,
        "max_slots": slots, **extra}}


def test_decode_keys_skip_for_pre_pr20_captures():
    """Skips-not-lies: histories without the BENCH_DECODE block neither
    gate nor fail the decode keys, in either direction."""
    report = regress.compare([{"value": 100.0}, {"value": 101.0},
                              _decode_cap()])
    assert report["ok"]
    rows = {r["metric"]: r for r in report["metrics"]}
    assert rows["decode.tokens_per_sec"]["verdict"] \
        == "skipped: no comparable prior capture"
    report = regress.compare([_decode_cap(), {"value": 100.0}])
    assert report["ok"]
    rows = {r["metric"]: r for r in report["metrics"]}
    assert "absent from newest" in rows["decode.tokens_per_sec"]["verdict"]


def test_decode_throughput_and_occupancy_regressions_flagged():
    report = regress.compare([_decode_cap(tps=5000.0),
                              _decode_cap(tps=2000.0)])
    assert "decode.tokens_per_sec" in report["regressions"]
    report = regress.compare([_decode_cap(occ=0.8), _decode_cap(occ=0.4)])
    assert "decode.slot_occupancy" in report["regressions"]
    # within tolerance: passes
    report = regress.compare([_decode_cap(tps=5000.0, occ=0.8),
                              _decode_cap(tps=4500.0, occ=0.75)])
    assert report["ok"]


def test_decode_ttft_lower_is_better_with_absolute_slack():
    """TTFT is a sub-100ms loopback wall: the atol shields sub-10ms
    scheduler jitter, but a real blowup is flagged."""
    report = regress.compare([_decode_cap(ttft=5.0), _decode_cap(ttft=12.0)])
    assert report["ok"]   # within 1.0 rel + 10ms atol slack
    report = regress.compare([_decode_cap(ttft=50.0),
                              _decode_cap(ttft=300.0)])
    assert "decode.ttft_p99_ms" in report["regressions"]


def test_decode_keys_guarded_on_slot_count():
    """A different max_slots is a different probe — guard refuses the
    comparison instead of calling a config change a regression."""
    report = regress.compare([_decode_cap(tps=8000.0, slots=16),
                              _decode_cap(tps=5000.0, slots=8)])
    rows = {r["metric"]: r for r in report["metrics"]}
    assert rows["decode.tokens_per_sec"]["verdict"] \
        == "skipped: no comparable prior capture"
    assert report["ok"]
