"""Variance budget for the headline bench (VERDICT r4 #3).

Runs ``bench.py`` as the driver does — a fresh process per run, default
headline knobs — N times, collects the headline value plus the per-phase
walls bench.py now reports (compile / warmup / per-rep steady-state), and
decomposes the spread:

- **within-run**: spread of the BENCH_REPS rep timings inside one process
  (dispatch jitter, clock wander during the run);
- **between-run**: spread of the per-run best values across process
  instances (compile-cache state, chip clock/thermal state).

The feed sections are disabled per run (BENCH_PIPELINE=0) — they execute
AFTER the headline measurement and cannot influence it; skipping them keeps
10 runs tractable. Everything upstream of the headline
section is exactly the driver path.

Writes ``benchmarks/results_variance.json`` and prints a summary.

Usage: python benchmarks/variance_study.py [N]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "benchmarks", "results_variance.json")


def one_run(i: int) -> dict:
    env = dict(os.environ)
    # disable every feed section (they run after the headline measurement
    # and cannot influence it): resident + host-feed + streaming
    env["BENCH_PIPELINE"] = "0"
    env["BENCH_RESIDENT"] = "0"
    env["BENCH_STREAMING"] = "0"
    t0 = time.time()
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                          capture_output=True, text=True, timeout=1800,
                          cwd=ROOT, env=env)
    wall = time.time() - t0
    line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
    try:
        rec = json.loads(line)
    except json.JSONDecodeError:
        raise SystemExit(f"run {i}: no JSON line; stderr:\n{proc.stderr[-2000:]}")
    rec["_run_wall_s"] = round(wall, 1)
    print(f"run {i}: {rec['value']} img/s  compile {rec['phases']['compile_s']}s "
          f"reps {rec['phases']['rep_s']}  ({wall:.0f}s total)", flush=True)
    return rec


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 10
    runs = [one_run(i) for i in range(n)]
    values = np.array([r["value"] for r in runs])
    batch = runs[0]["batch"]
    # rep-level throughput samples: batch*steps/rep_s per rep per run
    rep_ips = [[batch * r["phases"]["steps_per_rep"] / s
                for s in r["phases"]["rep_s"]] for r in runs]
    within = np.array([np.std(r) / np.mean(r) for r in rep_ips])
    run_means = np.array([np.mean(r) for r in rep_ips])
    run_bests = np.array([np.max(r) for r in rep_ips])

    doc = {
        "section": "variance_budget",
        "n_runs": n,
        "headline_values": values.tolist(),
        "value_min": float(values.min()),
        "value_median": float(np.median(values)),
        "value_max": float(values.max()),
        "value_spread_pct": round(
            100.0 * (values.max() - values.min()) / np.median(values), 2),
        "value_cv_pct": round(100.0 * values.std() / values.mean(), 2),
        # decomposition
        "within_run_cv_pct_mean": round(100.0 * within.mean(), 2),
        "between_run_cv_pct_of_best": round(
            100.0 * run_bests.std() / run_bests.mean(), 2),
        "between_run_cv_pct_of_mean": round(
            100.0 * run_means.std() / run_means.mean(), 2),
        "compile_s": [r["phases"]["compile_s"] for r in runs],
        "warmup_s": [r["phases"]["warmup_s"] for r in runs],
        "rep_s": [r["phases"]["rep_s"] for r in runs],
        "run_wall_s": [r["_run_wall_s"] for r in runs],
        "conditions": {"batch": batch, "format": runs[0]["format"],
                       "precision": runs[0]["precision"],
                       "steps_per_dispatch": runs[0]["steps_per_dispatch"],
                       "device": runs[0]["device_kind"],
                       "feed_sections": "disabled (BENCH_PIPELINE/"
                                        "RESIDENT/STREAMING=0)"},
    }
    with open(OUT, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({k: doc[k] for k in (
        "value_min", "value_median", "value_max", "value_spread_pct",
        "value_cv_pct", "within_run_cv_pct_mean",
        "between_run_cv_pct_of_best")}, indent=1))
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
