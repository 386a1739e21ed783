"""Bench-history regression gate CLI over ``BENCH_r*.json`` captures.

Thin driver around :mod:`dcnn_tpu.obs.regress` (semantics documented
there: newest capture vs the best of a trailing window, per metric, with
per-metric noise tolerances and a cache-warmth guard on ``compile_s``).

Usage::

    python benchmarks/compare.py                 # repo-root BENCH_r*.json
    python benchmarks/compare.py A.json B.json   # explicit history, oldest
                                                 # first; last file is gated
    python benchmarks/compare.py --window 3 --tolerance 0.15
    python benchmarks/compare.py --json          # machine-readable report
    python benchmarks/compare.py --self-test     # fixture run (tier-1)

Exit code: 0 = no regressions, 1 = regression(s) flagged, 2 = usage /
unreadable history. A CI job gates on exactly that.

``--self-test`` regression-tests the gate itself: it writes a made-up
five-capture BENCH history into a temp dir, appends a
capture with a planted 25% img/s regression, and asserts the gate flags
the planted file and passes the clean history. Tier-1 runs this via
``tests/test_regress.py``, so a gate that stops gating fails CI.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from dcnn_tpu.obs import regress  # noqa: E402


# Fixture trajectory for --self-test and tests/test_regress.py: made-up
# numbers in the shape a capture history takes (monotone img/s growth,
# metrics appearing over time, one series that bounces 3x between healthy
# captures). No measurement stands behind any of them.
_FIXTURE_HISTORY = [
    {"metric": "m", "value": 7000.0},
    {"metric": "m", "value": 22000.0, "mfu": 0.37, "h2d_gbps": 0.033},
    {"metric": "m", "value": 24000.0, "mfu": 0.40, "h2d_gbps": 0.010},
    {"metric": "m", "value": 25000.0, "mfu": 0.42, "h2d_gbps": 0.032},
    {"metric": "m", "value": 26000.0, "mfu": 0.44, "h2d_gbps": 0.011,
     "infer_int8_img_per_sec": 230000.0,
     "phases": {"compile_s": 150.0, "compile_cache_hit": None}},
]
# planted: img/s down 25% vs the window best — the gate MUST flag this
_FIXTURE_REGRESSED = {
    "metric": "m", "value": 19500.0, "mfu": 0.436, "h2d_gbps": 0.028,
    "infer_int8_img_per_sec": 231000.0,
    "phases": {"compile_s": 151.0, "compile_cache_hit": None}}
# planted-clean: everything within tolerance — the gate MUST pass this
_FIXTURE_CLEAN = {
    "metric": "m", "value": 25600.0, "mfu": 0.433, "h2d_gbps": 0.029,
    "infer_int8_img_per_sec": 228000.0,
    "phases": {"compile_s": 148.0, "compile_cache_hit": None}}


def write_fixture_history(d: str) -> list:
    """The fixture history as ``BENCH_r01..05.json`` under ``d``; returns
    the files oldest first."""
    for i, cap in enumerate(_FIXTURE_HISTORY, start=1):
        with open(os.path.join(d, f"BENCH_r{i:02d}.json"), "w") as f:
            json.dump({"n": i, "parsed": cap}, f)
    return regress.find_bench_files(d)


def self_test() -> int:
    """Fixture run: write BENCH files, plant a regression, assert the gate
    catches exactly it. Prints PASS/FAIL lines; returns an exit code."""
    failures = []

    def check(name, cond):
        print(f"  {'PASS' if cond else 'FAIL'}: {name}")
        if not cond:
            failures.append(name)

    with tempfile.TemporaryDirectory() as d:
        files = write_fixture_history(d)
        check("fixture discovery finds 5 captures in order",
              len(files) == 5 and files == sorted(files))

        clean = regress.compare_files(files)
        check("clean fixture trajectory passes", clean["ok"])

        # append the planted-regression capture as r06 and re-gate
        with open(os.path.join(d, "BENCH_r06.json"), "w") as f:
            json.dump({"n": 6, "parsed": _FIXTURE_REGRESSED}, f)
        flagged = regress.compare_files(regress.find_bench_files(d))
        check("planted 25% img/s regression is flagged",
              not flagged["ok"] and "img_per_sec" in flagged["regressions"])
        check("only the planted metric is flagged",
              flagged["regressions"] == ["img_per_sec"])

        # replace r06 with an in-tolerance capture: must pass again
        with open(os.path.join(d, "BENCH_r06.json"), "w") as f:
            json.dump({"n": 6, "parsed": _FIXTURE_CLEAN}, f)
        ok_again = regress.compare_files(regress.find_bench_files(d))
        check("in-tolerance follow-up capture passes", ok_again["ok"])

        # lower-is-better direction: compile_s blowing up must flag (same
        # cache-warmth guard value as the prior capture)
        blown = dict(_FIXTURE_CLEAN)
        blown["phases"] = {"compile_s": 400.0, "compile_cache_hit": None}
        with open(os.path.join(d, "BENCH_r06.json"), "w") as f:
            json.dump({"n": 6, "parsed": blown}, f)
        comp = regress.compare_files(regress.find_bench_files(d))
        check("compile_s blow-up (same cache state) is flagged",
              "compile_s" in comp["regressions"])

        # ...but a cache-warmth change makes compile_s incomparable
        warm = dict(blown)
        warm["phases"] = {"compile_s": 400.0, "compile_cache_hit": True}
        with open(os.path.join(d, "BENCH_r06.json"), "w") as f:
            json.dump({"n": 6, "parsed": warm}, f)
        guarded = regress.compare_files(regress.find_bench_files(d))
        row = next(r for r in guarded["metrics"]
                   if r["metric"] == "compile_s")
        check("compile_s skipped across a cache-warmth change",
              row["verdict"].startswith("skipped"))

    print("self-test:", "PASS" if not failures else
          f"FAIL ({len(failures)}: {failures})")
    return 0 if not failures else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="gate the newest BENCH capture against the trailing "
                    "window of prior captures")
    ap.add_argument("files", nargs="*",
                    help="capture files oldest->newest (default: "
                         "BENCH_r*.json in the repo root)")
    ap.add_argument("--window", type=int, default=regress.DEFAULT_WINDOW,
                    help="trailing captures compared per metric "
                         "(default %(default)s)")
    ap.add_argument("--tolerance", type=float,
                    default=regress.DEFAULT_TOLERANCE,
                    help="default relative tolerance; per-metric overrides "
                         "in obs/regress.py still apply "
                         "(default %(default)s)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON")
    ap.add_argument("--self-test", action="store_true",
                    help="run the fixture-based gate self-test and exit")
    args = ap.parse_args(argv)

    if args.self_test:
        return self_test()

    files = args.files or regress.find_bench_files(_ROOT)
    if len(files) < 2:
        print(f"need >= 2 captures to compare, found {len(files)} "
              f"({files or 'no BENCH_r*.json in ' + _ROOT})",
              file=sys.stderr)
        return 2
    try:
        report = regress.compare_files(files, window=args.window,
                                       tolerance=args.tolerance)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(f"gating {os.path.basename(report['files'][-1])} against "
              f"{len(report['files']) - 1} prior capture(s), "
              f"window {report['window']}:")
        print(regress.format_report(report))
        if report["unparseable_files"]:
            print(f"  (skipped unparseable: "
                  f"{report['unparseable_files']})")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
