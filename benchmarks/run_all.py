"""Run every microbenchmark section and persist one machine-readable report.

Reference equivalent: the ``benchmarks/`` executables of the reference
(gemm / tensor-ops / serialization / compression), unified behind one
command. Usage::

    python benchmarks/run_all.py [--out benchmarks/results.json]
    BENCH_TINY=1 python benchmarks/run_all.py      # CI-sized problems

Exit code is non-zero if any section's correctness gate fails — wrong-fast
is a bug, not a result (gemm_benchmark.cpp:21-34).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import print_table  # importing it enables the compile cache

SECTIONS = ("bench_gemm", "bench_conv", "bench_ops", "bench_attention",
            "bench_serialization", "bench_pipeline", "bench_pallas_conv",
            "bench_int8")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results.json"))
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of section module names")
    ap.add_argument("--merge", action="store_true",
                    help="merge the sections that ran into an existing --out "
                         "report instead of replacing it (for --only reruns)")
    args = ap.parse_args()

    import importlib

    import jax

    docs = []
    ok = True
    for mod_name in (args.only or SECTIONS):
        t0 = time.perf_counter()
        doc = importlib.import_module(mod_name).run()
        doc["wall_seconds"] = round(time.perf_counter() - t0, 1)
        print_table(doc)
        docs.append(doc)
        ok = ok and doc["all_correct"]

    out = {
        "suite": "dcnn_tpu_microbenchmarks",
        "device": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "tiny": os.environ.get("BENCH_TINY", "0") == "1",
        "all_correct": ok,
        "sections": docs,
    }
    if args.merge and os.path.exists(args.out):
        # refresh only the sections that ran (--only reruns), keep the rest,
        # and recompute the top-level gate — no hand-splicing of the report
        with open(args.out) as f:
            prev = json.load(f)
        merged = {s["section"]: s for s in prev.get("sections", [])}
        merged.update({s["section"]: s for s in docs})
        out["sections"] = list(merged.values())
        out["all_correct"] = ok = bool(
            all(s["all_correct"] for s in out["sections"]))
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"\nwrote {args.out}  all_correct={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
