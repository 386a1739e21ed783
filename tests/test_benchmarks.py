"""Benchmark-suite smoke tests (VERDICT r1 #7; reference
``benchmarks/gemm_benchmark.cpp:16-50`` correctness-gate pattern)."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARKS = os.path.join(REPO, "benchmarks")


@pytest.fixture(autouse=True)
def benchmarks_common():
    """``benchmarks/common.py`` and ``examples/common.py`` share the name
    ``common``. Another test file of the same worker (test_bring_up, through
    chip_smoke) may have imported the examples' one: resolve the name to the
    benchmarks' for these tests, and give back what was there."""
    theirs = sys.modules.pop("common", None)
    sys.path.insert(0, BENCHMARKS)
    try:
        yield
    finally:
        sys.path.remove(BENCHMARKS)
        sys.modules.pop("common", None)
        if theirs is not None:
            sys.modules["common"] = theirs


def test_check_match_gate():
    from common import check_match

    ok, err = check_match(np.ones(4), np.ones(4) + 1e-7, 1e-5)
    assert ok and isinstance(ok, bool) and err < 1e-5
    ok, _ = check_match(np.ones(4), np.ones(4) + 1.0, 1e-5)
    assert not ok
    ok, err = check_match(np.ones(4), np.ones(5), 1e-5)
    assert not ok and err == float("inf")


def test_serialization_section_runs_and_gates():
    import bench_serialization

    os.environ["BENCH_TINY"] = "1"
    try:
        doc = bench_serialization.run()
    finally:
        os.environ.pop("BENCH_TINY", None)
    assert doc["all_correct"] is True
    names = {r["name"] for r in doc["results"]}
    assert {"checkpoint_save", "checkpoint_load"} <= names
    assert any(n.startswith("compress_") for n in names)
    # machine-readable: every row JSON-serializable
    import json

    json.dumps(doc)


def test_time_chained_roofline_gate(monkeypatch):
    """The return contract: ALWAYS (seconds, sane) — sane=True when no
    roofline gate fired (ADVICE r5: the old polymorphic bare-float return
    invited silent tuple-as-number bugs) — and an implied FLOP rate above
    1.05x peak is retried then flagged sane=False rather than silently
    returned (the guard behind the int8 e2e rows; see RESULTS.md
    measurement-spread postmortem). The backend is pinned to the CPU
    per-dispatch fallback so the forced-insane case never chases the TPU
    noise-floor escalation (minutes on a real chip for a trivial op)."""
    import jax
    import jax.numpy as jnp

    from common import dep_feed, time_chained

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    x = jnp.ones((8, 8), jnp.float32)
    op = lambda a: a * 2.0

    dt, sane = time_chained(op, (x,), dep_feed(0), length=4)
    assert isinstance(dt, float) and dt > 0
    assert sane is True

    # absurdly high peak -> any measurement is sane
    dt, sane = time_chained(op, (x,), dep_feed(0), length=4,
                            roofline=(1.0, 1e30))
    assert sane is True and dt > 0
    # peak=None skips the check but keeps the tuple shape
    dt, sane = time_chained(op, (x,), dep_feed(0), length=4,
                            roofline=(1e30, None))
    assert sane is True
    # absurdly low peak -> implied rate always "impossible": retried, then
    # flagged, never silently returned as a bare float
    dt, sane = time_chained(op, (x,), dep_feed(0), length=4,
                            roofline=(1e30, 1.0))
    assert sane is False and dt > 0


def test_e2e_chain_length_contract(monkeypatch):
    """Both branches pinned explicitly (the real backend varies by host):
    TPU gets the long jitter-proof chain unless tiny mode; CPU keeps the
    caller's short length always."""
    import jax

    from common import e2e_chain_length

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert e2e_chain_length(8) == 8

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert e2e_chain_length(8) == 1024
    monkeypatch.setenv("BENCH_TINY", "1")
    assert e2e_chain_length(4) == 4


@pytest.mark.slow
def test_run_all_tiny_subprocess():
    """Full suite in tiny mode as one command (the 'one command emits a
    machine-readable benchmark report' done-criterion)."""
    env = dict(os.environ, BENCH_TINY="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run_all.py"),
         "--only", "bench_gemm", "--out", "/tmp/bench_results_test.json"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    import json

    with open("/tmp/bench_results_test.json") as f:
        doc = json.load(f)
    assert doc["all_correct"] is True
