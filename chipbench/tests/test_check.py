"""The output check has to fail what is wrong, at a size a test run can hold
(the cells' ``rehearsal`` sizes on the CPU, judged by their
``rehearsal_limits``): the control precision put in the program's place, and
the program itself with a fault planted under the harness. Slow (minutes a
case on a cold compile cache): every case is a process of its own."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    LISTED = {w["name"] for w in json.load(_f)["workloads"]}
CELLS = ["r18_train_fed", "r18_train_resident"]


def _needs(cell):
    if cell not in LISTED:
        pytest.skip(f"{cell} is not a cell of BENCHMARK.json")


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_planted_faults_fail_the_limits(cell):
    """``readings.py`` puts every set of gaps through ``compare.judge`` with
    the cell's limits (here the rehearsal ones), as a run does."""
    _needs(cell)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "readings.py"), "--workload", cell,
         "--seeds", "2147483659", "--wrong", "1"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=3000)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["program"]["correct"], r["program"]
    for wrong in ("control", "fault_half_batch", "fault_state_unchanged",
                  "fault_leaf_unmoved"):
        assert not r[wrong]["correct"], (wrong, r[wrong])
    assert any(n.startswith("change_") for n in r["fault_state_unchanged"]["over"]), r
    assert r["fault_leaf_unmoved"]["over"] == ["change_gap"], r


@pytest.mark.parametrize("cell,fault,correct", [
    ("r18_train_fed", "none", True),
    ("r18_train_fed", "state_unchanged", False),
    ("r18_train_fed", "half_batch", False),
    ("r18_train_resident", "state_unchanged", False),
    ("r18_train_resident", "half_batch", False),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(cell, fault, correct):
    _needs(cell)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run.py"), fault,
         "--workload", cell, "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=3000)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is correct, (result["checks"], out.stderr[-1500:])
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
