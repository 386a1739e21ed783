"""Readings for setting the limits of a cell's output check, and the verdicts
those limits give, on the chip at the cell's own size, several seeds in one
process (set-up is long):

    python3 chipbench/readings.py --workload <name> --seeds 1,2,3 [--wrong 3]

For every seed: the cell is built, the checked steps go through the timed
call, and the program's gaps against the reference are printed (the lower
reading is the largest of them over the seeds). For the first ``--wrong``
seeds the same gaps are read with something wrong put in the program's
place: the reference in the control precision of the configuration
(``control_precision``), and the planted faults the cell can have (half of
the batch left out; a step that leaves the state unchanged; one leaf that
the program leaves unmoved). Every set of gaps then goes through
``compare.judge`` with the cell's committed limits, as a run's do, and the
verdict stands beside it (``correct``, and ``over``: the numbers that failed).
The benchmark's own runs never do this; ``tests/test_check.py`` keeps the same
at a small size.
"""

import argparse
import contextlib
import json
import sys
import time

import numpy as np

import run as harness


def state_unchanged(reference):
    """What a program whose steps return their state as they got it leaves
    behind, where the reference cannot be run so (one dispatch an epoch): no
    change and no moment. It is given the reference's own loss, so only the
    numbers on the state can fail it."""
    out = {k: [np.zeros_like(a) for a in v] for k, v in reference.items()}
    out["losses"] = reference["losses"]
    return out


def leaf_unmoved(program, reference):
    """The program's own readings with one leaf left where it was: the leaf
    whose change has the median norm in the reference."""
    import compare
    norms = compare.leaf_norms(reference["change"])
    k = int(np.argsort(norms)[len(norms) // 2])
    out = dict(program)
    out["change"] = [np.zeros_like(a) if i == k else a
                     for i, a in enumerate(program["change"])]
    return out


def worst_leaves(program, reference, names, what="change", top=3):
    import compare
    ref, prog = compare.leaf_norms(reference[what]), compare.leaf_norms(program[what])
    by = "grad" if "grad" in reference else "moment"      # as training_gaps keeps them
    keep = compare.moving_leaves(compare.leaf_norms(reference[by]))
    gaps = np.where(keep, compare.leaf_gaps(prog, ref), 0.0)
    return [{"leaf": names[i], "size": int(np.size(reference[what][i])),
             "gap": float(gaps[i]), "ref_norm": float(ref[i]), "median_norm": float(np.median(ref))}
            for i in np.argsort(-gaps)[:top]]


def read_seed(workload, seed, wrong):
    import jax

    import compare

    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.0, trace=0)
    bench = harness.Bench(args)
    harness.apply_env(bench)
    driver = harness.load_module("drivers", bench.traffic["driver"])
    out = {"seed": seed}

    def judged(what, readings):
        gaps = compare.training_gaps(readings, reference)
        ok, rows = compare.judge(gaps, bench.limits)
        out[what] = dict(gaps, correct=ok, over=[n for n, v, lim in rows if not v <= lim])

    with contextlib.redirect_stdout(sys.stderr):
        t = time.perf_counter()
        job = driver.Job(bench)
        job.build()
        job.warm()
        program = job.program_readings()
        names = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(job.params0)[0]]
        job.close()
        del job.state
        harness.free_device()
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        reference = job.reference_readings()
        t_ref = time.perf_counter() - t
        judged("program", program)
        out["worst_change_leaves"] = worst_leaves(program, reference, names)
        out["seconds"] = {"program": t_prog, "reference": t_ref}
        if wrong:
            judged("control", job.reference_readings(
                quantize_name=bench.cfg["control_precision"]))
            judged("fault_half_batch", job.reference_readings(rows=slice(0, None, 2)))
            judged("fault_state_unchanged",
                   job.reference_readings(skip_update=True) if "grad" in reference
                   else state_unchanged(reference))
            judged("fault_leaf_unmoved", leaf_unmoved(program, reference))
        harness.free_device()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--wrong", type=int, default=3)
    a = ap.parse_args()
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        print(json.dumps(read_seed(a.workload, seed, i < a.wrong)), flush=True)


if __name__ == "__main__":
    main()
