"""Why the chip is idle between resident epochs: one cell of the benchmark
driven through a ``jax.profiler`` capture of a few epochs, then ``gaps``.

    chiprun -- python3 tools/epoch_gaps.py --workload kimilin_train_resident --seed 7 --seconds 8

Builds the cell's job as ``chipbench/run.py`` does (the same driver, window
and boundary readings), warms it with the checked epoch, captures a window of
``--seconds`` (whole epochs: 8 s is two of Kimi's) and prints the table of
``python -m dcnn_tpu.obs.trace gaps`` for it, then the window's entries of the
program's dispatch log epoch by epoch (the dispatch call, the fence, publish,
and the caller's time between two ``train_epoch`` calls). No reference and no
result line: a measuring aid, not the benchmark. ``--keep DIR`` copies the
capture there. ``--python-tracer 0`` captures without the profiler's Python
tracer, which is on in ``jax.profiler``'s defaults (and so in the harness's
traced runs) and slows the host between two epochs: the idle share of a
capture with it is not the idle share of a run without a capture (PERF.md,
PR 37). Under ``JAX_PLATFORMS=cpu`` it rehearses at the cell's tiny sizes and
has no device plane to read.
"""

import argparse
import contextlib
import glob
import importlib.util
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None, help="copy the capture's .xplane.pb here")
    ap.add_argument("--python-tracer", type=int, default=1, choices=(0, 1),
                    help="the capture's python_tracer_level (jax's default: 1)")
    args = ap.parse_args()
    args.trace = 1          # the harness's own chipbench: spans, as in its traced runs

    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(ROOT, "chipbench", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)             # puts chipbench/ and examples/ on the path
    bench = run.Bench(args)
    run.apply_env(bench)

    import jax
    devices = jax.devices()[:bench.chips]
    if devices[0].platform != "tpu" and not bench.rehearsal:
        run.log(f"epoch_gaps: JAX came up on {devices[0].platform!r}, not 'tpu'")
        return run.NO_CHIP

    from window import Window

    from dcnn_tpu.obs import dispatch_log
    from dcnn_tpu.obs.trace import device_gaps, format_gaps, read_xplane

    out = tempfile.mkdtemp(prefix="epoch-gaps-")
    try:
        with contextlib.redirect_stdout(sys.stderr):
            job = run.load_module("drivers", bench.traffic["driver"]).Job(bench)
            job.build()
            job.warm()
            window = bench.window = Window(
                args.seconds, on_boundary=lambda: run.memory_now(devices))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = args.python_tracer
            jax.profiler.start_trace(out, profiler_options=options)
            try:
                job.run_window(window)
            finally:
                jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(path, args.keep)
        print(f"{args.workload}, seed {args.seed}, python tracer {args.python_tracer}: "
              f"{window.steps} epochs in {window.elapsed:.3f} s")
        print(format_gaps(device_gaps(*read_xplane(path))))
    finally:
        shutil.rmtree(out, ignore_errors=True)

    print("the dispatch log, epoch by epoch (ms; fence in s)")
    print(f"  {'dispatch':>9} {'fence_s':>9} {'publish':>9} {'to next call':>12} {'first':>6}")
    entries = [e for e in dispatch_log() if window.t_open <= e.t_call < window.t_close]
    for e, nxt in zip(entries, entries[1:] + [None]):
        between = "" if nxt is None else f"{1e3 * (nxt.t_call - e.t_published):.2f}"
        print(f"  {1e3 * (e.t_returned - e.t_call):>9.2f} {e.t_fenced - e.t_returned:>9.4f} "
              f"{1e3 * (e.t_published - e.t_fenced):>9.2f} {between:>12} {str(e.first):>6}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
