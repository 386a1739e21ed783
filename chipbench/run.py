"""One run of one cell of the benchmark.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to a cell is found by name from ``BENCHMARK.json`` at
the root of the checkout: the configuration ``chipbench/configs/<config>.json``
(with its plain reference beside it), the traffic mix
``chipbench/traffic/<traffic>.json`` (which names its driver among
``chipbench/drivers/``), the limits of the output check
``chipbench/limits/<workload>.json`` and, for a traced run, one reader per
per-layer metric, ``chipbench/layer_metrics/<metric>.py``.

The run refuses any backend but ``tpu`` (exit 3, no result) — except under
the program's own rule, ``JAX_PLATFORMS=cpu``, where it rehearses the cell at
the tiny sizes of the traffic file's ``rehearsal`` block, names the device
``cpu`` and reports no metric. The last line of standard output is the
result; everything else goes to standard error.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT, os.path.join(ROOT, "examples")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

NO_CHIP = 3


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def lists_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Bench:
    """What a driver gets: the cell's files, the seed, the window, and the
    harness's own spans and counters."""

    def __init__(self, args):
        self.args = args
        self.seed = int(args.seed)
        self.trace = bool(int(args.trace))
        self.spec = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if args.workload not in cells:
            raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json "
                             f"(has: {sorted(cells)})")
        self.cell = cells[args.workload]
        self.chips = int(self.cell["chips"])
        self.cfg = load_json(HERE, "configs", self.cell["config"] + ".json")
        self.traffic = load_json(HERE, "traffic", self.cell["traffic"] + ".json")
        limits = load_json(HERE, "limits", args.workload + ".json")
        self.limits = limits["limits"]
        self.rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
        if self.rehearsal:
            self.limits = limits.get("rehearsal_limits", self.limits)
            small = self.traffic.get("rehearsal", {})
            self.cfg = dict(self.cfg, **small.get("config", {}))
            self.traffic = dict(self.traffic, **small.get("traffic", {}))
            if self.chips > 1:
                flag = f"--xla_force_host_platform_device_count={self.chips}"
                os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
        self.compiles = 0
        self.window = None
        self.counters = {}

    def span(self, what: str):
        """A host span of the harness's own, on the profiler's clock in a
        traced run and nothing at all otherwise."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("chipbench:" + what)


def apply_env(bench) -> None:
    """The program reads its settings from the environment, as its users set
    them: the configuration's precision and batch, the traffic file's ``env``."""
    cfg, traffic = bench.cfg, bench.traffic
    os.environ["DCNN_PRECISION"] = cfg["precision"]
    for k, v in traffic.get("env", {}).items():
        os.environ[k] = str(v)
    os.environ["BATCH_SIZE"] = str(traffic.get("batch_size", cfg["batch_size"]))
    os.environ["SEED"] = str(bench.seed % (2 ** 31 - 1))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def memory_now(devices) -> int:
    """What is not free on the fullest chip at this instant. The TPU runtime
    keeps two books, read here in one call: ``bytes_in_use`` (arrays) and
    ``bytes_reserved`` (what the loaded programs hold for their temporaries,
    from their first run until they are unloaded). The free memory it reports
    is the limit less both (PERF.md section 3)."""
    most = 0
    for d in devices:
        stats = d.memory_stats() or {}
        most = max(most, int(stats.get("bytes_in_use", 0)) + int(stats.get("bytes_reserved", 0)))
    return most


def memory_books(devices) -> dict:
    """Each book's own peak over the process so far, on the chip where it is
    largest: for the record, beside the reading taken inside the window."""
    stats = [d.memory_stats() or {} for d in devices]
    return {"arrays_peak_bytes": max(int(s.get("peak_bytes_in_use", 0)) for s in stats),
            "programs_peak_bytes": max(int(s.get("peak_bytes_reserved", 0)) for s in stats)}


def free_device() -> None:
    """Drop everything the program left on the chips, so that the reference
    has the memory to itself."""
    import gc

    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()
    gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    bench = Bench(args)
    cfg, traffic = bench.cfg, bench.traffic

    apply_env(bench)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not bench.rehearsal:
        log(f"chipbench: JAX came up on {platform!r}, not 'tpu'; no result")
        return NO_CHIP
    if len(devices) < bench.chips:
        log(f"chipbench: the cell asks for {bench.chips} chips, JAX has {len(devices)}; no result")
        return NO_CHIP
    devices = devices[:bench.chips]

    import flops
    peaks = None if bench.rehearsal else flops.load_peaks(devices[0].device_kind)

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            bench.compiles += 1
    jax.monitoring.register_event_duration_secs_listener(on_compile)

    from window import Window
    import compare

    driver = load_module("drivers", traffic["driver"])
    trace_dir = None
    reduced = {}
    with contextlib.redirect_stdout(sys.stderr):
        t_jax = time.perf_counter()
        job = driver.Job(bench)
        job.build()                # the program's objects, data and weights from the seed
        t_built = time.perf_counter()
        job.warm()                 # the checked steps, through the window's own call
        log(f"chipbench set-up: imports and devices {t_jax - T_START:.1f} s, build "
            f"{t_built - t_jax:.1f} s, warm-up with the checked steps "
            f"{time.perf_counter() - t_built:.1f} s")
        fullest = [0]

        def read_memory():         # at every step boundary of the window
            fullest[0] = max(fullest[0], memory_now(devices))
        if bench.trace:
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")

            def stop_trace():
                jax.profiler.stop_trace()
            bench.window = Window(args.seconds,
                                  trace_seconds=float(traffic.get("trace_seconds", 3.0)),
                                  on_trace_end=stop_trace, on_boundary=read_memory)
            jax.profiler.start_trace(trace_dir)
        else:
            bench.window = Window(args.seconds, on_boundary=read_memory)
        compiles_before = bench.compiles
        setup_s = time.perf_counter() - T_START
        job.run_window(bench.window)   # opens the window itself, at a step boundary
        w = bench.window
        compiles_in_window = bench.compiles - compiles_before
        if bench.trace and w.t_trace_end is None:
            jax.profiler.stop_trace()
            w.t_trace_end, w.traced_images = w.t_close, w.images
        mem_peak, books = fullest[0], memory_books(devices)
        log(f"chipbench memory: {mem_peak} bytes not free at the fullest boundary of the "
            f"window; over the process, arrays peaked at {books['arrays_peak_bytes']} and "
            f"the programs' temporaries at {books['programs_peak_bytes']}")
        program = job.program_readings()
        failed = int(job.failed_steps())
        job.close()
        del job.state
        free_device()

        if bench.trace:
            import trace_reduce
            paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
            if paths:
                reduced = trace_reduce.reduce(trace_reduce.load(paths[0]),
                                              trace_reduce.op_metadata(paths[0]))
            shutil.rmtree(trace_dir, ignore_errors=True)

        t_ref = time.perf_counter()
        reference = job.reference_readings()
        gaps = compare.training_gaps(program, reference)
        gaps["compiles_in_window"] = float(compiles_in_window)
        gaps["failed_steps"] = float(failed)
        limits = dict(bench.limits, compiles_in_window=0.0, failed_steps=0.0)
        correct, rows = compare.judge(gaps, limits)
        log("chipbench gaps: " + json.dumps(gaps))
        steps_s = sorted(w.step_seconds())
        if steps_s:
            log(f"chipbench steps: median {steps_s[len(steps_s) // 2]:.4f} s, "
                f"shortest {steps_s[0]:.4f} s, longest {steps_s[-1]:.4f} s")
        log(f"chipbench: reference took {time.perf_counter() - t_ref:.1f} s; "
            f"window {w.elapsed:.3f} s, {w.steps} steps, {w.images} images, "
            f"{w.epoch_turns} epoch turn-overs")

    metrics = {}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak, **books}
    result = {"correct": bool(correct), "attempted": int(w.steps), "failed": failed,
              "metrics": metrics, "device": device}
    if bench.rehearsal:
        result["rehearsal"] = True
    elif not bench.trace:
        for m in bench.spec["end_to_end"]:
            if not lists_cell(m, args.workload):
                continue
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif traffic["reports"].get("img_per_s") == m["name"]:
                metrics[m["name"]] = {"value": w.images_per_s, "unit": m["unit"]}
    else:
        import trace_reduce
        device["busy_s"] = reduced.get("busy_s_mean", 0.0)
        device["window_s"] = reduced.get("window_s", 0.0)
        ctx = {"reduced": reduced, "window": w, "cfg": cfg, "traffic": traffic,
               "peaks": peaks, "chips": bench.chips, "memory_peak_bytes": mem_peak,
               "counters": bench.counters, "log": log}
        for m in bench.spec["per_layer"]:
            if not lists_cell(m, args.workload):
                continue
            value = load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(reduced),
                               "idle_gaps": trace_reduce.top_gaps(reduced)}
    checks = {name: {"value": value, "limit": limit} for name, value, limit in rows}
    result["checks"] = checks
    for name, value, limit in rows:
        log(f"chipbench check: {name} = {value:.6g} (limit {limit:.6g})"
            f"{'' if value <= limit else '  <-- over'}")
    log(f"chipbench: correct = {bool(correct)}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
