"""Shared microbenchmark harness.

Reference equivalent: the timing + correctness-gate pattern of
``/root/reference/benchmarks/gemm_benchmark.cpp:16-50`` (every timed kernel
is first checked against a trusted reference implementation — a benchmark
that produces wrong numbers fast is a bug, not a result) and the
section-per-op layout of ``tensor_ops_benchmark.cpp``.

TPU specifics: all timing is fenced with ``core.fence.hard_fence``, jitted
callables are warmed before timing, and throughput is best-of-reps
(steady-state capability, robust to dispatch jitter).
"""

from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from dcnn_tpu.core.fence import hard_fence


from dcnn_tpu.utils import enable_compile_cache

enable_compile_cache()


@dataclass
class Result:
    """One benchmark row: name, timing, derived rate, correctness verdict."""

    name: str
    seconds: float
    rate: Optional[float] = None        # work / second (unit below)
    unit: Optional[str] = None
    correct: Optional[bool] = None      # None = no gate for this row
    max_err: Optional[float] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    def to_json(self) -> Dict[str, Any]:
        out = {"name": self.name, "seconds": round(self.seconds, 6)}
        if self.rate is not None:
            out["rate"] = round(self.rate, 3)
            out["unit"] = self.unit
        if self.correct is not None:
            # np.array_equal & co. return np.bool (numpy 2), which the json
            # encoder rejects — coerce at the boundary
            out["correct"] = bool(self.correct)
            out["max_err"] = (None if self.max_err is None
                              else float(f"{self.max_err:.3e}"))
        out.update(self.extra)
        return out


def check_match(got, want, tol: float, name: str = "") -> tuple:
    """Correctness gate (reference ``gemm_benchmark.cpp:21-34`` check_match):
    elementwise compare against the trusted reference; returns
    (passed, max_abs_err). Relative tolerance scaled by the magnitude of
    ``want`` so fp32-vs-bf16 comparisons use a meaningful threshold."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return False, float("inf")
    denom = max(1.0, float(np.max(np.abs(want))))
    err = float(np.max(np.abs(got - want))) / denom
    return bool(err <= tol), err


def time_callable(fn: Callable[[], Any], steps: int = 10, reps: int = 3,
                  warmup: int = 2) -> float:
    """Best-of-reps seconds for ``steps`` dispatches of ``fn``.

    ``fn`` must return (a pytree containing) the device array(s) produced, so
    the fence can await them. Warmup covers compile + cache effects."""
    out = None
    for _ in range(warmup):
        out = fn()
    hard_fence(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn()
        hard_fence(out)
        best = min(best, time.perf_counter() - t0)
    return best / steps


def time_chained(op: Callable, args: tuple, feed: Callable,
                 length: int = 32, reps: int = 5, roofline=None):
    """Per-iteration seconds for ``length`` data-dependent iterations of
    ``op`` inside ONE jitted dispatch (``lax.scan``).

    Returns ``(seconds, sane)`` — ALWAYS a tuple, with or without
    ``roofline`` (the r5 polymorphic bare-float return invited silent
    tuple-as-number bugs, ADVICE r5); ``sane`` is True whenever no gate
    fired.

    ``roofline=(flops_per_iteration, peak_flops_or_None)``: physical sanity
    gate — a two-length delta that lands on correlated jitter can imply a
    rate above the chip's peak. With ``roofline`` set the measurement is retried up to twice while the
    implied FLOP rate exceeds 1.05× peak, and ``sane`` becomes False when a
    persistently impossible number remains, so callers can flag (never
    silently report) it. ``peak=None`` skips the check.

    A single dispatch has a fixed cost regardless of the op, so
    ``time_callable`` measures the dispatch, not the chip, for short ops.
    Chaining amortizes the dispatch to ``1/length`` while the data dependency (``feed(out, args) -> args`` must
    thread the output back into the next iteration's inputs) stops XLA from
    collapsing the loop. ``feed`` must preserve the args pytree
    structure/shapes/dtypes (scan carry invariant).

    The fence has a fixed cost too, so a single-length measurement is
    still constant-biased. This uses the
    **two-length difference method**: time the scan at ``length`` and at
    ``length // 4`` and divide the delta by the iteration delta — every
    constant cost (dispatch RPC, fence RTT, first/last-iteration DCE
    asymmetries) cancels exactly. The fence probe is a scalar computed
    *inside* the jit (one element per carry leaf), so awaiting it is a single
    D2H round trip.

    On the CPU backend this falls back to per-dispatch timing: local dispatch
    costs ~µs, while XLA:CPU runs loop bodies
    single-threaded, which would make chained numbers 10-20x worse than the
    op's real multi-threaded performance."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def _gated(measure):
        dt = measure()
        if roofline is None:
            return dt, True
        flops, peak = roofline
        tries = 0
        while peak and flops / dt > 1.05 * peak and tries < 2:
            dt = measure()
            tries += 1
        return dt, not (peak and flops / dt > 1.05 * peak)

    if jax.default_backend() == "cpu":
        jfn = jax.jit(lambda a: op(*a))
        return _gated(lambda: time_callable(
            lambda: jfn(args), steps=min(length, 10), reps=reps))

    @jax.jit
    def run(a, n):
        # RUNTIME trip count (n is traced, not static): one executable
        # serves both lengths, so the difference method compares literally
        # identical code — a static length would let XLA pick different
        # unroll regimes for the long and short runs, breaking the
        # equal-constant-cost assumption (observed as impossible TFLOP/s on
        # small fast-mode matmuls).
        def body(i, c):
            return feed(op(*c), c)

        c = lax.fori_loop(0, n, body, a)
        # in-jit scalar probe: a FULL reduction of every carry leaf. A
        # single-element probe is not enough — XLA slice-sinks through the
        # carried matmul chain (a[0,0] needs only row 0 of the previous
        # carry, inductively collapsing every iteration to row@matrix; we
        # measured impossible >500 TFLOP/s numbers that way). A full sum
        # needs every element of the final carry, so every iteration runs at
        # full width; its own cost is one reduction per *run*, amortized to
        # nothing by the difference method. Awaiting the scalar is one D2H
        # round trip.
        return sum(jnp.sum(l).astype(jnp.float32)
                   for l in jax.tree_util.tree_leaves(c))

    length = max(2, length)   # the difference method needs short < length

    def one(n: int) -> float:
        t0 = time.perf_counter()
        jax.device_get(run(args, jnp.int32(n)))
        return time.perf_counter() - t0

    # compile + warm (single executable for all lengths)
    jax.device_get(run(args, jnp.int32(length)))

    # PAIRED differences, median-combined: taking independent best-of-reps
    # for each length lets slow drift between the two measurement groups
    # fake the delta. Back-to-back pairs see the same conditions; the
    # median rejects outlier round trips. If the delta is still below the
    # noise floor, escalate the iteration
    # count — the runtime trip count makes longer runs free of recompiles.
    NOISE_FLOOR = 0.05           # seconds the delta must clear
    MAX_LENGTH = 1 << 16
    MAX_RUN_WALL = 8.0           # never schedule a device loop much past
                                 # this — long single kernels can trip the
                                 # TPU watchdog and kill the worker process

    def measure() -> float:
        nonlocal length
        while True:
            short = max(1, length // 4)
            t_longs, diffs = [], []
            for _ in range(reps):
                tl = one(length)
                diffs.append(tl - one(short))
                t_longs.append(tl)
            diffs.sort()
            delta = diffs[len(diffs) // 2]
            t_long = sorted(t_longs)[len(t_longs) // 2]
            if (delta >= NOISE_FLOOR or length >= MAX_LENGTH
                    or t_long >= MAX_RUN_WALL):
                break
            if delta > 0:
                # scale so the next delta lands ~2x the floor, bounded by
                # the per-run wall guard (measured t_long is the ground
                # truth for how expensive this loop really is)
                est = delta / (length - short)
                target = max(length * 2, int(2 * NOISE_FLOOR / est * 1.34))
                wall_cap = max(length * 2,
                               int(length * MAX_RUN_WALL / max(t_long, 1e-3)))
                length = min(MAX_LENGTH, target, wall_cap)
            else:
                # delta lost in jitter: escalate gently — a huge jump here
                # (est~0 => max length) once produced a
                # quarter-million-iteration kernel that crashed the TPU
                # worker
                length = min(MAX_LENGTH, length * 4)
        if delta > 0:
            return delta / (length - short)
        # degenerate (op so cheap it drowns in jitter even at MAX_LENGTH):
        # fall back to the long-run average, which at worst over-reports
        return one(length) / length

    return _gated(measure)


def e2e_chain_length(short_length: int) -> int:
    """Chain length for end-to-end model rows (both bench entry points).

    On TPU, 1024 iterations put seconds of device work behind the
    two-length delta, so jitter of a few ms stays a small share of it. Tiny
    mode and CPU
    keep the caller's short length — the CPU fallback is per-dispatch
    timing and tiny mode must stay CI-sized on any backend."""
    import jax

    if tiny_mode() or jax.default_backend() != "tpu":
        return short_length
    return 1024


def replace_feed(i: int = 0):
    """Feed for time_chained when the op output has the same shape/dtype as
    ``args[i]``: the output simply becomes the next iteration's input. Full
    consumption of the output (XLA cannot dead-code or slice-sink any of the
    timed work) at zero added cost. Values may drift to inf over iterations —
    harmless for timing; TPU float arithmetic is constant-time."""

    def feed(out, args):
        new = list(args)
        new[i] = out
        return tuple(new)

    return feed


def outputs_as_args_feed():
    """Feed for ops whose output tuple matches the args tuple elementwise
    (e.g. a grad function over its own inputs)."""

    def feed(out, args):
        return tuple(out)

    return feed


def dep_feed(i: int):
    """Generic feed for shape-mismatched ops: fold a FULL reduction of every
    output leaf into a one-element perturbation of args[i].

    The full ``jnp.sum`` matters: consuming a single output element would let
    XLA slice-sink through the (single-user) producer and shrink the timed op
    to the one element the probe reads — e.g. a GEMM collapses to one K-dot.
    A whole-output reduction forces every element to exist. Cost: one extra
    read of the output per iteration — negligible for FLOP-bound ops; prefer
    :func:`replace_feed` (zero-cost) whenever shapes allow."""
    import jax
    import jax.numpy as jnp

    def feed(out, args):
        leaves = ([out] if hasattr(out, "dtype")
                  else jax.tree_util.tree_leaves(out))
        eps = sum(jnp.sum(l).astype(jnp.float32) for l in leaves) * 1e-30
        new = list(args)
        a = new[i]
        new[i] = a.at[(0,) * a.ndim].add(eps.astype(a.dtype))
        return tuple(new)

    return feed


def report(section: str, results: List[Result], out_path: Optional[str] = None,
           meta: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Assemble + optionally persist one section's machine-readable report."""
    import jax

    doc = {
        "section": section,
        "device": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "results": [r.to_json() for r in results],
        "all_correct": bool(all(r.correct for r in results
                                if r.correct is not None)),
    }
    if meta:
        doc["meta"] = meta
    if out_path:
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
    return doc


def print_table(doc: Dict[str, Any]) -> None:
    print(f"== {doc['section']} [{doc['device']}] ==")
    for r in doc["results"]:
        gate = ("" if "correct" not in r
                else ("  OK" if r["correct"] else "  **MISMATCH**"))
        rate = (f"  {r['rate']:>12.3f} {r['unit']}" if "rate" in r else "")
        print(f"  {r['name']:<42s} {r['seconds'] * 1e3:>9.3f} ms{rate}{gate}")


def tiny_mode() -> bool:
    """BENCH_TINY=1 shrinks problem sizes so the suite doubles as a CI test
    (the reference runs its benchmarks as manual executables; here the same
    code is importable and pytest-runnable)."""
    return os.environ.get("BENCH_TINY", "0") == "1"
