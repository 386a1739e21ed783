"""Per-layer profiling.

Reference equivalent: the µs-per-named-layer forward/backward maps +
``print_profiling_summary`` table in ``Sequential``
(``sequential.hpp:54-55,461-498,323-418``) with NORMAL (clear per batch) vs
CUMULATIVE modes (``train.hpp:37,160-162``).

On TPU, timing *inside* a jitted step is meaningless (XLA fuses across layer
boundaries), so per-layer timing runs the layer chain eagerly layer-by-layer
with a hard device fence — the same numbers the reference's
per-layer-sync profiling produces, at the same cost model (a profiling run,
not the training fast path; the fence is ``core.fence.hard_fence``). The
replay walks every layer on its own; a training-mode ``Sequential.apply``
does not where a model starts with conv -> [bn] -> activation -> 2x2 max-pool
(``nn/sequential.py _apply_pool_phase``: ResNet-18/34's stem), so for those
four layers the replay's split, and its ``reduce_window`` pool backward, are
of a path the fused step no longer takes: read them as one unit. For
production tracing, ``trace()`` wraps
``jax.profiler`` for xprof/tensorboard.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from collections import defaultdict
from typing import Dict

import jax

from ..core.config import ProfilerType
from ..core.fence import hard_fence
from ..core.precision import cast_to_compute, get_precision_mode
from ..nn.sequential import Sequential


class LayerProfiler:
    def __init__(self, mode: ProfilerType = ProfilerType.NORMAL):
        self.mode = mode
        self.forward_us: Dict[str, float] = defaultdict(float)
        self.backward_us: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        # (direction, model, x.shape, x.dtype, training, precision-mode)
        # tuples already warmed — everything that changes the compiled
        # executable gets its own warm pass. Holding the model object (not
        # id()) also pins it against GC id-reuse aliasing.
        self._warmed: set = set()

    def clear(self) -> None:
        self.forward_us.clear()
        self.backward_us.clear()
        self.counts.clear()

    def maybe_clear_per_batch(self) -> None:
        if self.mode == ProfilerType.NORMAL:
            self.clear()

    def profile_forward(self, model: Sequential, params, state, x, *,
                        training: bool = False, rng=None):
        """Run the model layer-by-layer, timing each (device-synced).

        An untimed warm pass runs first so the timed pass measures steady
        state: the first call to each layer executable AND to the fence's
        tiny slice executable otherwise pays XLA compile time inside the
        timed region (the reference profiles steady-state kernels too —
        CUDA context/module load happens before its timers start)."""
        def run(record: bool):
            # Mirror Sequential.apply's precision policy (input + per-layer
            # param casts) so bf16-mode timings measure the bf16 path, not
            # the fp32 one the mode exists to avoid.
            h = cast_to_compute(x)
            new_state = []
            for i, layer in enumerate(model.layers):
                sub_rng = jax.random.fold_in(rng, i) if rng is not None else None
                t0 = time.perf_counter()
                h, s = layer.apply(cast_to_compute(params[i]), state[i], h,
                                   training=training, rng=sub_rng)
                hard_fence(h)
                if record:
                    self.forward_us[layer.name] += (time.perf_counter() - t0) * 1e6
                    self.counts[layer.name] += 1
                new_state.append(s)
            return h, tuple(new_state)

        # Key on the model object itself (not id(): reuse after GC would alias)
        # plus everything that changes the compiled executable — shape, dtype,
        # mode, and the precision policy (a bf16 re-profile must re-warm).
        warm_key = ("fwd", model, tuple(x.shape), str(x.dtype), training,
                    get_precision_mode())
        if warm_key not in self._warmed:
            run(record=False)
            self._warmed.add(warm_key)
        return run(record=True)

    def profile_backward(self, model: Sequential, params, state, x, grad_out, *,
                         training: bool = True, rng=None):
        """Per-layer backward timing via per-layer vjp (mirrors the
        reference's reverse loop timing, sequential.hpp:562-572)."""
        # forward pass saving per-layer inputs (compute-dtype path, like
        # Sequential.apply)
        h = cast_to_compute(x)
        inputs = []
        for i, layer in enumerate(model.layers):
            sub_rng = jax.random.fold_in(rng, i) if rng is not None else None
            inputs.append(h)
            h, _ = layer.apply(cast_to_compute(params[i]), state[i], h,
                               training=training, rng=sub_rng)
        def run(record: bool):
            g = grad_out.astype(h.dtype)
            for i in reversed(range(len(model.layers))):
                layer = model.layers[i]
                sub_rng = jax.random.fold_in(rng, i) if rng is not None else None

                def fwd(p, xin, _layer=layer, _i=i, _rng=sub_rng):
                    y, _ = _layer.apply(cast_to_compute(p), state[_i], xin,
                                        training=training, rng=_rng)
                    return y

                t0 = time.perf_counter()
                _, vjp = jax.vjp(fwd, params[i], inputs[i])
                gp, g = vjp(g)
                hard_fence(g)
                if record:
                    self.backward_us[layer.name] += (time.perf_counter() - t0) * 1e6
            return g

        warm_key = ("bwd", model, tuple(x.shape), str(x.dtype), training,
                    get_precision_mode())
        if warm_key not in self._warmed:
            run(record=False)
            self._warmed.add(warm_key)
        return run(record=True)

    def summary(self) -> str:
        """Printable table (reference ``print_profiling_summary``,
        sequential.hpp:323-418)."""
        names = list(self.forward_us.keys())
        for n in self.backward_us:
            if n not in names:
                names.append(n)
        lines = [f"{'layer':<28} {'fwd µs':>12} {'bwd µs':>12} {'calls':>7}"]
        tf = tb = 0.0
        for n in names:
            f, b = self.forward_us.get(n, 0.0), self.backward_us.get(n, 0.0)
            tf += f
            tb += b
            lines.append(f"{n:<28} {f:>12.1f} {b:>12.1f} {self.counts.get(n, 0):>7}")
        lines.append(f"{'TOTAL':<28} {tf:>12.1f} {tb:>12.1f}")
        return "\n".join(lines)


_trace_lock = threading.Lock()
_trace_active = False
_trace_seq = itertools.count()


def _try_claim() -> bool:
    """Test-and-set the one-capture-per-process flag."""
    global _trace_active
    with _trace_lock:
        if _trace_active:
            return False
        _trace_active = True
        return True


@contextlib.contextmanager
def _owned_capture(log_dir: str):
    """The capture body; assumes the claim is already held and releases
    it on exit (including the never-entered error paths)."""
    global _trace_active
    try:
        path = os.path.join(
            log_dir, f"{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}"
                     f"-{next(_trace_seq):03d}")
        os.makedirs(path, exist_ok=True)
        from ..obs import get_tracer
        with get_tracer().span("profiler.xprof", track="profiler",
                               log_dir=path):
            jax.profiler.start_trace(path)
            try:
                yield path
            finally:
                jax.profiler.stop_trace()
    finally:
        with _trace_lock:
            _trace_active = False


def trace(log_dir: str = "/tmp/dcnn_tpu_trace"):
    """XLA-level trace for xprof/tensorboard (the TPU-native answer to the
    reference's profiling commands, SURVEY.md §5.1).

    ``log_dir`` is the PARENT: every call captures into its own
    timestamped subdir (``<log_dir>/<YYYYmmdd-HHMMSS>-<pid>-<seq>``,
    yielded to the caller), so back-to-back traces never clobber each
    other's capture — the old single hard-coded dir made the second
    trace of a process overwrite the first. Nested use raises a clear
    ``RuntimeError`` up front: ``jax.profiler`` supports one capture per
    process, and the error it raises mid-capture is cryptic.

    The capture is also recorded as a ``profiler.xprof`` span on the
    shared tracer (``dcnn_tpu.obs``), so an xprof capture shows up on the
    span timeline and both can run together.
    """
    if not _try_claim():
        raise RuntimeError(
            "profiling.trace() does not nest: an xprof capture is "
            "already active in this process (jax.profiler supports one "
            "trace at a time); finish it before starting another")
    return _owned_capture(log_dir)


def try_trace(log_dir: str = "/tmp/dcnn_tpu_trace"):
    """Non-raising :func:`trace`: returns the capture context manager, or
    ``None`` when a capture is already active (counted on
    ``profiler_trace_busy_total``). The anomaly-capture path
    (``obs/anomaly.py``) uses this so an operator's manual trace always
    wins the race instead of one side crashing.

    The claim is taken HERE, not at ``__enter__`` — a non-None return
    means the capture slot is yours, so you must enter (and exit) the
    returned context manager to release it.
    """
    if _try_claim():
        return _owned_capture(log_dir)
    from ..obs import get_registry
    get_registry().counter(
        "profiler_trace_busy_total",
        "try_trace() calls that found a capture already active").inc()
    return None
