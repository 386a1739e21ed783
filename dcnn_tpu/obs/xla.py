"""Compiled-executable introspection: XLA cost analysis, compile-event
accounting, HBM watermarks.

The headline MFU has so far been computed from the model's own
``forward_complexity() × 3`` analytic formula — an estimate of what the
model *should* cost, not what the compiled program *does* cost. XLA knows
the truth: every compiled executable carries a cost analysis (FLOPs and
bytes accessed, post-fusion/post-layout) and the runtime exposes per-device
HBM occupancy. This module is the thin, version-tolerant shim between
those APIs and the obs registry:

- :func:`executable_cost` / :func:`jit_cost` — normalized
  ``{flops, bytes_accessed, bytes_per_flop}`` from
  ``lowered.compile().cost_analysis()`` (which returns a list-of-dicts on
  some jax versions, a dict on others, and nothing on some backends —
  callers always see one dict or ``None``, never a version branch).
  ``bytes_per_flop`` is the roofline coordinate: against a chip's
  ``HBM GB/s ÷ peak FLOP/s`` ridge it says whether an executable is
  compute- or bandwidth-bound.
- :func:`install_compile_listener` — ``compile_total`` /
  ``compile_seconds_total`` and the persistent cache's hits, misses and
  load seconds, counted from JAX's own monitoring events, so that a plain
  ``jax.jit`` compile counts like a ``lower().compile()`` site's, and
  the seconds a jitted function spends on its way there
  (``compile_trace_seconds_total``, ``compile_lower_seconds_total``);
  :func:`compile_log` keeps the events with their time stamps.
- :func:`record_compile` — a compile site's own wall, as
  ``compile_<what>_seconds_total`` (bench's headline step, the serve
  engine's per-bucket sessions).
- :func:`sample_hbm` — HBM gauges from ``jax.Device.memory_stats()``
  (the ``utils/hardware.py`` path): ``hbm_bytes_in_use`` /
  ``hbm_bytes_limit`` summed over devices plus a monotone
  ``hbm_peak_bytes`` watermark. Cheap to call on epoch/dispatch
  boundaries; on backends without memory stats (CPU) the first failed
  probe latches and every later call is a no-op.

jax is imported lazily inside each function — the ``obs`` package stays
importable before backend selection, as its package docstring promises.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from .registry import MetricsRegistry, get_registry
from .tracer import get_tracer

# tri-state memory_stats support latch: None = unprobed, True/False after
# the first attempt — keeps per-dispatch sampling free on CPU backends
_HBM_SUPPORTED: Optional[bool] = None


def executable_cost(compiled: Any) -> Optional[Dict[str, float]]:
    """Normalized cost analysis of a compiled executable (the object
    ``jitted.lower(...).compile()`` returns). ``None`` when the backend
    exposes no analysis — callers must treat cost as optional."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    # jax has returned list-of-dicts (one per partition), a bare dict, and
    # None across versions; take the first partition's properties
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    by = ca.get("bytes accessed")
    if flops is None and by is None:
        return None
    out: Dict[str, float] = {}
    if flops is not None and flops > 0:
        out["flops"] = float(flops)
    if by is not None and by > 0:
        out["bytes_accessed"] = float(by)
    if "flops" in out and "bytes_accessed" in out:
        out["bytes_per_flop"] = out["bytes_accessed"] / out["flops"]
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["temp_bytes"] = float(mem.temp_size_in_bytes)
            out["argument_bytes"] = float(mem.argument_size_in_bytes)
            out["output_bytes"] = float(mem.output_size_in_bytes)
    except Exception:
        pass  # memory analysis is a bonus, never a requirement
    return out or None


def jit_cost(jitted: Any, *args, **kwargs) -> Optional[Dict[str, float]]:
    """Cost analysis of ``jitted`` at the avals of ``args``/``kwargs``
    (concrete arrays or ``jax.ShapeDtypeStruct`` specs — lowering never
    executes). With the persistent compile cache on, the ``.compile()``
    here is served from cache when the caller already compiled these
    shapes; on any failure (backend without lowering introspection, aval
    mismatch) the answer is ``None``, not an exception — cost telemetry
    must never break the measurement it describes."""
    try:
        compiled = jitted.lower(*args, **kwargs).compile()
    except Exception:
        return None
    return executable_cost(compiled)


# JAX's monitoring events (jax 0.9.0: ``_src/dispatch.py``,
# ``_src/compiler.py``, ``_src/compilation_cache.py``) -> the short name a
# ``compile_log`` entry carries. ``backend_compile`` is taken round
# ``compile_or_get_cached``, so it fires for a persistent-cache hit too and
# then already holds that hit's ``cache_load`` seconds. ``trace`` and
# ``lower`` are a jitted function's way to that compile: the jaxpr, then
# the MLIR module.
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
# one benchmark process logs about 1,400 entries, 1,000 of them the
# outermost traces of single operations run eagerly (PERF.md, PR 37)
_COMPILE_LOG_CAP = 8192
_compile_log: deque = deque(maxlen=_COMPILE_LOG_CAP)
_listener_lock = threading.Lock()
_listener_installed = False     # dcnn: guarded_by=_listener_lock


def _on_compile_event(event: str, seconds: float = 0.0, **_kw) -> None:
    what = _COMPILE_EVENTS.get(event)
    if what is None:
        return
    now, seconds = time.perf_counter(), float(seconds)
    reg = get_registry()
    if what in ("trace", "lower"):
        # a function traced inside another's trace fires first and lies
        # inside it (a model's epoch: thousands of them): the outermost
        # entry takes their place and the counter counts their seconds once
        nested = 0.0
        while True:
            try:
                last = _compile_log.pop()
            except IndexError:
                break
            if last[2] != what or last[0] - last[1] < now - seconds:
                _compile_log.append(last)
                break
            nested += last[1]
        _compile_log.append((now, seconds, what))
        reg.counter(f"compile_{what}_seconds_total",
                    "wall seconds tracing jitted functions to jaxprs "
                    "(trace) or lowering them to MLIR modules (lower), a "
                    "function inside another's counted once"
                    ).inc(max(seconds - nested, 0.0))
        return
    _compile_log.append((now, seconds, what))
    if what == "backend_compile":
        reg.counter("compile_total",
                    "XLA backend compiles, persistent-cache loads "
                    "included").inc()
        reg.counter("compile_seconds_total",
                    "wall seconds in XLA backend compiles, persistent-"
                    "cache loads included").inc(max(seconds, 0.0))
        get_tracer().instant("xla.compile", track="xla",
                             seconds=seconds)
    elif what == "cache_load":
        reg.counter("compile_cache_load_seconds_total",
                    "wall seconds loading executables from the persistent "
                    "compile cache").inc(max(seconds, 0.0))
    elif what == "cache_hit":
        reg.counter("compile_cache_hits_total",
                    "persistent compile cache hits").inc()
    else:
        reg.counter("compile_cache_misses_total",
                    "persistent compile cache misses (entries "
                    "written)").inc()


def install_compile_listener() -> None:
    """Count every XLA compile of this process from JAX's own monitoring
    events, on the process-global registry: ``compile_total`` /
    ``compile_seconds_total`` (one per backend compile: a plain ``jax.jit``
    as much as a ``lower().compile()`` site; a persistent-cache hit counts,
    with its load time), ``compile_cache_hits_total`` / ``compile_cache_misses_total`` /
    ``compile_cache_load_seconds_total``; and the seconds jitted functions
    spend being traced and lowered, ``compile_trace_seconds_total`` /
    ``compile_lower_seconds_total``. Each backend compile is also an
    ``xla.compile`` instant in the tracer's ring. Idempotent; called where
    the program first builds anything jitted."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_compile_event)
        monitoring.register_event_listener(_on_compile_event)
        _listener_installed = True


def compile_log() -> List[Tuple[float, float, str]]:
    """The newest 8,192 compile events the listener saw, oldest first:
    ``(time.perf_counter() stamp, seconds, event)`` with ``event`` one of
    ``trace``, ``lower``, ``backend_compile``, ``cache_load``,
    ``cache_hit``, ``cache_miss`` (the last two carry 0 seconds). A
    ``backend_compile`` that was a cache hit already holds its
    ``cache_load`` seconds: sum one kind, not both. An entry covers
    ``[stamp - seconds, stamp]``; a ``trace`` or ``lower`` entry that lay
    inside the next one of its kind has given way to it, and where two
    still overlap (two threads tracing at once) whoever sums takes the
    union of their intervals."""
    return list(_compile_log)


def record_compile(seconds: float, *, what: str = "",
                   registry: Optional[MetricsRegistry] = None) -> None:
    """A compile site's own wall: ``compile_<what>_seconds_total`` +=
    ``seconds`` (lowering, cache lookup and backend compile together, as
    the site timed them). ``compile_total`` / ``compile_seconds_total``
    are the listener's (:func:`install_compile_listener`), which sees these
    sites' compiles like any other."""
    if not what:
        return
    reg = registry if registry is not None else get_registry()
    reg.counter(f"compile_{what}_seconds_total",
                f"wall seconds compiling {what} executables").inc(
        max(seconds, 0.0))


def analytic_mfu(flops_per_sample: Optional[float],
                 samples_per_sec: Optional[float],
                 peak_tflops: Optional[float]) -> Optional[float]:
    """MFU from measured executable FLOPs: achieved FLOP/s over the chip
    peak. ``None`` whenever an input is unknown (no cost analysis, no
    known peak) — absent beats fabricated."""
    if not flops_per_sample or not samples_per_sec or not peak_tflops:
        return None
    return (flops_per_sample * samples_per_sec) / (peak_tflops * 1e12)


def sample_hbm(registry: Optional[MetricsRegistry] = None,
               devices=None) -> Optional[Dict[str, float]]:
    """Sample device memory into HBM gauges; returns the sample dict or
    ``None`` when the backend has no memory stats.

    - ``hbm_bytes_in_use`` / ``hbm_bytes_limit``: summed over devices
      (the fleet-level occupancy a scraper plots);
    - ``hbm_peak_bytes``: monotone high-water mark — the max per-device
      ``peak_bytes_in_use`` seen by ANY sample this process (falls back
      to tracking max ``bytes_in_use`` when the runtime reports no peak).
    """
    global _HBM_SUPPORTED
    if _HBM_SUPPORTED is False:
        return None
    try:
        import jax

        devs = devices if devices is not None else jax.devices()
        in_use = limit = 0.0
        peak = 0.0
        got = False
        for d in devs:
            stats = d.memory_stats()
            if not stats:
                continue
            got = True
            in_use += float(stats.get("bytes_in_use") or 0)
            limit += float(stats.get("bytes_limit") or 0)
            peak = max(peak, float(stats.get("peak_bytes_in_use")
                                   or stats.get("bytes_in_use") or 0))
        if not got:
            _HBM_SUPPORTED = False
            return None
    except Exception:
        _HBM_SUPPORTED = False
        return None
    _HBM_SUPPORTED = True
    reg = registry if registry is not None else get_registry()
    reg.gauge("hbm_bytes_in_use",
              "device memory in use, summed over devices").set(in_use)
    if limit:
        reg.gauge("hbm_bytes_limit",
                  "device memory capacity, summed over devices").set(limit)
    g = reg.gauge("hbm_peak_bytes",
                  "high-water per-device memory this process")
    if peak > g.value:
        g.set(peak)
    return {"hbm_bytes_in_use": in_use, "hbm_bytes_limit": limit or None,
            "hbm_peak_bytes": max(peak, g.value)}
