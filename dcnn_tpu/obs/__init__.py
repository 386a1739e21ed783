"""Unified observability: one metrics registry + one span tracer for the
whole framework.

Before this subsystem the repo had three disconnected measurement surfaces
— ``train/profiling.py`` (per-layer fenced µs tables), ``serve/metrics.py``
(rolling serving percentiles), and the hand-rolled ``streaming_timeline``
stats in ``bench.py`` / ``data/transfer.py`` — each speaking its own
format. ``dcnn_tpu.obs`` is the shared layer they now report through:

- :mod:`~dcnn_tpu.obs.registry` — thread-safe Counter / Gauge / Histogram
  (fixed log-spaced buckets), O(1) recorders, ``snapshot()`` dict export
  and Prometheus text exposition; :func:`get_registry` is the
  process-global instance.
- :mod:`~dcnn_tpu.obs.tracer` — structured span tracing
  (``span("h2d.put", chunk=i)`` context manager, explicit
  ``begin``/``end`` for cross-thread spans), bounded ring buffer,
  exporters to JSONL and Chrome ``trace_event`` JSON (Perfetto-loadable,
  labeled tracks); :func:`get_tracer` is the process-global instance —
  its ring is off (the ring-only entry points < 100 ns, asserted in
  tests) until enabled via :func:`configure` or ``DCNN_TRACE=1``; on or
  off, every ``span()`` also lies in a running ``jax.profiler`` capture
  as ``dcnn:<name>``, on the profiler's clock.

Instrumented out of the box: ``Trainer`` epochs/steps/eval,
``data/transfer.py`` per-chunk H2D gathers+puts, the host-driven pipeline
(one track per stage) and compiled-pipeline dispatches, and the serving
stack's enqueue → dispatch → infer decomposition. ``BENCH_OBS=1 python
bench.py`` writes the Chrome trace artifact and embeds a telemetry block
in the bench JSON. Workflow guide: ``docs/observability.md``.

Since PR 6 the package also carries the EXPORT half of observability —
the pieces that let the outside world see a process (docs/observability.md
"External scraping"):

- :mod:`~dcnn_tpu.obs.server` — :class:`TelemetryServer`: a stdlib
  threaded HTTP server exposing ``/metrics`` (Prometheus text),
  ``/healthz`` (200/503 liveness + resilience checks) and ``/snapshot``
  (JSON registry + recent spans); wired into ``Trainer``
  (``TrainingConfig.metrics_port``) and ``DynamicBatcher.start_telemetry``
  so a future replica router can scrape every replica.
- :mod:`~dcnn_tpu.obs.exposition` — the ONE Prometheus text renderer
  both ``MetricsRegistry.prometheus`` and ``ServeMetrics.prometheus``
  share.
- :mod:`~dcnn_tpu.obs.xla` — compiled-executable introspection: XLA
  ``cost_analysis`` FLOPs/bytes (analytic MFU + roofline byte/FLOP),
  the compile listener (``compile_total``/``compile_seconds_total`` and
  the persistent cache's hits and load seconds, from JAX's own events),
  HBM watermark gauges. (Imports jax lazily — this package stays importable first.)
- :mod:`~dcnn_tpu.obs.regress` — the BENCH_r*.json trajectory regression
  gate behind ``benchmarks/compare.py`` and bench.py's ``regressions``
  block.

PR 12 made the tracer DISTRIBUTED and failures self-documenting:

- every span carries ``trace_id``/``span_id``/``parent_id``;
  ``Tracer.inject``/``Tracer.activate`` are the propagation contract
  every framed hop uses (``parallel/comm.py`` ships the carrier as the
  ``_trace`` meta key), so a router request or an elastic
  reconfiguration is ONE trace across processes;
- :mod:`~dcnn_tpu.obs.trace` — ``python -m dcnn_tpu.obs.trace`` merges
  per-process JSONL shards into one Perfetto-loadable Chrome trace
  (handshake-measured clock offsets) and inspects flight bundles;
- :mod:`~dcnn_tpu.obs.flight` — :class:`FlightRecorder`: atomic keep-K
  postmortem bundles (spans + metrics + healthz reasons + offending
  config) dumped on degradation edges; :func:`get_flight_recorder` is
  the process-global instance, off until ``DCNN_FLIGHT_DIR`` /
  :func:`configure_flight`.

PR 15 grew the MONITORING PLANE on top (docs/observability.md
"Monitoring plane"): retained history, rule evaluation, and fleet-wide
aggregation —

- :mod:`~dcnn_tpu.obs.tsdb` — :class:`TimeSeriesStore`: fixed-memory
  per-series ring buffers + a downsampled coarse tier, a PromQL-style
  over-time query API (``rate``/``delta``/``*_over_time``/
  histogram-quantile), atomic ``history.jsonl`` persistence, and
  :class:`TsdbSampler` (a cadence thread over the registry; sleep-free
  by hand in tests). ``python -m dcnn_tpu.obs.tsdb`` is the postmortem
  CLI (``report``/``export``/ASCII ``plot``).
- :mod:`~dcnn_tpu.obs.rules` — :class:`RuleEngine`: declarative
  recording rules and threshold/rate/absence alert rules with ``for_s``
  hold windows (inactive → pending → firing → resolved); firing edges
  bump ``alerts_fired_total``, export ``alert_state{rule=...}``, dump
  ``alert_firing`` flight bundles with the offending series' window,
  and degrade ``/healthz`` via :func:`rules_check`.
- :mod:`~dcnn_tpu.obs.fleet` — :class:`FleetAggregator`: scrapes N
  telemetry surfaces (HTTP via :class:`HttpScraper` or in-process),
  merges them into labeled fleet series (per-replica + sum/max) in its
  own tsdb, and serves ``/fleet`` + ``/alerts`` + a fleet ``/healthz``
  roll-up; the serving ``Autoscaler`` reads its replica signals through
  one of these.

PR 37 measures the trainer loop from inside:

- :mod:`~dcnn_tpu.obs.hostlog` — the host's always-on logs in
  ``compile_log()``'s make: :func:`dispatch_log` (every resident epoch's
  call, return, fence and publish) and :func:`phase` / :func:`phase_log`
  (set-up's phases), each stamp ``time.perf_counter()``.

This package is stdlib-only at import time (no jax import) — safe to
import from any layer, including before backend selection.
"""

from .flight import FlightRecorder, configure_flight, get_flight_recorder
from .hostlog import Dispatch, dispatch_log, log_dispatch, phase, phase_log
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       get_registry)
from .server import (TelemetryServer, checkpoint_check, elastic_check,
                     pipeline_check, watchdog_check)
from .tracer import Tracer, configure, get_tracer

# monitoring-plane names resolve lazily (PEP 562): tsdb/rules/fleet stay
# runnable as `python -m dcnn_tpu.obs.tsdb` without runpy's
# already-imported warning, and the base import stays lean
_LAZY = {
    "TimeSeriesStore": "tsdb", "TsdbSampler": "tsdb",
    "RuleEngine": "rules", "AlertRule": "rules",
    "RecordingRule": "rules", "rules_check": "rules",
    "FleetAggregator": "fleet", "HttpScraper": "fleet",
}


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    import importlib
    return getattr(importlib.import_module(f".{mod}", __name__), name)


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "Tracer", "configure", "get_tracer",
    "Dispatch", "dispatch_log", "log_dispatch", "phase", "phase_log",
    "TelemetryServer", "watchdog_check", "checkpoint_check",
    "elastic_check", "pipeline_check",
    "FlightRecorder", "get_flight_recorder", "configure_flight",
    "TimeSeriesStore", "TsdbSampler",
    "RuleEngine", "AlertRule", "RecordingRule", "rules_check",
    "FleetAggregator", "HttpScraper",
]
