"""Dynamic batcher: the online half of the serving stack.

Single requests arrive asynchronously; TPU throughput lives at large
batches. The classic reconciliation (Clipper NSDI'17; TF-Serving's batching
scheduler) is a **batching window**: hold the first request at most
``max_wait_ms``, group everything that arrives meanwhile up to
``max_batch``, run once, scatter results. This module implements that with

- a **bounded queue** (capacity in samples) — the load-shedding valve:
  beyond capacity, :meth:`DynamicBatcher.submit` raises
  :class:`QueueFullError` *immediately* instead of letting latency grow
  without bound (an overloaded server that queues forever serves nobody;
  one that sheds keeps its p99 for the traffic it accepts);
- a dispatcher thread that pops a batch when it is **due** — queue holds
  ``max_batch`` samples, or the oldest request has waited ``max_wait_ms``,
  or the batcher is draining — pads it to the engine's nearest bucket,
  runs the pre-compiled session, and resolves per-request futures;
- graceful teardown with a **no-orphan guarantee**: :meth:`drain` stops
  intake and completes everything already accepted; :meth:`shutdown`
  with ``drain=False`` fails still-queued requests with
  :class:`ShutdownError`; and a :meth:`drain` that trips its ``timeout``
  fails every still-pending future the same way before raising — a caller
  blocked on ``future.result()`` is *always* released, never left parked
  on a future nobody will resolve.

Determinism for tests: with ``start=False`` no thread runs and
:meth:`step` dispatches synchronously; combined with an injectable
``clock`` the whole submit → deadline → dispatch → latency pipeline is
exercised sleep-free (``tests/test_serve.py``). The threaded mode uses the
same ``_pop_due`` core, so the sleep-free tests cover the real dispatch
logic, not a test-only twin.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from typing import Callable, List, Optional

import numpy as np

from ..obs import get_registry, get_tracer
from ..obs.xla import sample_hbm
from .engine import InferenceEngine
from .metrics import ServeMetrics


class QueueFullError(RuntimeError):
    """Backpressure: the bounded request queue is at capacity."""


class DrainingError(RuntimeError):
    """Intake refused because the batcher is draining or shut down — a
    *typed* rejection, so a router can tell "replica temporarily not
    accepting (swap/drain in progress; fail over and maybe come back)"
    apart from a programming error. Subclasses ``RuntimeError`` so the
    pre-router contract (``submit`` raises ``RuntimeError`` after
    ``drain``/``shutdown``) is unchanged."""


class ShutdownError(RuntimeError):
    """The batcher shut down (or a timed drain gave up) before this
    request could be served. Raised from the request's future — never
    left forever-pending."""


class _Request:
    __slots__ = ("x", "n", "single", "future", "t_submit", "span")

    def __init__(self, x, n, single, future, t_submit, span=None):
        self.x, self.n, self.single = x, n, single
        self.future, self.t_submit = future, t_submit
        # cross-thread obs span: begun on the submitter thread, ended on
        # whichever thread dispatches (its length = queue+window residency)
        self.span = span


class DynamicBatcher:
    """Thread-safe request queue + batching dispatcher over an
    :class:`~dcnn_tpu.serve.engine.InferenceEngine`.

    ``max_wait_ms`` trades tail latency for occupancy: 0 dispatches
    whatever is queued the moment the dispatcher is free (lowest latency,
    small batches at low load); a few ms lets concurrent arrivals coalesce
    into fuller buckets. ``queue_capacity`` is in samples.
    """

    def __init__(self, engine: InferenceEngine, *,
                 max_batch: Optional[int] = None, max_wait_ms: float = 2.0,
                 queue_capacity: int = 128,
                 metrics: Optional[ServeMetrics] = None,
                 clock: Callable[[], float] = time.monotonic,
                 start: bool = True):
        if max_wait_ms < 0:
            raise ValueError(f"max_wait_ms must be >= 0, got {max_wait_ms}")
        if queue_capacity < 1:
            raise ValueError(f"queue_capacity must be >= 1, "
                             f"got {queue_capacity}")
        self.engine = engine
        self.max_batch = min(max_batch or engine.max_batch, engine.max_batch)
        self.max_wait_s = max_wait_ms / 1e3
        self.queue_capacity = queue_capacity
        self.metrics = metrics if metrics is not None else ServeMetrics(
            clock=clock)
        # open the slot-goodput clock: the dispatch slot exists (and is
        # idle) from construction, so occupied/idle/draining seconds sum
        # to the replica's lifetime (serve/metrics.py record_slot_state)
        self.metrics.record_slot_state("idle")
        self._clock = clock
        self._q: deque = deque()  # dcnn: guarded_by=_cond
        self._rows = 0  # dcnn: guarded_by=_cond
        # every accepted, not-yet-resolved future: the no-orphan guarantee's
        # ledger
        self._accepted: set = set()  # dcnn: guarded_by=_cond
        self._cond = threading.Condition()
        self._closing = False
        self._thread: Optional[threading.Thread] = None
        self._telemetry = None  # TelemetryServer from start_telemetry()
        self._tsdb = None  # TsdbSampler riding the telemetry lifecycle
        self._compile_mirrored = False  # engine compile counters copied
        # onto the scrape registry at most once
        if start:
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name=f"dcnn-serve-batcher-{engine.name}")
            self._thread.start()

    # -- intake --
    def submit(self, x) -> Future:
        """Enqueue one request — a single sample ``input_shape`` (future
        resolves to ``(classes,)`` logits) or a small batch
        ``(n, *input_shape)``, ``n <= max_batch`` (future resolves to
        ``(n, classes)``). Raises :class:`QueueFullError` when the queue is
        at capacity and ``RuntimeError`` after :meth:`drain`/
        :meth:`shutdown`."""
        x = np.asarray(x)
        shp = self.engine.input_shape
        single = x.shape == shp
        if single:
            x = x[None]
        if x.ndim != len(shp) + 1 or x.shape[1:] != shp:
            raise ValueError(f"expected {shp} or (n, *{shp}), "
                             f"got shape {x.shape}")
        n = x.shape[0]
        if not 1 <= n <= self.max_batch:
            raise ValueError(f"request batch {n} outside [1, "
                             f"{self.max_batch}]; chunk it or use "
                             f"engine.infer")
        fut: Future = Future()
        tracer = get_tracer()
        with self._cond:
            if self._closing:
                raise DrainingError("batcher is draining or shut down")
            if self._rows + n > self.queue_capacity:
                self.metrics.record_shed(n)
                tracer.instant("serve.shed", track="serve.queue", n=n)
                raise QueueFullError(
                    f"queue at capacity ({self._rows}/{self.queue_capacity}"
                    f" samples); request of {n} shed")
            self._q.append(_Request(
                x, n, single, fut, self._clock(),
                span=tracer.begin("serve.queue", track="serve.queue", n=n)))
            self._accepted.add(fut)
            self._rows += n
            self.metrics.record_submit(n)
            self.metrics.record_queue_depth(self._rows)
            self._cond.notify_all()
        return fut

    @property
    def queue_depth(self) -> int:
        with self._cond:
            return self._rows

    # -- telemetry (the per-replica scrape surface a router reads) ---------
    def health_reason(self) -> Optional[str]:
        """``None`` while this batcher can accept traffic; otherwise the
        machine-readable reason it can't. This is the ``/healthz``
        contract for the planned replica router (ROADMAP item 2): a
        draining or dead replica must fail health BEFORE requests error,
        so the router stops routing to it."""
        if self._closing:
            return "draining or shut down: not accepting requests"
        if self._thread is not None and not self._thread.is_alive():
            return "dispatcher thread dead"
        return None

    def start_telemetry(self, port: int = 0, host: str = "127.0.0.1"):
        """Expose THIS batcher over HTTP
        (:class:`~dcnn_tpu.obs.server.TelemetryServer`): ``/metrics`` is
        ``ServeMetrics.prometheus()`` (registry instruments + exact
        windowed percentile gauges), ``/healthz`` follows
        :meth:`health_reason`, ``/snapshot`` adds the live serve snapshot
        and engine compile/cost stats. ``port=0`` binds an ephemeral port
        (read ``.port`` back). The server survives :meth:`drain` — final
        stats stay scrapeable, with ``/healthz`` already 503 — and stops
        at :meth:`shutdown`. Calling it again replaces the previous
        server (stopped first — never a leaked bound port). Returns the
        started server."""
        from ..obs.server import TelemetryServer
        from ..obs.tsdb import TimeSeriesStore, TsdbSampler

        self._stop_telemetry()
        srv = TelemetryServer(registry=self.metrics.registry,
                              metrics_text=self.metrics.prometheus,
                              host=host, port=port)
        # merged-trace attribution + flight bundles on this replica's
        # own scrape surface (the recorder is a no-op until enabled)
        from ..obs.flight import get_flight_recorder
        srv.set_identity(component="replica", name=self.engine.name)
        srv.attach_flight(get_flight_recorder())
        # mirror the engine's per-sample cost gauges, HBM watermark, and
        # per-bucket compile accounting onto THIS scrape registry:
        # ServeMetrics' default registry is private, and the startup
        # allocation spike / roofline / compile-wall numbers must appear
        # on the surface the router actually reads. Counters are bumped
        # once (flag-guarded — a second start_telemetry must not
        # double-count).
        reg = self.metrics.registry
        # attribute-guarded: the batcher contract is duck-typed and a
        # custom engine (e.g. the soak's SyntheticEngine) has no cost
        # gauges or compile accounting to mirror — the scrape surface
        # must still come up
        if hasattr(self.engine, "_export_cost_gauges"):
            self.engine._export_cost_gauges(reg)
        sample_hbm(reg)
        compile_stats = getattr(self.engine, "compile_stats", None)
        if compile_stats and not self._compile_mirrored:
            self._compile_mirrored = True
            secs = sum(st.get("compile_s", 0.0)
                       for st in compile_stats.values())
            if reg is not get_registry():
                # the process-global registry has these two from the
                # compile listener (obs/xla.py); a private scrape registry
                # gets this engine's own
                reg.counter("compile_total",
                            "XLA executables compiled").inc(
                    len(compile_stats))
                reg.counter("compile_seconds_total",
                            "wall seconds spent compiling").inc(secs)
            if reg is not getattr(self.engine, "registry", None):
                reg.counter("compile_serve_seconds_total",
                            "wall seconds compiling serve "
                            "executables").inc(secs)
        srv.add_check("batcher", self.health_reason)
        srv.add_snapshot("serve", self.metrics.snapshot)
        srv.add_snapshot("engine", lambda: {
            "name": self.engine.name,
            "version": getattr(self.engine, "version", None),
            "buckets": self.engine.bucket_sizes,
            "batch_invariant": self.engine.batch_invariant,
            "compile_stats": getattr(self.engine, "compile_stats", {}),
        })
        # per-replica monitoring-plane history (obs/tsdb.py): THIS
        # surface's own /metrics text sampled at a cadence for as long
        # as it is up, so flight bundles carry the time-resolved serve
        # series — text (not registry) sampling, because the windowed
        # p99/shed-fraction gauges a postmortem wants exist only in
        # ServeMetrics' rendered exposition
        store = TimeSeriesStore()
        self._tsdb = TsdbSampler(
            store, registry=self.metrics.registry,
            text_fn=self.metrics.prometheus,
            interval_s=float(os.environ.get(
                "DCNN_TSDB_INTERVAL", "1.0"))).start()
        srv.add_snapshot("tsdb", store.summary)
        # flight bundles from this process now carry the pre-trigger
        # window (newest surface wins when several replicas share the
        # process-global recorder; detach below is identity-guarded)
        get_flight_recorder().attach_tsdb(store)
        self._telemetry = srv.start()
        return srv

    def _stop_telemetry(self) -> None:
        """Stop the scrape server AND its history sampler (idempotent —
        called from every shutdown path and on re-start)."""
        if self._tsdb is not None:
            from ..obs.flight import get_flight_recorder
            rec = get_flight_recorder()
            # detach only OUR store: another replica's attachment (it
            # started later, it wins) must survive this shutdown
            if getattr(rec, "_tsdb", None) is self._tsdb.store:
                rec.attach_tsdb(None)
            self._tsdb.stop()
            self._tsdb = None
        if self._telemetry is not None:
            self._telemetry.stop()
            self._telemetry = None

    # -- dispatch core (shared by the thread and the synchronous step) --
    def _pop_due(self, force: bool) -> List[_Request]:
        """Pop up to ``max_batch`` samples' worth of whole requests, but
        only if a dispatch is due — queue full enough, oldest request past
        its deadline, draining, or ``force``. Never splits a request."""
        with self._cond:
            if not self._q:
                return []
            due = (force or self._closing
                   or self._rows >= self.max_batch
                   or self._clock() >= self._q[0].t_submit + self.max_wait_s)
            if not due:
                return []
            tracer = get_tracer()
            batch, rows = [], 0
            while self._q and rows + self._q[0].n <= self.max_batch:
                req = self._q.popleft()
                self._rows -= req.n
                # canonical Future handoff: claims the request for this
                # batch, and drops one the caller cancelled while queued
                # (set_result on it would otherwise poison the scatter)
                if not req.future.set_running_or_notify_cancel():
                    tracer.end(req.span, cancelled=True)
                    self._accepted.discard(req.future)
                    continue
                tracer.end(req.span)  # queue residency: enqueue -> dispatch
                rows += req.n
                batch.append(req)
            self.metrics.record_queue_depth(self._rows)
            return batch

    def _run(self, batch: List[_Request]) -> None:
        tracer = get_tracer()
        self.metrics.record_slot_state("occupied")
        try:
            x = (batch[0].x if len(batch) == 1
                 else np.concatenate([r.x for r in batch]))
            rows = x.shape[0]
            # distributed-trace parentage: the dispatch covers every
            # request in the batch, and a batch may mix traces. A
            # single-trace batch parents the dispatch/infer spans under
            # that trace (the cross-process correlation the router soak
            # asserts); a mixed batch records the trace-id list instead
            # — one span cannot honestly claim several parents. Guarded
            # on `enabled` so the disabled dispatch path does zero
            # context work (the null spans carry no contexts anyway).
            parent, extra = None, {}
            if tracer.enabled:
                ctxs = [c for c in (r.span.context() if r.span is not None
                                    else None for r in batch) if c]
                tids = {c["trace_id"] for c in ctxs}
                parent = ctxs[0] if len(tids) == 1 else None
                if len(tids) > 1:
                    extra = {"trace_ids": sorted(tids)[:8]}
            with tracer.span("serve.dispatch", track="serve", parent=parent,
                             requests=len(batch), rows=rows,
                             **extra) as dspan:
                padded, _ = self.engine.pad_to_bucket(x)
                dspan.set(bucket=int(padded.shape[0]))
                # np.asarray materializes on host — a hard fence, so
                # recorded latency covers the full compute, and scatter is
                # cheap views; the infer span is therefore device-true
                with tracer.span("serve.infer", track="serve",
                                 bucket=int(padded.shape[0]), rows=rows):
                    y = np.asarray(self.engine.run_padded(padded))
            t_done = self._clock()
            off = 0
            for r in batch:
                try:
                    r.future.set_result(y[off] if r.single
                                        else y[off:off + r.n])
                    self.metrics.record_done(t_done - r.t_submit, r.n)
                except InvalidStateError:
                    pass  # failed by a timed-out drain racing this dispatch
                off += r.n
            self.metrics.record_batch(rows, padded.shape[0])
            # dispatch-boundary HBM watermark (obs/xla): latched no-op on
            # backends without memory stats, so the hot path stays clean
            sample_hbm(self.metrics.registry)
        except Exception as e:  # scatter the failure, don't kill the thread
            for r in batch:
                if not r.future.done():
                    try:
                        r.future.set_exception(e)
                    except InvalidStateError:
                        pass
        finally:
            with self._cond:
                for r in batch:
                    self._accepted.discard(r.future)
                closing = self._closing
            self.metrics.record_slot_state(
                "draining" if closing else "idle")

    def step(self, force: bool = True) -> int:
        """Synchronously dispatch one batch (``start=False`` mode and
        :meth:`drain`). ``force=False`` dispatches only if due — the hook
        the fake-clock deadline tests drive. Returns requests served."""
        batch = self._pop_due(force)
        if batch:
            self._run(batch)
        return len(batch)

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._q and not self._closing:
                    self._cond.wait()
                if not self._q:  # closing and fully drained
                    return
                # hold for the batching window: until full, the oldest
                # request's deadline, or drain (re-check the queue each
                # wakeup — a concurrent step() call may have emptied it)
                while (self._q and self._rows < self.max_batch
                       and not self._closing):
                    remaining = (self._q[0].t_submit + self.max_wait_s
                                 - self._clock())
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
            batch = self._pop_due(force=True)
            if batch:
                self._run(batch)

    # -- teardown --
    def _fail_pending(self, exc: Exception) -> int:
        """Resolve every still-pending accepted future with ``exc`` —
        the no-orphan guarantee's last resort. Safe against races with a
        dispatcher concurrently resolving the same futures (whoever sets
        first wins; the loser's ``InvalidStateError`` is absorbed).
        Returns how many futures this call actually failed."""
        with self._cond:
            queued = list(self._q)
            self._q.clear()
            self._rows = 0
            pending = set(self._accepted)
            self._accepted.clear()
            self.metrics.record_queue_depth(0)
        tracer = get_tracer()
        failed = 0
        for r in queued:
            tracer.end(r.span, failed=type(exc).__name__)
        for fut in pending:
            try:
                fut.set_exception(exc)
                failed += 1
            except InvalidStateError:
                pass  # resolved (or cancelled) while we swept
        return failed

    def drain(self, timeout: Optional[float] = None) -> None:
        """Stop accepting new requests; complete everything accepted.
        Threaded mode joins the dispatcher (it exits once empty);
        ``start=False`` mode dispatches the backlog inline. If ``timeout``
        trips, every still-pending future is failed with
        :class:`ShutdownError` (never orphaned) and ``TimeoutError``
        raises."""
        with self._cond:
            self._closing = True
            self._cond.notify_all()
        self.metrics.record_slot_state("draining")
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                n = self._fail_pending(ShutdownError(
                    f"drain timed out after {timeout}s with requests "
                    f"pending; the batcher is shutting down"))
                raise TimeoutError(
                    f"drain did not finish in {timeout}s "
                    f"({n} pending request(s) failed with ShutdownError)")
            self._thread = None
        else:
            while self.step(force=True):
                pass

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """``drain=True``: :meth:`drain`. ``drain=False``: reject further
        intake and fail queued requests — their futures raise
        :class:`ShutdownError` (a request someone is blocked on must
        resolve, not vanish with the batcher)."""
        if drain:
            try:
                self.drain(timeout)
            finally:
                # even an expired drain (TimeoutError) must release the
                # scrape port — a leaked server blocks the replica restart
                self._stop_telemetry()
            return
        exc = ShutdownError("batcher shut down without drain")
        with self._cond:
            self._closing = True
            # pop the backlog under the lock so the dispatcher can't drain
            # it; in-flight work (already popped) completes during join
            queued = list(self._q)
            self._q.clear()
            self._rows = 0
            for r in queued:
                self._accepted.discard(r.future)
            self.metrics.record_queue_depth(0)
            self._cond.notify_all()
        self.metrics.record_slot_state("draining")
        tracer = get_tracer()
        for r in queued:
            try:
                r.future.set_exception(exc)
            except InvalidStateError:
                pass  # caller cancelled it while queued
            tracer.end(r.span, failed="ShutdownError")
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        self._fail_pending(exc)  # sweep any remainder: no future orphaned
        self._stop_telemetry()

    def __enter__(self) -> "DynamicBatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=exc == (None, None, None))

    def __repr__(self) -> str:
        return (f"DynamicBatcher(engine={self.engine.name!r}, "
                f"max_batch={self.max_batch}, "
                f"max_wait_ms={self.max_wait_s * 1e3:g}, "
                f"capacity={self.queue_capacity})")
