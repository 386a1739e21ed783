"""Unified observability subsystem tests (dcnn_tpu/obs/).

Contracts:

- registry: O(1) thread-safe recorders with EXACT totals under concurrent
  increments, get-or-create identity, snapshot dict + Prometheus text
  exposition (cumulative histogram buckets);
- tracer: no event lost, duplicated, or torn under many concurrent
  recording threads; exact timestamps/durations under an injected fake
  clock (sleep-free); bounded ring buffer evicting oldest-first; Chrome
  ``trace_event`` export that ``json.load`` accepts with labeled tracks;
  cross-thread begin/end spans; and a DISABLED hot path costing
  < 100 ns/span (the bound that makes always-on instrumentation of
  per-chunk/per-request paths acceptable);
- integrations: one enabled run over the real train / H2D-transfer /
  pipeline-stage / serve code paths lands all span families in ONE
  Chrome trace on their labeled tracks (the BENCH_OBS=1 acceptance shape
  in miniature);
- satellites: ``train.profiling.trace()`` unique-subdir + no-nesting
  contract; ``ServeMetrics`` Prometheus exposition over its registry
  backing.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax

from dcnn_tpu.obs import (MetricsRegistry,
                          configure, get_registry, get_tracer)
from dcnn_tpu.obs.tracer import Tracer, _NULL_SPAN

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture
def global_tracer_enabled():
    """Enable the process-global tracer for one test, restore the no-op
    state afterwards (other tests assert the disabled-path bound)."""
    t = configure(enabled=True)
    t.clear()
    yield t
    configure(enabled=False)
    t.clear()


# --------------------------------------------------------------- registry

def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("foo_total")
    c.inc()
    c.inc(3)
    assert c.value == 4 and isinstance(c.value, int)
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("depth")
    g.set(7)
    g.add(2)
    assert g.value == 9
    h = r.histogram("lat_seconds")
    for v in (1e-5, 1e-3, 0.5):
        h.observe(v)
    hv = h.value
    assert hv["count"] == 3
    assert hv["sum"] == pytest.approx(0.50101)
    assert hv["min"] == 1e-5 and hv["max"] == 0.5
    assert sum(hv["buckets"].values()) == 3  # all within bounds, no overflow
    assert hv["overflow"] == 0
    h.observe(1e9)  # beyond the last bound -> overflow bucket
    assert h.value["overflow"] == 1


def test_registry_get_or_create_identity_and_kind_collision():
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")
    # span-style dotted names map to the same prometheus-legal instrument
    assert r.counter("h2d.bytes") is r.counter("h2d_bytes")
    with pytest.raises(ValueError):
        r.gauge("a")  # registered as Counter
    with pytest.raises(ValueError):
        r.counter("0bad name!")
    with pytest.raises(ValueError):
        r.counter("latencia_µ")  # Unicode alnum, but not Prometheus-legal


def test_registry_concurrent_increments_exact():
    r = MetricsRegistry()
    c = r.counter("hits_total")
    h = r.histogram("obs_seconds")
    N, T = 5000, 8

    def work():
        for _ in range(N):
            c.inc()
            h.observe(1e-3)

    threads = [threading.Thread(target=work) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * T            # no lost increments
    assert h.value["count"] == N * T


def test_registry_snapshot_and_prometheus():
    fc = FakeClock()
    r = MetricsRegistry(clock=fc)
    r.counter("req_total", "requests").inc(5)
    r.gauge("depth").set(3)
    r.histogram("lat_seconds").observe(3e-6)
    fc.advance(2.0)
    s = r.snapshot()
    assert s["req_total"] == 5 and s["depth"] == 3
    assert s["lat_seconds"]["count"] == 1
    assert s["_wall_s"] == pytest.approx(2.0)
    json.dumps(s)  # machine-readable: the bench telemetry block embeds it

    text = r.prometheus()
    assert "# TYPE req_total counter" in text
    assert "# HELP req_total requests" in text
    assert "req_total 5" in text
    assert "# TYPE depth gauge" in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_count 1" in text
    # buckets are CUMULATIVE: every bound >= 4e-6 reports the observation
    assert 'lat_seconds_bucket{le="4e-06"} 1' in text
    assert 'lat_seconds_bucket{le="1e-06"} 0' in text


def test_registry_reset_keeps_instrument_identity():
    r = MetricsRegistry()
    c = r.counter("x_total")
    c.inc(9)
    r.reset()
    assert c.value == 0
    assert r.counter("x_total") is c
    c.inc()
    assert r.snapshot()["x_total"] == 1


# ----------------------------------------------------------------- tracer

_ID_KEYS = ("trace_id", "span_id", "parent_id")


def _user_args(ev):
    """Span args minus the distributed-tracing identity keys (PR 12:
    every recorded span carries trace_id/span_id[/parent_id])."""
    return {k: v for k, v in ev["args"].items() if k not in _ID_KEYS}


def test_tracer_fake_clock_exact():
    fc = FakeClock()
    t = Tracer(clock=fc, enabled=True)
    with t.span("a.work", track="x", k=1):
        fc.advance(0.25)
    fc.advance(1.0)
    with t.span("a.work", track="x"):
        fc.advance(0.5)
    evs = t.events()
    assert [e["name"] for e in evs] == ["a.work", "a.work"]
    assert evs[0]["ts_s"] == 0.0 and evs[0]["dur_s"] == 0.25
    assert evs[1]["ts_s"] == 1.25 and evs[1]["dur_s"] == 0.5
    assert evs[0]["track"] == "x"
    # user attrs intact; every span now also carries its trace identity
    assert _user_args(evs[0]) == {"k": 1}
    assert evs[0]["args"]["trace_id"] and evs[0]["args"]["span_id"]
    # the two spans are separate roots: distinct traces, no parent
    assert evs[0]["args"]["trace_id"] != evs[1]["args"]["trace_id"]
    assert "parent_id" not in evs[0]["args"]


def test_tracer_record_span_replay():
    """record_span replays externally-measured intervals (feed workers
    stamp phases in their own process; the parent lands them on per-worker
    tracks) — timestamps interpreted in the tracer's clock domain."""
    fc = FakeClock()
    t = Tracer(clock=fc, enabled=True)
    t.record_span("feed.gather", 1.0, 1.5, track="feed-w3", shard=2)
    t.record_span("feed.pack", 2.0, 2.0, track="feed-w3")
    evs = t.events()
    assert evs[0] == {"name": "feed.gather", "ts_s": 1.0, "dur_s": 0.5,
                      "track": "feed-w3", "args": {"shard": 2}}
    assert evs[1]["dur_s"] == 0.0
    # disabled tracer: the swapped-in null fn records nothing
    t.set_enabled(False)
    t.record_span("feed.gather", 3.0, 4.0, track="feed-w3")
    assert len(t.events()) == 2


def test_tracer_cross_thread_begin_end():
    fc = FakeClock()
    t = Tracer(clock=fc, enabled=True)
    h = t.begin("q.wait", track="queue", req=7)
    fc.advance(0.125)

    def closer():
        t.end(h, dispatched=True)

    th = threading.Thread(target=closer)
    th.start()
    th.join()
    (ev,) = t.events()
    assert ev["name"] == "q.wait" and ev["dur_s"] == 0.125
    # the event lands on the span's OWN track, not the closing thread's
    assert ev["track"] == "queue"
    assert _user_args(ev) == {"req": 7, "dispatched": True}


def test_tracer_concurrent_spans_none_lost_or_duplicated():
    t = Tracer(capacity=100_000, enabled=True)
    T, N = 8, 200

    def work(tid):
        for i in range(N):
            with t.span("w.op", track=f"t{tid}", tid=tid, i=i):
                pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(T)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    evs = t.events()
    assert len(evs) == T * N
    seen = {(e["args"]["tid"], e["args"]["i"]) for e in evs}
    assert len(seen) == T * N  # unique -> nothing duplicated, nothing torn


def test_tracer_ring_buffer_bounded_evicts_oldest():
    fc = FakeClock()
    t = Tracer(capacity=100, clock=fc, enabled=True)
    for i in range(250):
        with t.span("s", track="x", i=i):
            fc.advance(0.001)
    assert len(t) == 100
    kept = [e["args"]["i"] for e in t.events()]
    assert kept == list(range(150, 250))  # newest 100, oldest evicted


def test_tracer_instant_and_error_annotation():
    t = Tracer(enabled=True)
    t.instant("boom.mark", track="x", n=3)
    with pytest.raises(RuntimeError):
        with t.span("failing.op", track="x"):
            raise RuntimeError("nope")
    evs = t.events()
    assert evs[0]["dur_s"] is None and evs[0]["args"] == {"n": 3}
    assert evs[1]["args"]["error"] == "RuntimeError"  # span still recorded


def test_chrome_trace_schema(tmp_path):
    fc = FakeClock()
    t = Tracer(clock=fc, enabled=True)
    with t.span("a.x", track="alpha", k=1):
        fc.advance(0.002)
    t.instant("a.mark", track="beta")
    path = t.export_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)  # Perfetto's minimum bar: valid JSON object form
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    insts = [e for e in evs if e["ph"] == "i"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert len(xs) == 1 and len(insts) == 1
    assert xs[0]["name"] == "a.x" and xs[0]["dur"] == pytest.approx(2000.0)
    assert xs[0]["ts"] == pytest.approx(0.0)
    assert {"pid", "tid", "cat", "args"} <= set(xs[0])
    assert insts[0]["s"] == "t"
    # labeled tracks: one thread_name metadata record per distinct track,
    # tids consistent between metadata and events
    names = {m["args"]["name"]: m["tid"] for m in metas
             if m["name"] == "thread_name"}
    assert set(names) == {"alpha", "beta"}
    assert xs[0]["tid"] == names["alpha"]
    assert insts[0]["tid"] == names["beta"]
    assert any(m["name"] == "process_name" for m in metas)


def test_jsonl_export_round_trip(tmp_path):
    t = Tracer(enabled=True)
    for i in range(5):
        with t.span("s", track="x", i=i):
            pass
    path = t.export_jsonl(str(tmp_path / "t.jsonl"))
    with open(path) as f:
        lines = [json.loads(l) for l in f]
    # line 1 is the shard header (merge-CLI metadata); events follow
    assert "shard" in lines[0] and lines[0]["shard"]["pid"] == os.getpid()
    events = lines[1:]
    assert [l["args"]["i"] for l in events] == list(range(5))
    assert all(l["dur_s"] >= 0 for l in events)


def _net_cost(call, n=50_000, reps=25):
    """Seconds per ``call()``, net of loop overhead, min-of-reps with the
    GC off: a single CPython GC pass or a scheduler preemption inside one
    rep must not fail a bound — min-of-reps measures the uncontended cost,
    which is the quantity the contracts bound (robust to scheduler noise,
    though not to a uniformly much slower host)."""
    import gc

    def loop(f):
        t0 = time.perf_counter()
        for _ in range(n):
            f()
        return time.perf_counter() - t0

    def empty():
        pass

    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        return (min(loop(call) for _ in range(reps))
                - min(loop(empty) for _ in range(reps))) / n
    finally:
        if gc_was_enabled:
            gc.enable()


@pytest.fixture
def global_tracer_disabled():
    tracer = get_tracer()
    was_enabled = tracer.enabled
    configure(enabled=False)
    try:
        yield tracer
    finally:
        configure(enabled=was_enabled)


def test_disabled_tracer_records_nothing(global_tracer_disabled):
    tracer = global_tracer_disabled
    assert tracer.begin("y") is _NULL_SPAN
    assert tracer.instant("z") is _NULL_SPAN
    with tracer.span("x", k=1) as s:
        s.set(more=2)               # the span interface holds, ring off
        assert s.context() is None
    tracer.end(tracer.begin("y"))
    tracer.record_span("w", 0.0, 1.0)
    assert tracer.inject() is None
    assert len(tracer) == 0


@pytest.mark.parametrize("entry", ["begin", "end", "instant", "record_span"])
def test_disabled_ring_entry_is_cheap(global_tracer_disabled, entry):
    """THE hot-path bound: with the ring off, the ring-only entry points
    (per serve request, per pipeline microbatch, per replayed worker
    interval) cost < 100 ns (the absolute bound is this subsystem's
    acceptance contract, with ~2x margin on the tier-1 container)."""
    tracer = global_tracer_disabled
    call = {"begin": lambda: tracer.begin("x"),
            "end": lambda: tracer.end(_NULL_SPAN),
            "instant": lambda: tracer.instant("x"),
            "record_span": lambda: tracer.record_span("x", 0.0, 1.0)}[entry]
    net = _net_cost(call)
    assert net < 100e-9, f"disabled {entry} costs {net * 1e9:.0f} ns"


def test_disabled_span_costs_only_the_annotation(global_tracer_disabled):
    """``span()`` with the ring off and no profiler capture running still
    opens and closes the ``jax.profiler.TraceAnnotation`` that puts it on a
    capture's clock: a native no-op of about 0.5 us here, bounded at 2 us.
    The densest call sites (one span per H2D chunk, per served batch, per
    train step) each cover milliseconds of work."""
    import jax  # noqa: F401 - the mirror resolves only once jax is imported

    tracer = global_tracer_disabled

    def call():
        with tracer.span("x", batch=1):
            pass

    assert tracer.span("x") is not _NULL_SPAN
    net = _net_cost(call, n=20_000, reps=15)
    print(f"span() with the ring off: {net * 1e9:.0f} ns")
    assert net < 2e-6, f"disabled span costs {net * 1e9:.0f} ns"


def test_configure_preserves_identity_and_capacity():
    t = get_tracer()
    assert configure(enabled=True) is t  # in-place: hoisted refs stay wired
    try:
        t.clear()
        for i in range(20):
            with t.span("s", i=i):
                pass
        configure(capacity=10)
        assert len(t) == 10  # newest kept
        assert [e["args"]["i"] for e in t.events()] == list(range(10, 20))
    finally:
        configure(enabled=False, capacity=65536)
        t.clear()


# --------------------------------------------- profiling.trace() satellite

def test_profiling_trace_unique_subdirs(tmp_path):
    from dcnn_tpu.train.profiling import trace

    parent = str(tmp_path / "xprof")
    with trace(parent) as d1:
        pass
    with trace(parent) as d2:
        pass
    assert d1 != d2, "back-to-back traces must not clobber each other"
    assert os.path.dirname(d1) == parent and os.path.dirname(d2) == parent
    assert os.path.isdir(d1) and os.path.isdir(d2)


def test_profiling_trace_nested_raises(tmp_path):
    from dcnn_tpu.train.profiling import trace

    with trace(str(tmp_path / "a")):
        with pytest.raises(RuntimeError, match="does not nest"):
            with trace(str(tmp_path / "b")):
                pass
    # the guard must release on exit — a fresh trace works again
    with trace(str(tmp_path / "c")) as d:
        assert os.path.isdir(d)


def test_profiling_trace_emits_obs_span(tmp_path, global_tracer_enabled):
    from dcnn_tpu.train.profiling import trace

    with trace(str(tmp_path / "x")) as d:
        pass
    evs = [e for e in global_tracer_enabled.events()
           if e["name"] == "profiler.xprof"]
    assert len(evs) == 1 and evs[0]["args"]["log_dir"] == d


# ------------------------------------------ ServeMetrics registry backing

def test_serve_metrics_prometheus_exposition():
    from dcnn_tpu.serve import ServeMetrics

    fc = FakeClock()
    m = ServeMetrics(clock=fc)
    m.record_submit(4)
    m.record_shed(1)
    m.record_batch(3, 4)
    m.record_done(0.010, 3)
    fc.advance(1.0)
    text = m.prometheus()
    assert "# TYPE serve_samples_submitted_total counter" in text
    assert "serve_samples_submitted_total 4" in text
    assert "serve_samples_shed_total 1" in text
    assert "# TYPE serve_latency_seconds histogram" in text
    assert "serve_latency_seconds_count 1" in text
    assert "serve_latency_window_p50_ms 10.0" in text
    assert "serve_shed_fraction 0.2" in text
    # snapshot contract untouched by the registry backing
    s = m.snapshot()
    assert s["requests_submitted"] == 4 and s["requests_shed"] == 1
    assert s["p50_ms"] == pytest.approx(10.0)


def test_serve_metrics_shared_registry_injection():
    from dcnn_tpu.serve import ServeMetrics

    reg = MetricsRegistry()
    m = ServeMetrics(registry=reg)
    m.record_submit(2)
    assert reg.snapshot()["serve_samples_submitted_total"] == 2
    # constructing a SECOND instance on the shared registry must not zero
    # the live series (counters never go backwards by accident)
    m2 = ServeMetrics(registry=reg)
    assert reg.snapshot()["serve_samples_submitted_total"] == 2
    assert m2.snapshot()["requests_submitted"] == 0  # per-instance view
    m.reset()  # explicit reset DOES zero the shared series
    assert reg.snapshot()["serve_samples_submitted_total"] == 0


# ----------------------------------------------- end-to-end labeled trace

def test_end_to_end_trace_all_subsystems(tmp_path, global_tracer_enabled):
    """The BENCH_OBS=1 acceptance shape in miniature: training steps, H2D
    chunk puts, pipeline stage microbatches, and serve enqueue→infer all
    recorded by ONE enabled run, exported to ONE Chrome trace that
    json.load accepts, each family on its labeled track."""
    import jax.numpy as jnp

    from dcnn_tpu.data.transfer import TransferEngine
    from dcnn_tpu.nn import SequentialBuilder
    from dcnn_tpu.optim import Adam
    from dcnn_tpu.parallel.pipeline import PipelineStage
    from dcnn_tpu.serve import DynamicBatcher, InferenceEngine

    tr = global_tracer_enabled

    # 1) training steps: a 2-batch epoch through the real Trainer loop
    from dcnn_tpu.core.config import TrainingConfig
    from dcnn_tpu.data import SyntheticClassificationLoader
    from dcnn_tpu.train.trainer import Trainer, create_train_state

    model = (SequentialBuilder(name="obs_e2e", data_format="NHWC")
             .input((4, 4, 1)).flatten().dense(5).build())
    cfg = TrainingConfig(epochs=1, batch_size=16, progress_interval=0)
    loader = SyntheticClassificationLoader(32, (4, 4, 1), 5, batch_size=16,
                                           seed=0)
    loader.load_data()
    trainer = Trainer(model, Adam(1e-3), "softmax_crossentropy", cfg)
    ts = create_train_state(model, trainer.optimizer, jax.random.PRNGKey(0))
    ts = trainer.fit(ts, loader, None, epochs=1)  # donated: use the return

    # 2) H2D chunk transfers
    with TransferEngine(num_chunks=3, num_threads=2) as eng:
        x = np.arange(6 * 4, dtype=np.float32).reshape(6, 4)
        eng.put_shard(x, np.arange(6, dtype=np.int32))

    # 3) pipeline stage forward/backward on its own track
    stage = PipelineStage(0, model, Adam(1e-3))
    stage.initialize(jax.random.PRNGKey(1), model.input_shape)
    y = stage.forward(0, jnp.zeros((2, 4, 4, 1), jnp.float32))
    stage.backward(0, jnp.ones_like(y))

    # 4) serve: enqueue -> dispatch -> infer through the real batcher
    engine = InferenceEngine.from_model(model, ts.params, ts.state,
                                        fold=False, max_batch=2,
                                        name="obs_e2e")
    b = DynamicBatcher(engine, max_batch=2, start=False)
    f = b.submit(np.zeros((4, 4, 1), np.float32))
    assert b.step() == 1
    f.result(timeout=5)
    b.drain()

    path = tr.export_chrome(str(tmp_path / "e2e.json"))
    with open(path) as fh:
        doc = json.load(fh)
    evs = doc["traceEvents"]
    spans = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"train.epoch", "train.step", "h2d.gather", "h2d.put",
            "h2d.shard", "pipe.fwd", "pipe.bwd", "serve.queue",
            "serve.dispatch", "serve.infer",
            "serve.compile"} <= spans, spans
    tracks = {m["args"]["name"] for m in evs
              if m["ph"] == "M" and m["name"] == "thread_name"}
    assert {"train", "h2d", "stage0", "serve", "serve.queue"} <= tracks, tracks
    # registry rollups rode along
    snap = get_registry().snapshot()
    assert snap["h2d_bytes_total"] > 0
    assert snap["train_epochs_total"] >= 1


# ------------------------------------------------- example import smoke

def test_trace_training_example_imports():
    """Import smoke for examples/trace_training.py (same isolation dance as
    the serve_snapshot smoke: the examples dir must resolve its own
    `common`)."""
    import importlib

    ex_dir = os.path.join(REPO, "examples")
    saved_common = sys.modules.pop("common", None)
    sys.path.insert(0, ex_dir)
    try:
        mod = importlib.import_module("trace_training")
        assert callable(mod.main)
        assert callable(mod.train_traced)
        assert callable(mod.validate_chrome_trace)
    finally:
        sys.path.remove(ex_dir)
        sys.modules.pop("trace_training", None)
        sys.modules.pop("common", None)
        if saved_common is not None:
            sys.modules["common"] = saved_common
