"""Serving subsystem tests (dcnn_tpu/serve/).

Contracts:

- engine: one pre-compiled warm session per bucket, pad-to-bucket exactness
  within a session, cross-bucket BIT-IDENTITY for int8 engines (integer
  accumulation is reduction-order-free), checkpoint/artifact constructors
  agree with the live model;
- batcher: output bit-identical to running each request alone through the
  engine (acceptance criterion — asserted on the int8 serving graph, where
  it holds across buckets by construction); backpressure sheds beyond
  queue capacity while accepted requests complete through drain();
- metrics: exact, sleep-free accounting under an injected fake clock.

Everything tier-1 here is sleep-free: deadline/latency logic is driven by
the fake clock through the synchronous ``step(force=False)`` path (the same
``_pop_due`` core the dispatcher thread runs), and threaded tests use
``max_wait_ms=0`` so dispatch is purely event-driven. The real-time
open-loop soak is marked ``slow``.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dcnn_tpu.nn import SequentialBuilder, export_inference
from dcnn_tpu.serve import (
    DynamicBatcher, InferenceEngine, QueueFullError, ServeMetrics,
    serve_buckets,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    """Injectable monotonic clock: tests advance it by hand, so latency
    and deadline assertions are exact equalities and nothing sleeps."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _tiny_model():
    return (SequentialBuilder(name="srv", data_format="NHWC")
            .input((8, 8, 3))
            .conv2d(4, 3, padding=1).batchnorm().activation("relu")
            .maxpool2d(2).flatten().dense(5)
            .build())


@pytest.fixture(scope="module")
def tiny():
    model = _tiny_model()
    params, state = model.init(jax.random.PRNGKey(0), model.input_shape)
    rng = np.random.default_rng(0)
    calib = jnp.asarray(rng.normal(size=(16, 8, 8, 3)).astype(np.float32))
    pool = rng.normal(size=(16, 8, 8, 3)).astype(np.float32)
    return model, params, state, calib, pool


@pytest.fixture(scope="module")
def int8_engine(tiny):
    model, params, state, calib, _ = tiny
    return InferenceEngine.from_model(model, params, state,
                                      int8_calib=calib, max_batch=8)


@pytest.fixture(scope="module")
def float_engine(tiny):
    model, params, state, _, _ = tiny
    return InferenceEngine.from_model(model, params, state, max_batch=8)


# ---------------------------------------------------------------- buckets

def test_serve_buckets():
    assert serve_buckets(1) == [1]
    assert serve_buckets(8) == [1, 2, 4, 8]
    assert serve_buckets(32) == [1, 2, 4, 8, 16, 32]
    # non-power-of-two cap becomes its own last bucket, not an over-pad
    assert serve_buckets(6) == [1, 2, 4, 6]
    with pytest.raises(ValueError):
        serve_buckets(0)


# ----------------------------------------------------------------- engine

def test_engine_precompiles_warm_sessions(float_engine):
    assert float_engine.bucket_sizes == [1, 2, 4, 8]
    assert sorted(float_engine.compile_stats) == [1, 2, 4, 8]
    for st in float_engine.compile_stats.values():
        assert st["compile_s"] >= 0 and st["warmup_s"] >= 0
    # run_padded accepts exactly the bucket shapes
    y = float_engine.run_padded(jnp.zeros((4, 8, 8, 3), jnp.float32))
    assert y.shape == (4, 5)
    with pytest.raises(ValueError, match="no session"):
        float_engine.run_padded(jnp.zeros((3, 8, 8, 3), jnp.float32))


def test_engine_bucket_math(float_engine):
    assert [float_engine.bucket_for(n) for n in (1, 2, 3, 5, 8)] == \
        [1, 2, 4, 8, 8]
    with pytest.raises(ValueError):
        float_engine.bucket_for(0)
    with pytest.raises(ValueError):
        float_engine.bucket_for(9)


def test_engine_infer_shapes_and_chunking(float_engine, tiny):
    *_, pool = tiny
    assert float_engine.infer(pool[0]).shape == (5,)       # single sample
    assert float_engine.infer(pool[:3]).shape == (3, 5)    # padded batch
    # beyond max_batch: chunked through the biggest bucket, rows preserved
    y = float_engine.infer(pool)  # 16 rows > max_batch 8
    assert y.shape == (16, 5)
    np.testing.assert_array_equal(np.asarray(y[:8]),
                                  np.asarray(float_engine.infer(pool[:8])))
    with pytest.raises(ValueError, match="trailing dims"):
        float_engine.infer(np.zeros((2, 4, 4, 3), np.float32))


def test_engine_padding_is_row_exact_within_bucket(float_engine, tiny):
    """Zero-pad rows ride along and are sliced off; the real rows are
    bit-identical to the same content unpadded at the same bucket."""
    *_, pool = tiny
    x5 = pool[:5]
    padded, n = float_engine.pad_to_bucket(x5)
    assert padded.shape == (8, 8, 8, 3) and n == 5
    full = np.zeros((8, 8, 8, 3), np.float32)
    full[:5] = x5
    np.testing.assert_array_equal(
        np.asarray(float_engine.run_padded(padded))[:5],
        np.asarray(float_engine.run_padded(jnp.asarray(full)))[:5])


def test_engine_int8_is_batch_invariant(int8_engine, tiny):
    """The int8 graph's cross-row-shape reductions are exact integer
    accumulations: a request's logits are bit-identical no matter which
    bucket served it. This is the property the batcher's bit-identity
    guarantee rests on."""
    *_, pool = tiny
    assert int8_engine.batch_invariant
    ref = np.asarray(int8_engine.infer(pool[:8]))
    for i in range(8):
        np.testing.assert_array_equal(
            np.asarray(int8_engine.infer(pool[i])), ref[i])


def test_engine_float_is_allclose_across_buckets(float_engine, tiny):
    """Float graphs are NOT promised bit-identity across buckets (XLA
    retiles fp32 reductions per shape) — only tight allclose. Documented
    here so the int8 guarantee above reads as the deliberate contrast."""
    *_, pool = tiny
    assert not float_engine.batch_invariant
    ref = np.asarray(float_engine.infer(pool[:8]))
    for i in range(8):
        np.testing.assert_allclose(np.asarray(float_engine.infer(pool[i])),
                                   ref[i], rtol=1e-5, atol=1e-5)


def test_engine_from_checkpoint(tiny, tmp_path):
    from dcnn_tpu.train.checkpoint import save_checkpoint

    model, params, state, _, pool = tiny
    save_checkpoint(str(tmp_path / "ck"), model, params, state)
    eng = InferenceEngine.from_checkpoint(str(tmp_path / "ck"), max_batch=4)
    ref = InferenceEngine.from_model(model, params, state, max_batch=4)
    np.testing.assert_array_equal(np.asarray(eng.infer(pool[:4])),
                                  np.asarray(ref.infer(pool[:4])))


def test_engine_from_artifact(tiny, float_engine):
    from dcnn_tpu.nn import fold_batchnorm

    model, params, state, _, pool = tiny
    fm, fp, fs = fold_batchnorm(model, params, state)
    blob = export_inference(fm, fp, fs)
    eng = InferenceEngine.from_artifact(blob, max_batch=8)
    assert eng.input_shape == (8, 8, 3)
    # same program, same backend, same bucket -> bit-identical to the
    # checkpoint-built engine
    np.testing.assert_array_equal(np.asarray(eng.infer(pool[:4])),
                                  np.asarray(float_engine.infer(pool[:4])))
    # pinned-batch artifacts can't serve buckets: explicit error
    pinned = export_inference(fm, fp, fs, batch_size=4)
    with pytest.raises(ValueError, match="batch-polymorphic"):
        InferenceEngine.from_artifact(pinned)


# ---------------------------------------------------------------- batcher

def test_batcher_bit_identical_to_engine_alone(int8_engine, tiny):
    """ACCEPTANCE: DynamicBatcher output is bit-identical to running each
    request alone through the engine. Asserted on the int8 engine — the
    serving graph of record — where batch-invariance makes it hold
    regardless of how requests were grouped into buckets."""
    *_, pool = tiny
    b = DynamicBatcher(int8_engine, max_batch=4, queue_capacity=64,
                       start=False)
    futs = [b.submit(pool[i]) for i in range(7)]  # batches of 4 + 3
    b.drain()
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(
            np.asarray(f.result(timeout=1)),
            np.asarray(int8_engine.infer(pool[i])))


def test_batcher_mixed_size_requests(int8_engine, tiny):
    *_, pool = tiny
    b = DynamicBatcher(int8_engine, max_batch=8, queue_capacity=64,
                       start=False)
    f2 = b.submit(pool[:2])
    f3 = b.submit(pool[2:5])
    f1 = b.submit(pool[5])
    b.drain()
    np.testing.assert_array_equal(np.asarray(f2.result(1)),
                                  np.asarray(int8_engine.infer(pool[:2])))
    np.testing.assert_array_equal(np.asarray(f3.result(1)),
                                  np.asarray(int8_engine.infer(pool[2:5])))
    np.testing.assert_array_equal(np.asarray(f1.result(1)),
                                  np.asarray(int8_engine.infer(pool[5])))
    assert f1.result(1).shape == (5,)  # single in, single out


def test_batcher_float_same_bucket_exact(float_engine, tiny):
    """A full batch through the batcher runs the same session as the same
    rows through engine.infer: bit-identical even for float. Singles run
    at bucket 1 instead, so only allclose is promised there."""
    *_, pool = tiny
    b = DynamicBatcher(float_engine, max_batch=4, queue_capacity=64,
                       start=False)
    futs = [b.submit(pool[i]) for i in range(4)]
    assert b.step() == 4  # one batch of 4 -> bucket 4
    got = np.stack([np.asarray(f.result(1)) for f in futs])
    np.testing.assert_array_equal(got,
                                  np.asarray(float_engine.infer(pool[:4])))
    for i in range(4):
        np.testing.assert_allclose(np.asarray(float_engine.infer(pool[i])),
                                   got[i], rtol=1e-5, atol=1e-5)


def test_batcher_backpressure_sheds_and_drain_completes(int8_engine, tiny):
    """ACCEPTANCE: requests beyond queue capacity are rejected
    (QueueFullError, counted as shed) while everything accepted completes
    through drain()."""
    *_, pool = tiny
    mets = ServeMetrics()
    b = DynamicBatcher(int8_engine, max_batch=4, queue_capacity=6,
                       metrics=mets, start=False)
    accepted = [b.submit(pool[i]) for i in range(6)]
    with pytest.raises(QueueFullError):
        b.submit(pool[6])
    with pytest.raises(QueueFullError):
        b.submit(pool[:2])  # batch requests shed identically
    assert b.queue_depth == 6
    b.drain()
    for i, f in enumerate(accepted):
        np.testing.assert_array_equal(
            np.asarray(f.result(timeout=1)),
            np.asarray(int8_engine.infer(pool[i])))
    snap = mets.snapshot()
    assert snap["requests_completed"] == 6
    assert snap["requests_shed"] == 3  # 1 single + 1 two-sample request
    assert snap["shed_fraction"] == pytest.approx(3 / 9)
    assert snap["queue_depth"] == 0
    # drained batcher refuses new work
    with pytest.raises(RuntimeError, match="draining or shut down"):
        b.submit(pool[0])


def test_batcher_deadline_batching_fake_clock(int8_engine, tiny):
    """The batching window, sleep-free: nothing dispatches before the
    oldest request's deadline or a full batch; latencies recorded from the
    injected clock are exact."""
    *_, pool = tiny
    fc = FakeClock()
    mets = ServeMetrics(clock=fc)
    b = DynamicBatcher(int8_engine, max_batch=4, max_wait_ms=10.0,
                       queue_capacity=64, metrics=mets, clock=fc,
                       start=False)
    f0 = b.submit(pool[0])              # t = 0, deadline t = 0.010
    assert b.step(force=False) == 0     # not due: not full, not expired
    fc.advance(0.004)
    f1 = b.submit(pool[1])              # t = 0.004
    assert b.step(force=False) == 0
    fc.advance(0.007)                   # t = 0.011 > deadline
    assert b.step(force=False) == 2     # one batch of 2 (bucket 2)
    assert f0.done() and f1.done()
    snap = mets.snapshot()
    # exact latencies through the fake clock: 11 ms and 7 ms
    assert snap["p99_ms"] == pytest.approx(11.0)
    assert snap["p50_ms"] == pytest.approx(11.0)  # nearest-rank of [7, 11]
    assert snap["mean_ms"] == pytest.approx(9.0)
    assert snap["batches"] == 1 and snap["batch_occupancy"] == 1.0
    # a full batch is due immediately, no deadline wait
    futs = [b.submit(pool[i]) for i in range(4)]
    assert b.step(force=False) == 4
    assert all(f.done() for f in futs)


def test_batcher_threaded_event_driven(int8_engine, tiny):
    """Dispatcher-thread mode: max_wait_ms=0 makes dispatch purely
    event-driven (no timed waits), so this runs sleep-free while proving
    the thread path end to end — results still bit-identical."""
    *_, pool = tiny
    b = DynamicBatcher(int8_engine, max_batch=8, max_wait_ms=0.0,
                       queue_capacity=256)
    futs = [b.submit(pool[i % 16]) for i in range(48)]
    got = [np.asarray(f.result(timeout=30)) for f in futs]
    b.shutdown()
    for i, y in enumerate(got):
        np.testing.assert_array_equal(
            y, np.asarray(int8_engine.infer(pool[i % 16])))
    snap = b.metrics.snapshot()
    assert snap["requests_completed"] == 48
    assert snap["requests_shed"] == 0
    assert snap["batches"] >= 1 and snap["p99_ms"] is not None


def test_batcher_thread_survives_concurrent_step(int8_engine, tiny):
    """Regression: a step() call emptying the queue while the dispatcher
    waits out the batching window must not kill the thread (the window
    loop re-checks the queue each wakeup). The batcher must keep serving
    afterwards."""
    *_, pool = tiny
    b = DynamicBatcher(int8_engine, max_batch=8, max_wait_ms=50.0,
                       queue_capacity=64)
    f0 = b.submit(pool[0])   # thread now holds it for the 50 ms window
    b.step(force=True)       # steal the queue out from under the wait
    np.testing.assert_array_equal(np.asarray(f0.result(timeout=5)),
                                  np.asarray(int8_engine.infer(pool[0])))
    f1 = b.submit(pool[1])   # dispatcher must still be alive to serve it
    np.testing.assert_array_equal(np.asarray(f1.result(timeout=5)),
                                  np.asarray(int8_engine.infer(pool[1])))
    b.shutdown()


def test_batcher_submit_validation(float_engine, tiny):
    *_, pool = tiny
    b = DynamicBatcher(float_engine, max_batch=4, start=False)
    with pytest.raises(ValueError, match="expected"):
        b.submit(np.zeros((4, 4, 3), np.float32))
    with pytest.raises(ValueError, match="outside"):
        b.submit(pool[:5])  # > max_batch must be chunked by the caller
    b.drain()


def test_batcher_scatter_failure_to_futures(float_engine, tiny,
                                            monkeypatch):
    """An engine failure resolves every grouped future with the exception
    instead of hanging callers or killing the dispatcher."""
    *_, pool = tiny
    b = DynamicBatcher(float_engine, max_batch=4, start=False)
    futs = [b.submit(pool[i]) for i in range(2)]
    monkeypatch.setattr(b.engine.__class__, "run_padded",
                        lambda self, x: (_ for _ in ()).throw(
                            RuntimeError("boom")), raising=True)
    assert b.step() == 2
    for f in futs:
        with pytest.raises(RuntimeError, match="boom"):
            f.result(timeout=1)


def test_batcher_user_cancel_while_queued(float_engine, tiny):
    """A future the caller cancels while queued is dropped at dispatch —
    the rest of its batch still completes normally."""
    *_, pool = tiny
    b = DynamicBatcher(float_engine, max_batch=4, start=False)
    f0 = b.submit(pool[0])
    f1 = b.submit(pool[1])
    assert f0.cancel()
    assert b.step() == 1  # only the live request is served
    assert f0.cancelled()
    np.testing.assert_allclose(np.asarray(f1.result(1)),
                               np.asarray(float_engine.infer(pool[1])),
                               rtol=1e-5, atol=1e-5)
    b.drain()


def test_batcher_shutdown_without_drain_fails_pending(float_engine, tiny):
    """shutdown(drain=False) must FAIL still-pending futures with
    ShutdownError — a caller blocked on result() is released with a clear
    error, never orphaned on a forever-pending future."""
    from dcnn_tpu.serve.batcher import ShutdownError

    *_, pool = tiny
    b = DynamicBatcher(float_engine, max_batch=4, start=False)
    futs = [b.submit(pool[i]) for i in range(3)]
    b.shutdown(drain=False)
    for f in futs:
        assert f.done() and not f.cancelled()
        with pytest.raises(ShutdownError):
            f.result(timeout=0)
    assert b.queue_depth == 0
    with pytest.raises(RuntimeError):
        b.submit(pool[0])


def test_batcher_drain_timeout_fails_pending_not_orphans(float_engine, tiny):
    """A drain(timeout=) that trips must release every still-pending
    future with ShutdownError — including one held by a dispatch stuck in
    a hung engine — then raise TimeoutError. No future is left
    forever-pending, and the late engine completion is absorbed."""
    import threading

    from dcnn_tpu.serve.batcher import ShutdownError

    *_, pool = tiny
    b = DynamicBatcher(float_engine, max_batch=2, max_wait_ms=0,
                       queue_capacity=8)
    gate = threading.Event()
    real_run = b.engine.run_padded

    def hung_run(padded):
        gate.wait(timeout=30)  # a wedged accelerator
        return real_run(padded)

    from types import SimpleNamespace
    b.engine = SimpleNamespace(  # shadow only what submit/_run touch
        run_padded=hung_run, pad_to_bucket=float_engine.pad_to_bucket,
        input_shape=float_engine.input_shape, name=float_engine.name,
        max_batch=float_engine.max_batch)

    f0 = b.submit(pool[0])          # dispatched, stuck in hung_run
    import time as _t
    for _ in range(100):            # wait for the dispatcher to pick it up
        if f0.running():
            break
        _t.sleep(0.01)
    f1 = b.submit(pool[1])          # still queued behind the hung dispatch
    with pytest.raises(TimeoutError):
        b.drain(timeout=0.2)
    for f in (f0, f1):
        assert f.done()
        with pytest.raises(ShutdownError):
            f.result(timeout=0)
    gate.set()                      # un-wedge: late set_result is absorbed
    b._thread.join(timeout=30)
    assert not b._thread.is_alive()


# ---------------------------------------------------------------- metrics

def test_metrics_fake_clock_exact():
    fc = FakeClock()
    m = ServeMetrics(clock=fc)
    for lat_ms in (1, 2, 3, 4, 5, 6, 7, 8, 9, 10):
        m.record_done(lat_ms / 1e3)
    m.record_submit(10)
    m.record_shed(2)
    m.record_batch(6, 8)
    m.record_queue_depth(3)
    fc.advance(2.0)
    s = m.snapshot()
    assert s["throughput_rps"] == pytest.approx(5.0)  # 10 done / 2 s
    assert s["p50_ms"] == pytest.approx(6.0)   # nearest-rank on 10 samples
    assert s["p95_ms"] == pytest.approx(10.0)
    assert s["p99_ms"] == pytest.approx(10.0)
    assert s["mean_ms"] == pytest.approx(5.5)
    assert s["batch_occupancy"] == pytest.approx(0.75)
    assert s["shed_fraction"] == pytest.approx(2 / 12)
    assert s["queue_depth"] == 3 and s["wall_s"] == pytest.approx(2.0)
    m.reset()
    s = m.snapshot()
    assert s["requests_completed"] == 0 and s["p50_ms"] is None
    assert s["throughput_rps"] is None  # no wall elapsed yet


def test_metrics_rolling_window():
    m = ServeMetrics(window=4)
    for lat_ms in (100, 100, 100, 1, 1, 1, 1):  # spike ages out
        m.record_done(lat_ms / 1e3)
    s = m.snapshot()
    assert s["p99_ms"] == pytest.approx(1.0)
    assert s["requests_completed"] == 7  # counters stay cumulative


def test_metrics_empty_snapshot_is_unambiguous():
    m = ServeMetrics(clock=FakeClock())
    s = m.snapshot()
    assert s["p50_ms"] is None and s["batch_occupancy"] is None
    assert s["requests_completed"] == 0 and s["shed_fraction"] == 0.0


# ------------------------------------------------- example / bench surface

def test_serve_snapshot_example_imports():
    """Import smoke for examples/serve_snapshot.py: the module must import
    (no main() execution) with the examples dir resolving its `common`,
    not benchmarks/common which other tests may have loaded first."""
    import importlib

    ex_dir = os.path.join(REPO, "examples")
    saved_common = sys.modules.pop("common", None)
    sys.path.insert(0, ex_dir)
    try:
        mod = importlib.import_module("serve_snapshot")
        assert callable(mod.main)
        assert callable(mod.run_open_loop)
    finally:
        sys.path.remove(ex_dir)
        sys.modules.pop("serve_snapshot", None)
        sys.modules.pop("common", None)
        if saved_common is not None:
            sys.modules["common"] = saved_common


def test_bench_serve_curve_structure(int8_engine, tiny):
    """bench.py's serving section over an injected tiny engine: the result
    block must carry >= 3 offered-load points with latency, throughput,
    occupancy, and shed keys (the BENCH_SERVE=1 acceptance shape). Runs
    with sub-second traffic windows."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import bench

    doc = bench.serve_section(None, engine=int8_engine,
                              loads=(200.0, 400.0, 800.0), seconds=0.25)
    assert doc["max_batch"] == int8_engine.max_batch
    assert len(doc["loads"]) >= 3
    for pt in doc["loads"]:
        assert set(pt) >= {"offered_rps", "achieved_rps", "p50_ms",
                           "p99_ms", "batch_occupancy", "shed_fraction"}
        assert pt["achieved_rps"] is None or pt["achieved_rps"] > 0


@pytest.mark.slow
def test_batcher_real_time_open_loop_soak(int8_engine, tiny):
    """Real-clock variant: open-loop arrivals with real sleeps, deadline
    waits exercised for real. Everything accepted must complete and the
    latency accounting must be populated."""
    from dcnn_tpu.serve import open_loop

    *_, pool = tiny
    b = DynamicBatcher(int8_engine, max_batch=8, max_wait_ms=2.0,
                       queue_capacity=64)
    futs = open_loop(b, pool, 400.0, 0.5)  # ~200 requests offered
    b.drain(timeout=30)
    for i, f in futs:
        np.testing.assert_array_equal(
            np.asarray(f.result(timeout=1)),
            np.asarray(int8_engine.infer(pool[i])))
    snap = b.metrics.snapshot()
    assert snap["requests_completed"] + snap["requests_shed"] >= len(futs)
    assert snap["requests_completed"] == len(futs)
    assert snap["p99_ms"] is not None and snap["throughput_rps"] > 0
