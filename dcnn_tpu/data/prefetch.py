"""Prefetching loader: overlap host batch prep + H2D transfer with device
compute.

Reference equivalent: the input-pipeline side of SURVEY.md §7 hard part 5.
The reference hides decode cost by decoding the whole dataset up front
(``tiny_imagenet_data_loader.hpp:26-132`` + stb_image) and then streams
host-resident batches into device memory synchronously with the train loop.
On TPU the idiomatic shape is a *bounded producer queue*: a background thread
walks the host loader, optionally applies a host-side transform, and
``jax.device_put``s each batch (optionally with a ``Sharding`` for
data-parallel meshes) so the H2D DMA for batch i+1 rides under the device
step for batch i. The train loop then never blocks on the host except at
epoch boundaries.

JAX's async dispatch makes the device side overlap for free; what this adds
is the *host* side (numpy slicing, augmentation, one-hot, transfer enqueue)
running ahead of the consumer — the part a Python-serial loop would
otherwise serialize with the step loop.

Usage::

    loader = PrefetchLoader(inner_loader, depth=2)   # or sharding=...
    for x, y in loader:          # x, y are device-resident
        ts, loss, _ = step(ts, x, y, rng, lr)
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterator, Optional, Tuple

import numpy as np

import jax

from ..obs import get_registry, get_tracer

_SENTINEL = object()
_PRODUCER_TRACK = "feed.producer"


def _stack(xs, ys):
    return np.stack(xs), np.stack(ys)


class _FeedMeter:
    """Where a fed batch's time goes, as spans (in a running profiler
    capture and, with tracing on, in the ring) and as always-on registry
    counters: ``feed.next`` / ``feed.transform`` / ``feed.stack`` (host
    preparation, ``feed_prep_seconds_total``), ``feed.put``
    (``feed_put_seconds_total``, ``feed_put_bytes_total``; the put's
    dispatch, not fenced), ``feed.blocked`` (the producer waiting on a full
    queue) and, on the consumer's side, ``feed.wait`` (the train loop
    waiting on an empty one). A counter is bumped once per span, so at one
    batch of 2048 images a step their cost does not show."""

    def __init__(self):
        self.tracer = get_tracer()
        reg = get_registry()
        self.batches = reg.counter(
            "feed_batches_total", "host batches handed to the prefetch queue")
        self.prep_s = reg.counter(
            "feed_prep_seconds_total",
            "producer seconds in the inner loader's next, the host "
            "transform and chunk stacking")
        self.put_bytes = reg.counter(
            "feed_put_bytes_total", "bytes the producer put to the device")
        self.put_s = reg.counter(
            "feed_put_seconds_total",
            "producer seconds in device_put and the device transform's "
            "dispatch (not fenced)")
        self.blocked_s = reg.counter(
            "feed_blocked_seconds_total",
            "producer seconds waiting on a full prefetch queue")
        self.wait_s = reg.counter(
            "feed_wait_seconds_total",
            "consumer seconds waiting on an empty prefetch queue")

    def prep(self, name: str, fn: Callable, *args):
        """``fn(*args)`` under the span ``name``, counted as preparation."""
        t0 = time.perf_counter()
        try:
            # callers pass the literals feed.next / feed.transform /
            # feed.stack (mapped in obs/goodput.SPAN_BUCKETS)
            with self.tracer.span(name, track=_PRODUCER_TRACK):  # dcnn: disable=GP01
                return fn(*args)
        finally:
            self.prep_s.inc(time.perf_counter() - t0)

    def put(self, device_put: Callable, q: queue.Queue, x, y,
            batches: int) -> None:
        """Put one item to the device and hand it to the queue."""
        nbytes = int(getattr(x, "nbytes", 0)) + int(getattr(y, "nbytes", 0))
        t0 = time.perf_counter()
        with self.tracer.span("feed.put", track=_PRODUCER_TRACK,
                              bytes=nbytes):
            dev = device_put(x, y)
        t1 = time.perf_counter()
        with self.tracer.span("feed.blocked", track=_PRODUCER_TRACK):
            q.put(dev)
        self.put_s.inc(t1 - t0)
        self.put_bytes.inc(nbytes)
        self.blocked_s.inc(time.perf_counter() - t1)
        self.batches.inc(batches)

    def get(self, q: queue.Queue):
        t0 = time.perf_counter()
        with self.tracer.span("feed.wait", track="train"):
            item = q.get()
        self.wait_s.inc(time.perf_counter() - t0)
        return item


class PrefetchLoader:
    """Wraps any ``BaseDataLoader``-style iterable of (x, y) numpy batches.

    ``depth`` bounds the number of in-flight device batches (2 is enough to
    hide host prep in steady state; more only grows HBM footprint).
    ``sharding`` (a ``jax.sharding.Sharding``) places each batch for a
    data-parallel mesh; default placement is the default device.
    ``transform(x, y) -> (x, y)`` runs on the producer thread (host-side
    augmentation hook mirroring the reference's per-batch augmentation).
    ``device_transform(x, y) -> (x, y)`` runs on the producer thread AFTER
    ``device_put`` — a (jitted) on-device function dispatched asynchronously,
    e.g. uint8→bf16 decode + normalize + one-hot. Shipping uint8 and casting
    on device cuts H2D bytes 4× vs fp32, which is the idiomatic TPU input
    recipe (and decisive on hosts where H2D bandwidth, not decode, bounds
    feed rate). When the inner loader declares a uint8 wire
    (``wire_dtype``/``scale``, the loader contract) and no
    ``device_transform`` is given, the default ``wire.decode_batch``
    transform is installed automatically: the put ships 1-byte pixels and
    the yielded x is already ``float32 * scale`` — labels untouched.
    ``stage_batches=K`` stacks K batches per transfer, yielding [K, B, ...]
    device arrays for ``train.make_multi_step`` — the remote-TPU-friendly
    feeding mode (one H2D sync per K steps). With a ``sharding``, note the
    stacked layout: data-parallel batch is axis 1, so use
    ``PartitionSpec(None, "data")``.
    ``transfer_engine`` (a ``data.transfer.TransferEngine``, caller-owned)
    routes each staged transfer through the chunked multi-stream H2D
    pipeline — several chunk copies in flight at once instead of one
    blocking put — and reassembles on device with a jitted concatenate, so
    the yielded arrays are bit-identical to the plain path. Ignored when a
    ``sharding`` is set (sharded placement stays one ``device_put``).
    ``feed_workers=N`` delegates the whole host side of the producer —
    row gather, optional ``worker_augment`` (a picklable
    ``AugmentationStrategy`` applied in float32 with per-(epoch, chunk)
    seeded rng), and collation into the staged [K, B, ...] layout — to a
    :class:`~dcnn_tpu.data.workers.FeedWorkerPool` of N worker processes
    producing into shared-memory ring slots; without ``worker_augment``
    the yielded batches are bit-identical to the serial producer. Requires
    a ``BaseDataLoader``-style inner (in-memory ``_x``/``_y`` arrays) with
    no entangled ``augmentation`` hook (its single sequential rng cannot
    be parallelized — move the recipe to ``worker_augment``); ``transform``
    is likewise producer-serial-only and mutually exclusive with the pool.
    ``worker_pool`` injects a caller-owned (possibly thread-backend) pool;
    ``close()`` releases an internally-created one (also invoked by
    ``with PrefetchLoader(...) as pf:``).
    """

    def __init__(self, inner, depth: int = 2,
                 sharding: Optional[Any] = None,
                 transform: Optional[Callable] = None,
                 device_transform: Optional[Callable] = None,
                 stage_batches: int = 1,
                 transfer_engine: Optional[Any] = None,
                 feed_workers: int = 0,
                 worker_augment: Optional[Callable] = None,
                 worker_pool: Optional[Any] = None):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        if stage_batches < 1:
            raise ValueError("stage_batches must be >= 1")
        if feed_workers < 0:
            raise ValueError("feed_workers must be >= 0")
        self.inner = inner
        self.depth = depth
        self.sharding = sharding
        self.transform = transform
        self.device_transform = device_transform
        self._auto_xform: Optional[Callable] = None
        self._auto_xform_ready = False
        self.stage_batches = stage_batches
        self.transfer_engine = transfer_engine
        self.feed_workers = feed_workers
        self.worker_augment = worker_augment
        self._pool = worker_pool
        self._own_pool = False
        if self._pooled and transform is not None:
            raise ValueError(
                "transform= runs on the serial producer thread and cannot "
                "compose with the worker pool — express it as a picklable "
                "worker_augment (AugmentationStrategy) instead")

    # passthroughs so PrefetchLoader is a drop-in for Trainer.fit
    @property
    def batch_size(self):
        return self.inner.batch_size

    @property
    def num_samples(self):
        return self.inner.num_samples

    def __len__(self):
        return len(self.inner)

    def shuffle(self, epoch: int) -> None:
        if hasattr(self.inner, "shuffle"):
            self.inner.shuffle(epoch)

    @property
    def wire_dtype(self):
        """What this loader actually ships over the H2D wire — the inner
        loader's wire dtype (the decode happens after the put here)."""
        return getattr(self.inner, "wire_dtype", None)

    @property
    def scale(self):
        return getattr(self.inner, "scale", 1.0)

    def _device_xform(self) -> Optional[Callable]:
        """The post-put transform: the explicit ``device_transform``, or —
        for a uint8-wire inner with none given — the cached default
        decode (lru-cached per scale; TS06 forbids a per-call closure)."""
        if self.device_transform is not None:
            return self.device_transform
        if not self._auto_xform_ready:
            wd = self.wire_dtype
            if wd is not None and np.dtype(wd) == np.uint8:
                from .wire import default_decode_transform
                self._auto_xform = default_decode_transform(
                    float(self.scale))
            self._auto_xform_ready = True
        return self._auto_xform

    # -- worker-pool delegation -------------------------------------------
    @property
    def _pooled(self) -> bool:
        return self.feed_workers > 0 or self._pool is not None

    def close(self) -> None:
        """Release an internally-created worker pool (workers + shared
        memory). Idempotent; a caller-provided ``worker_pool`` is the
        caller's to close."""
        if self._own_pool and self._pool is not None:
            self._pool.close()
            self._pool = None
            self._own_pool = False

    def __enter__(self) -> "PrefetchLoader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _ensure_pool(self):
        if self._pool is not None:
            return self._pool
        from .workers import FeedWorkerPool

        inner = self.inner
        if hasattr(inner, "_ensure_loaded"):
            inner._ensure_loaded()
        x = getattr(inner, "_x", None)
        y = getattr(inner, "_y", None)
        if x is None or y is None:
            raise ValueError(
                "feed_workers= needs a BaseDataLoader-style inner with "
                "in-memory arrays (the pool gathers rows itself); got "
                f"{type(inner).__name__}")
        self._pool = FeedWorkerPool(
            x, y, self.stage_batches * inner.batch_size,
            num_workers=self.feed_workers, augment=self.worker_augment,
            seed=getattr(inner, "seed", 0))
        self._own_pool = True
        return self._pool

    def _pool_plan(self):
        """Group the inner loader's batch plan (its own
        ``batch_indices()`` — the ONE definition of batch order, shared
        with ``__iter__``) into pool tasks that mirror the staged-chunk
        boundaries: full batches in groups of ``stage_batches``, a ragged
        tail batch on its own — so the pooled epoch yields the same chunk
        shapes and contents as the serial producer."""
        inner = self.inner
        if getattr(inner, "augmentation", None) is not None:
            raise ValueError(
                "the inner loader's augmentation hook draws from one "
                "sequential rng and cannot be parallelized bit-stably; "
                "move the recipe to worker_augment=")
        if not hasattr(inner, "batch_indices"):
            raise ValueError(
                "feed_workers= needs a BaseDataLoader-style inner exposing "
                "batch_indices() (the shared batch-order plan); got "
                f"{type(inner).__name__}")
        b = inner.batch_size
        sels, group = [], []
        for take in inner.batch_indices():
            if len(take) < b:       # ragged tail: its own chunk
                if group:
                    sels.append(np.concatenate(group))
                    group = []
                sels.append(np.asarray(take, np.int64))
                continue
            group.append(np.asarray(take, np.int64))
            if len(group) == self.stage_batches:
                sels.append(np.concatenate(group))
                group = []
        if group:
            sels.append(np.concatenate(group))
        return sels

    def _produce_pooled(self, q: queue.Queue, stop: threading.Event,
                        err: list, meter: _FeedMeter) -> None:
        from .workers import put_may_alias

        try:
            pool = self._ensure_pool()
            epoch = int(getattr(self.inner, "_epoch", 0))
            b = self.inner.batch_size
            it = pool.shards(self._pool_plan(), epoch=epoch)
            try:
                while True:
                    ps = meter.prep("feed.next", next, it, _SENTINEL)
                    if ps is _SENTINEL:
                        break
                    if stop.is_set():
                        return
                    xh, yh = ps.for_put()
                    if self.stage_batches > 1:
                        # collated view -> the staged [K, B, ...] layout
                        # (a reshape of the slot — no copy); a ragged tail
                        # ships as its own [1, B', ...] chunk
                        k = max(ps.rows // b, 1) if ps.rows % b == 0 else 1
                        xh = xh.reshape(k, ps.rows // k, *xh.shape[1:])
                        yh = yh.reshape(k, ps.rows // k, *yh.shape[1:])

                    def put_durable(x, y, ps=ps):
                        dev = self._device_put(x, y)
                        if ps.leased and not put_may_alias():
                            # the put copies from the recyclable slot (real
                            # H2D): make it durable before recycling. (On
                            # aliasing backends for_put() already detached.)
                            jax.block_until_ready(dev)
                        ps.release()
                        return dev
                    meter.put(put_durable, q, xh, yh,
                              max(-(-ps.rows // b), 1))
            finally:
                it.close()
        except BaseException as e:  # noqa: BLE001 - repropagated by caller
            err.append(e)
        finally:
            q.put(_SENTINEL)

    def _device_put(self, x, y):
        if self.sharding is not None:
            dx, dy = (jax.device_put(x, self.sharding),
                      jax.device_put(y, self.sharding))
        elif self.transfer_engine is not None:
            # chunked multi-stream transfer + on-device concat: same bytes,
            # pipelined wire. Labels are KB-scale — chunking them buys
            # nothing, ship plainly.
            dx, dy = self.transfer_engine.put_array(x), jax.device_put(y)
        else:
            dx, dy = jax.device_put(x), jax.device_put(y)
        xform = self._device_xform()
        if xform is not None:
            dx, dy = xform(dx, dy)
        return dx, dy

    def __iter__(self) -> Iterator[Tuple[jax.Array, jax.Array]]:
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        err: list = []
        stop = threading.Event()

        meter = _FeedMeter()

        def produce():
            try:
                it = iter(self.inner)
                # Chunked staging (stage_batches > 1): stack K host batches
                # and ship them as ONE [K, B, ...] transfer. Per-transfer
                # sync cost is paid once per K steps; the consumer runs the
                # chunk via train.make_multi_step (one dispatch) or slices
                # it on-device.
                xs, ys = [], []

                def flush():
                    x, y = meter.prep("feed.stack", _stack, xs, ys)
                    meter.put(self._device_put, q, x, y, len(xs))
                    xs.clear()
                    ys.clear()

                while True:
                    item = meter.prep("feed.next", next, it, _SENTINEL)
                    if item is _SENTINEL:
                        break
                    x, y = item
                    if stop.is_set():
                        return
                    if self.transform is not None:
                        x, y = meter.prep("feed.transform", self.transform,
                                          x, y)
                    if self.stage_batches == 1:
                        # device_put on the producer thread: enqueues the
                        # H2D copy immediately, so the DMA overlaps the
                        # consumer's current step instead of serializing
                        # with it
                        meter.put(self._device_put, q, x, y, 1)
                        continue
                    # a ragged batch (e.g. a drop_last=False tail smaller than
                    # batch_size) can't stack with the full ones: flush what's
                    # accumulated, then ship the odd batch as its own chunk
                    if xs and x.shape[0] != xs[0].shape[0]:
                        flush()
                    xs.append(x)
                    ys.append(y)
                    if len(xs) == self.stage_batches:
                        flush()
                if xs and not stop.is_set():
                    # trailing partial chunk: shipped with its own (smaller)
                    # leading dim — consumers jitting on chunk shape recompile
                    # once per distinct tail size
                    flush()
            except BaseException as e:  # noqa: BLE001 - repropagated below
                err.append(e)
            finally:
                q.put(_SENTINEL)

        if self._pooled:
            produce = lambda: self._produce_pooled(  # noqa: E731
                q, stop, err, meter)
        t = threading.Thread(target=produce, name="prefetch-producer",
                             daemon=True)
        t.start()
        try:
            while True:
                item = meter.get(q)
                if item is _SENTINEL:
                    break
                yield item
        finally:
            # If the consumer bailed early (break/exception), tell the
            # producer to quit at its next iteration, then drain until the
            # sentinel so its bounded put() can't deadlock.
            stop.set()
            while t.is_alive() or not q.empty():
                try:
                    if q.get(timeout=0.1) is _SENTINEL:
                        break
                except queue.Empty:
                    continue
            t.join()
        if err:
            raise err[0]
