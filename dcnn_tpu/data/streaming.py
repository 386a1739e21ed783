"""Streaming device feed for datasets larger than HBM.

Fills the gap between the two existing feed paths (VERDICT r3 missing #6):

- ``PrefetchLoader`` (host-driven, one H2D per batch): flexible but
  dispatch/transfer-bound.
- HBM-resident (``device_dataset.py``): one dispatch per epoch, zero
  steady-state H2D — but caps the dataset at device HBM.

Here the dataset lives in host RAM as uint8; it streams through HBM in
**shards** of K batches with double buffering: while shard *i* trains
(one fused dispatch: on-device shuffle → decode → augment → one-hot →
K train steps), shard *i+1* rides the chunked multi-stream transfer
engine (``data/transfer.py``) — C chunks gathered chunk-parallel and
shipped by a pool of transfer threads, several H2D copies in flight at
once. Shard buffers are donated to the dispatch, so steady-state HBM
holds ~2 shards regardless of dataset size. This is the TPU-native
analog of the reference's chunked batch loader feeding the accelerator
(``include/data_loading/data_loader.hpp:25-187`` prepare_batches +
to_device), with the transfer/compute overlap its threading provides.

Throughput law: epoch wall ≈ max(T_feed, T_compute) + one shard's
latency — NOT their sum; ``overlap_efficiency`` in the bench reports how
close the implementation gets.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import native
from ..obs import get_registry, get_tracer
from ..resilience import faults as _faults
from .transfer import TransferEngine
from .workers import FeedWorkerPool


def make_shard_step(model, loss_fn: Callable, optimizer, *, num_classes: int,
                    batch_size: int, shard_batches: int,
                    augment: Optional[Callable] = None,
                    scale: float = 1.0 / 255.0, num_microbatches: int = 1):
    """Build the per-shard train dispatch: ``step(ts, x_u8, y, rng, lr) ->
    (ts, mean_loss)`` where ``x_u8`` is (K*B, ...) uint8 ON DEVICE and the
    whole shard (shuffle → decode → augment → one-hot → K train steps) runs
    in one dispatch. Steady-state HBM is bounded at ~2 shards because the
    epoch loop drops its reference to each consumed shard (uint8 inputs
    cannot be donation targets — no output matches them); only the train
    state is donated."""
    from ..core.precision import get_compute_dtype
    from ..train.trainer import make_train_step
    from .device_dataset import make_batch_scan_body

    base = make_train_step(model, loss_fn, optimizer,
                           num_microbatches=num_microbatches, jit=False)
    cdt = get_compute_dtype()
    k, b = shard_batches, batch_size

    def step(ts, x_u8, y, rng, lr):
        if isinstance(x_u8, (tuple, list)):
            # chunk-tuple feed (transfer.TransferEngine reassemble="chunks"):
            # concatenating INSIDE the jitted step folds the reassembly into
            # the shard dispatch — no separate device-side copy pass. The
            # tuple arity is fixed per engine, so one executable serves
            # every shard.
            x_u8 = jnp.concatenate(x_u8, axis=0)
        if x_u8.shape[0] != k * b:
            raise ValueError(f"shard must hold exactly {k}x{b} samples, "
                             f"got {x_u8.shape[0]}")
        kperm, kstep = jax.random.split(rng)
        with jax.named_scope("shuffle"):
            idx = jax.random.permutation(kperm, k * b).reshape(k, b)
        lrs = jnp.broadcast_to(jnp.asarray(lr, jnp.float32), (k,))
        # the SAME scan body as the resident path (numerics parity)
        body = make_batch_scan_body(base, x_u8, y, num_classes=num_classes,
                                    scale=scale, cdt=cdt, augment=augment,
                                    kstep=kstep,
                                    sample_shape=model.input_shape)
        ts, losses = jax.lax.scan(body, ts, (idx, jnp.arange(k), lrs))
        return ts, jnp.mean(losses)

    return jax.jit(step, donate_argnums=(0,))


class StreamingDeviceDataset:
    """Host-RAM uint8 split streamed through HBM in double-buffered shards.

    ``shard_batches`` sets the shard size (K batches); the trailing
    remainder that doesn't fill a shard is folded into the epoch by
    re-sampling shard boundaries each epoch (host-side shard permutation →
    different samples are dropped each epoch, matching drop_last loader
    semantics shard-wise).

    ``workers``/``host_augment`` are the default knobs for the parallel
    host input pipeline (``data/workers.py``): epochs driven through
    :func:`train_streaming_epoch` then gather/augment/pack each shard on a
    ``workers``-process pool instead of the single producer thread.
    ``workers=0`` with a ``host_augment`` runs the same deterministic
    prepare serially (the bit-identity reference)."""

    def __init__(self, x: np.ndarray, y: np.ndarray, num_classes: int, *,
                 batch_size: int, shard_batches: int = 8, seed: int = 0,
                 workers: int = 0, host_augment=None):
        x = np.ascontiguousarray(x)
        y = np.asarray(y)
        if y.ndim == 2:
            y = y.argmax(axis=-1)
        if len(x) != len(y):
            raise ValueError(f"x/y length mismatch {len(x)} vs {len(y)}")
        self.x, self.y = x, y.astype(np.int32)
        self.num_classes = int(num_classes)
        self.batch_size = int(batch_size)
        self.shard_batches = int(shard_batches)
        self.shard_samples = self.batch_size * self.shard_batches
        if len(x) < self.shard_samples:
            raise ValueError(
                f"dataset ({len(x)}) smaller than one shard "
                f"({self.shard_samples}) — use DeviceDataset (resident) instead")
        self.num_shards = len(x) // self.shard_samples
        self.seed = int(seed)
        self.workers = int(workers)
        self.host_augment = host_augment
        self._rng = np.random.default_rng(seed)

    @property
    def steps_per_epoch(self) -> int:
        return self.num_shards * self.shard_batches

    def shard_selections(self):
        """Yield one sorted int64 row-selection per shard in a fresh random
        order; samples are globally permuted each epoch so shard membership
        and the dropped remainder rotate. The selection (not the gathered
        copy) is the unit the transfer engine consumes: each chunk task
        gathers its own row range, making the gather chunk-parallel."""
        perm = self._rng.permutation(len(self.x))
        for s in range(self.num_shards):
            sel = perm[s * self.shard_samples:(s + 1) * self.shard_samples]
            sel.sort()  # contiguous-ish gather: faster host copy
            yield sel.astype(np.int64, copy=False)

    def shards(self):
        """Yield (x_u8_shard, y_shard) host arrays (materialized). The
        gather runs through the native chunk-parallel row-memcpy kernel
        (``native.gather_rows``, bit-identical numpy fancy-index fallback
        when the toolchain is absent) instead of single-threaded numpy
        fancy indexing."""
        for sel in self.shard_selections():
            yield native.gather_rows(self.x, sel), native.gather_rows(
                self.y, sel)


def train_streaming_epoch(step, ts, dataset: StreamingDeviceDataset, rng,
                          lr: float, *,
                          timeline: Optional[List[dict]] = None,
                          engine: Optional[TransferEngine] = None,
                          workers: Optional[int] = None,
                          host_augment=None,
                          worker_pool: Optional[FeedWorkerPool] = None,
                          epoch: int = 0):
    """One epoch with a producer thread feeding a bounded queue: the host
    side of the feed runs on its own thread(s), so it overlaps the device
    compute the consumer loop dispatches.

    The feed itself is the chunked multi-stream **transfer engine**
    (``data/transfer.py``): each shard is split into C chunks, gathered
    (chunk-parallel native row memcpy) and shipped by a small pool of
    transfer threads so several H2D copies are in flight at once, then
    handed to ``make_shard_step`` as a chunk tuple (concatenated inside the
    shard dispatch; no device-side copy pass). numpy/native gathers and the PjRt
    host-to-device path all release the GIL, so the overlap is real even on
    one core. Queue depth 1 bounds steady-state HBM at ~3 shards (computing
    + queued + in-transfer).

    ``engine``: a configured :class:`~dcnn_tpu.data.transfer.TransferEngine`
    (caller-owned). Default: a private engine with 4 chunks x 2 transfer
    threads, closed when the epoch returns.
    ``TransferEngine(num_chunks=1, num_threads=1, reassemble="concat")``
    reproduces the r5 monolithic path exactly (the bit-identity reference
    in tests/test_transfer.py).

    ``workers`` routes the host side of the feed — gather, optional
    ``host_augment`` (an :class:`~dcnn_tpu.data.augment.AugmentationStrategy`
    run in float32, re-quantized to the uint8 wire), label prep, packing —
    through a :class:`~dcnn_tpu.data.workers.FeedWorkerPool` of that many
    worker processes writing preallocated shared-memory ring slots; the
    producer thread hands filled slots straight to the transfer engine.
    Default: the dataset's ``workers`` attribute (0 = the in-line serial
    path). Output batches are bit-identical for every worker count
    (per-(epoch, shard) seeded augmentation + ordered delivery).
    ``worker_pool`` passes a caller-owned pool (reused across epochs —
    workers and slots are start-once costs); otherwise a private pool is
    built and closed per call when ``workers > 0``. ``epoch`` seeds the
    per-shard augmentation rng derivation (pass the real epoch index for
    fresh augmentation draws each epoch).

    ``timeline``: pass a list to receive one dict per shard —
    ``{shard, gather_s, put_s, feed_wall_s, queue_wait_s, dispatch_s,
    put_done_t, dispatch_t, chunks, inflight_max, h2d_gbps, bytes}``.
    ``gather_s`` sums the per-chunk gather walls, ``put_s`` is the UNION of
    the put spans (overlapped transfers don't double-count), ``chunks``
    carries the raw per-chunk spans, ``inflight_max`` the peak number of
    concurrently in-flight chunk transfers, and ``h2d_gbps`` the effective
    rate over the union wall — the measurement surface for the overlap
    accounting in RESULTS.md.

    Returns (ts, mean_loss)."""
    t_epoch0 = time.perf_counter()
    if workers is None:
        workers = getattr(dataset, "workers", 0)
    if host_augment is None:
        host_augment = getattr(dataset, "host_augment", None)
    use_pool = worker_pool is not None or workers > 0 \
        or host_augment is not None
    # validate BEFORE creating any owned resource, so an early raise
    # can't leak a transfer-thread pool or worker processes
    if worker_pool is not None:
        if worker_pool.max_rows < dataset.shard_samples:
            raise ValueError(f"worker pool slots hold "
                             f"{worker_pool.max_rows} rows; the dataset's "
                             f"shards need {dataset.shard_samples}")
        pooled_workers = worker_pool.num_workers
    else:
        pooled_workers = workers
    if use_pool and pooled_workers > 0 and engine is not None \
            and not engine.fence:
        # a recycled slot must never be re-written while its bytes are
        # still on the wire; the fenced engine is what makes release safe
        raise ValueError("worker-pool feed requires a fenced "
                         "TransferEngine (fence=True)")
    own_engine = engine is None
    if own_engine:
        engine = TransferEngine(num_chunks=4, num_threads=2,
                                reassemble="chunks")
    own_pool = worker_pool is None and use_pool
    pool = worker_pool
    if own_pool:
        try:
            pool = FeedWorkerPool(dataset.x, dataset.y,
                                  dataset.shard_samples,
                                  num_workers=workers, augment=host_augment,
                                  seed=getattr(dataset, "seed", 0))
        except BaseException:
            if own_engine:
                engine.close()
            raise
    q: "queue.Queue" = queue.Queue(maxsize=1)
    stop = threading.Event()

    def put_or_stop(item) -> bool:
        # never park unconditionally in q.put: the consumer may have died
        # (step() raised) and set `stop` — re-check it every timeout tick so
        # the thread always exits and its staged HBM buffers get released
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def shard_plan():
        # prefer the selection iterator (chunk-parallel gather inside the
        # engine's pool tasks); fall back to materialized shards for
        # dataset-likes that only expose shards()
        if hasattr(dataset, "shard_selections"):
            for sel in dataset.shard_selections():
                yield dataset.x, dataset.y, sel
        else:
            for sx, sy in dataset.shards():
                yield sx, sy, None

    def produce_pooled():
        # worker-pool feed: the pool's workers gather/augment/pack each
        # shard into shared-memory slots; this thread only ships filled
        # slots (fenced — see the engine check above) and recycles them
        it = pool.shards(dataset.shard_selections(), epoch=epoch)
        try:
            for i, ps in enumerate(it):
                if stop.is_set():
                    return
                _faults.trip("stream.produce", shard=i)
                sx_h, sy_h = ps.for_put()
                sx, sy, stats = engine.put_shard(sx_h, sy_h, None,
                                                 t_base=t_epoch0)
                prep = ps.stats
                ps.release()  # bytes are on device (fenced) — recycle
                stats = dict(stats)
                stats["prep"] = {
                    "worker": prep.get("worker"),
                    "gather_s": prep["gather_s"],
                    "augment_s": prep["augment_s"],
                    "pack_s": prep["pack_s"],
                    "prep_s": prep["prep_s"],
                    "prep_t0": prep["gather_t0"] - t_epoch0,
                    "prep_t1": prep["pack_t1"] - t_epoch0,
                }
                if not put_or_stop(
                        (i, sx, sy, stats, time.perf_counter() - t_epoch0)):
                    return
        finally:
            it.close()  # reclaims in-flight slots if we bailed early

    def produce_serial():
        it = shard_plan()
        i = 0
        while not stop.is_set():
            nxt = next(it, None)
            if nxt is None:
                break
            # fault-injection point: an armed "stream.produce" raises
            # here at shard at=i, proving the sentinel path delivers
            # producer-thread failures to the training loop
            _faults.trip("stream.produce", shard=i)
            # per-chunk fencing happens on the engine's pool threads
            # (device_put returns at issue — without the fence the queue
            # would pace on issue time and the spans would not measure
            # the transfer); the consumer's dispatches still overlap the
            # whole shipment.
            sx, sy, stats = engine.put_shard(nxt[0], nxt[1], nxt[2],
                                             t_base=t_epoch0)
            if not put_or_stop(
                    (i, sx, sy, stats, time.perf_counter() - t_epoch0)):
                return
            i += 1

    def producer():
        # the terminating sentinel is (None | exception): a producer-side
        # failure (device_put OOM, a raising chunk task) must
        # reach the consumer as a re-raised exception, never as a silent
        # missing sentinel that would park q.get() forever
        err = None
        try:
            if pool is not None:
                produce_pooled()
            else:
                produce_serial()
        except BaseException as e:  # noqa: BLE001 — forwarded, not dropped
            err = e
        put_or_stop(err)

    worker = threading.Thread(target=producer, name="stream-feed",
                              daemon=True)
    worker.start()
    losses = []
    fed_bytes = 0
    try:
        while True:
            t3 = time.perf_counter()
            item = q.get()
            if item is None:
                break
            if isinstance(item, BaseException):
                raise item
            i, sx, sy, stats, put_done_t = item
            t4 = time.perf_counter()
            # dispatch span (async XLA: issue wall, not device compute —
            # the h2d.* spans from the engine's fenced pool threads carry
            # the device-true feed side)
            with get_tracer().span("train.shard_dispatch", track="train",
                                   shard=i):
                ts, loss = step(ts, sx, sy, jax.random.fold_in(rng, i), lr)
            t5 = time.perf_counter()
            losses.append(loss)
            fed_bytes += int(stats["bytes"])
            if timeline is not None:
                entry = {
                    "shard": i, "gather_s": stats["gather_s"],
                    "put_s": stats["put_s"],
                    "feed_wall_s": stats["wall_s"],
                    "queue_wait_s": t4 - t3, "dispatch_s": t5 - t4,
                    "put_done_t": put_done_t,
                    "dispatch_t": t5 - t_epoch0,
                    "chunks": stats["chunks"],
                    "inflight_max": stats["inflight_max"],
                    "h2d_gbps": stats["h2d_gbps"],
                    "bytes": stats["bytes"]}
                if "prep" in stats:
                    entry["prep"] = stats["prep"]
                timeline.append(entry)
    finally:
        stop.set()
        worker.join(timeout=60.0)
        if own_engine:
            engine.close()
        if own_pool:
            pool.close()
    # wire accounting: what actually crossed H2D this epoch, per image —
    # the uint8-first wire contract's headline series (docs/performance.md
    # §5; the regression gate tracks the bench mirror of this number).
    # Shards are uniform (shard_selections yields shard_samples rows each),
    # so images = consumed shards x shard_samples.
    fed_images = len(losses) * int(getattr(dataset, "shard_samples", 0))
    if fed_images:
        reg = get_registry()
        reg.gauge("feed_wire_bytes_per_image",
                  "bytes shipped host-to-device per image, last streaming "
                  "epoch").set(fed_bytes / fed_images)
        reg.gauge("feed_wire_epoch_bytes",
                  "total bytes shipped host-to-device, last streaming "
                  "epoch").set(float(fed_bytes))
        tr = get_tracer()
        if getattr(tr, "enabled", False):
            # epoch goodput ledger (obs/goodput.py): attribute this
            # epoch's wall to buckets from the spans recorded above —
            # the live "you are feed-bound" signal the ROADMAP's #1
            # wall lacked (gauges: goodput_fraction & friends)
            from ..obs.goodput import GoodputLedger
            GoodputLedger(tracer=tr, registry=reg).snapshot(
                t0_abs=t_epoch0, publish=True)
    # ONE on-device reduction + ONE readback instead of a float()
    # readback per loss
    mean = float(jnp.mean(jnp.stack(losses))) if losses else 0.0
    return ts, mean
