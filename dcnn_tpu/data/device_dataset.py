"""HBM-resident dataset: stage once, train epochs with zero steady-state H2D.

Reference equivalent: the Tiny-ImageNet loader's decode-everything-up-front
strategy (``include/data_loading/tiny_imagenet_data_loader.hpp:26-132``
decodes the whole split into host RAM once, then every epoch is pure memory
traffic). TPU-native redesign: the decoded split is staged into **HBM** once
as uint8 (Tiny-ImageNet train ≈ 1.2 GB — comfortably resident on a 16 GB
v5e), and everything the host loader used to do per batch — shuffle, batch
gather, uint8→float decode, augmentation, one-hot — happens **on device,
inside the jitted train step**:

- shuffle: ``jax.random.permutation`` over sample indices, once per epoch;
- batching: the permutation reshaped to [steps, B] feeds a ``lax.scan`` —
  each step gathers its B rows straight from the resident uint8 array and
  reshapes them back to the sample shape;
- decode: cast to the precision-policy compute dtype and scale (1/255);
- augmentation: jittable ops from ``augment_device`` (flip/crop/cutout/…);
- labels: kept as int32, one-hot materialised per batch on device.

The whole epoch is ONE device dispatch. Steady-state H2D is a PRNG key and
the lr per epoch — nothing else crosses the host boundary, so feed
efficiency is ~1.0 by construction (measured in ``bench.py``).

Validation runs the same way: the split + int labels stay resident; full
batches scan on device and a statically-shaped remainder batch completes the
split exactly (no padding rows, so any mean-reducing loss is exact).

**The staged form** (:func:`lane_dense`). A split is staged as
``[N, D/128, 128]`` — a free host-side view of the sample's ``D`` elements —
whenever ``D`` is a multiple of 128, and sample-shaped otherwise. Why: the
TPU runtime keeps a 4-D uint8 array ``[N, 3, 64, 64]`` batch-minor (layout
``{0,3,2,1}``), and a row gather wants it row-major, so every epoch
dispatch began by transposing the whole 1.23 GB split into a 2.46 GB
temporary (row-major, the 64-wide minor dimension padded to 128 lanes:
15 ms a dispatch on the v5e, PERF.md PR 26). In the lane-dense view the
runtime's layout is already row-major with no padding — a sample is whole
4-KB tiles, contiguous — so the epoch program reads it as staged. The scan
body, the eval and the profiler reshape what they read back to the model's
``input_shape``; the epoch and eval functions accept either form.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import get_registry, get_tracer
from ..ops.losses import upcast_logits


def lane_dense(x: np.ndarray) -> np.ndarray:
    """The view a split is staged in: ``[N, D/128, 128]`` when a sample's
    ``D`` elements fill whole 128-lane rows (and it has more than one
    axis to fold), else ``x`` itself. See the module docstring for why."""
    d = int(np.prod(x.shape[1:]))
    if x.ndim > 2 and d % 128 == 0:
        return x.reshape(len(x), d // 128, 128)
    return x


def as_samples(rows, sample_shape):
    """Rows read from a staged split, back in the sample shape (a no-op for
    a split staged sample-shaped, or when the model's shape is unknown)."""
    if sample_shape is None:
        return rows
    return rows.reshape(rows.shape[:1] + tuple(sample_shape))


class DeviceDataset:
    """A classification split staged into device memory once.

    Args:
      x: [N, ...] images, uint8 (preferred: 4× smaller than fp32 in HBM) or
         float. Layout must already match the model's data_format.
      y: [N] integer class labels.
      num_classes: one-hot width.
      batch_size: per-step batch; an epoch runs ``N // batch_size`` steps
         (remainder handled by the shuffled permutation — every sample is
         seen with equal probability across epochs, like the reference's
         drop_last batching).
      augment: optional ``DeviceAugment`` applied after decode, per batch.
      scale: decode multiplier (default 1/255 for uint8 inputs, 1 for float).
      transfer_engine: optional ``data.transfer.TransferEngine``
         (caller-owned) for the one-time staging put — the multi-GB initial
         H2D is chunked across the engine's transfer threads (pipelined
         wire, same bytes on device) instead of one blocking ``device_put``.
         The reassembly transiently needs ~2x the split in HBM (chunks +
         concatenated output); for splits near HBM capacity stage plainly.
    """

    def __init__(self, x: np.ndarray, y: np.ndarray, num_classes: int, *,
                 batch_size: int, augment: Optional[Callable] = None,
                 scale: Optional[float] = None, transfer_engine=None):
        x = np.asarray(x)
        y = np.asarray(y)
        if y.ndim == 2:  # accept one-hot and collapse: labels live as int32
            y = y.argmax(axis=-1)
        if len(x) != len(y):
            raise ValueError(f"x/y length mismatch: {len(x)} vs {len(y)}")
        self._describe(x, num_classes, batch_size, augment, scale)
        # staged once, lane-dense (module docstring); uint8 stays uint8 in
        # HBM (decode happens in-step). ``x_staged`` is what the epoch and
        # eval functions take; ``x`` is the sample-shaped view of it.
        # Labels are KB-scale — chunking them buys nothing, ship plainly.
        self.y = jax.device_put(y.astype(np.int32))
        self.x_staged = _stage(lane_dense(x), transfer_engine)

    def _describe(self, x: np.ndarray, num_classes: int, batch_size: int,
                  augment: Optional[Callable], scale: Optional[float]) -> None:
        """What the epoch and eval functions read of a split beside its
        staged arrays; the one place a kind of split sets it."""
        if batch_size > len(x):
            raise ValueError(f"batch_size {batch_size} > dataset {len(x)}")
        self.num_classes = int(num_classes)
        self.batch_size = int(batch_size)
        self.augment = augment
        self.scale = float(scale if scale is not None
                           else (1.0 / 255.0 if x.dtype == np.uint8 else 1.0))
        self.num_samples = len(x)
        self.sample_shape = x.shape[1:]

    @property
    def steps_per_epoch(self) -> int:
        return self.num_samples // self.batch_size

    def __len__(self) -> int:
        """Batches per epoch — loader-compatible (schedulers size per-batch
        cycles with len(train_loader))."""
        return self.steps_per_epoch

    @property
    def x(self) -> jax.Array:
        """The split in its sample shape ``[N, *sample_shape]``. Where that
        is not the staged form this is a device-side reshape (a copy of the
        split): for inspection, not for the hot path."""
        return as_samples(self.x_staged, self.sample_shape)

    @property
    def hbm_bytes(self) -> int:
        return self.x_staged.nbytes + self.y.nbytes

    # Pandas-free convenience for building from a host loader's arrays.
    @classmethod
    def from_loader(cls, loader, num_classes: int, *, batch_size=None,
                    augment=None) -> "DeviceDataset":
        """Stage a host ``BaseDataLoader``'s arrays (loader must be loaded;
        one-hot y is collapsed back to int labels).

        The host loader's numpy ``augmentation`` hook cannot run on device
        and is NOT carried over — rebuild the recipe with
        ``DeviceAugmentBuilder`` and pass it as ``augment=`` (a warning fires
        if one would otherwise be dropped silently)."""
        loader._ensure_loaded()
        if getattr(loader, "augmentation", None) is not None and augment is None:
            import warnings
            warnings.warn(
                "from_loader: the host loader's numpy augmentation hook does "
                "not transfer to device — rebuild it with DeviceAugmentBuilder "
                "and pass augment=, or training will run unaugmented",
                stacklevel=2)
        return cls(loader._x, loader._y, num_classes,
                   batch_size=batch_size or loader.batch_size,
                   augment=augment)


class TokenDataset(DeviceDataset):
    """A token split staged into device memory once: ``[N, S + 1]`` int32
    ids, each row one training sequence with its next-token labels (step
    input ``row[:-1]``, labels ``row[1:]``). The Trainer routes it as it does
    a ``DeviceDataset`` (one dispatch an epoch, shuffled on the device);
    there is no label array, which is how the scan body knows a token split
    from an image split.

    ``batch_size`` counts sequences. ``vocab_size`` is the width of the
    model's output (``num_classes`` to the epoch-function cache)."""

    def __init__(self, tokens: np.ndarray, vocab_size: int, *, batch_size: int):
        tokens = np.asarray(tokens)
        if tokens.ndim != 2 or tokens.shape[1] < 2:
            raise ValueError(f"tokens must be [N, S + 1], got {tokens.shape}")
        if not np.issubdtype(tokens.dtype, np.integer):
            raise ValueError(f"token ids must be integers, got {tokens.dtype}")
        self._describe(tokens, vocab_size, batch_size, None, 1.0)
        self.y = None
        self.x_staged = _stage(tokens.astype(np.int32, copy=False))

    @property
    def seq_len(self) -> int:
        return self.sample_shape[0] - 1

    @property
    def hbm_bytes(self) -> int:
        return self.x_staged.nbytes


def _stage(x: np.ndarray, transfer_engine=None) -> jax.Array:
    """One split's array onto the device, fenced, under the ``data.stage``
    span and the ``data_stage_*`` counters (the copy's own time: the first
    dispatch would wait for it anyway)."""
    t0 = time.perf_counter()
    with get_tracer().span(
            "data.stage", track="data", bytes=int(x.nbytes),
            engine="transfer" if transfer_engine is not None else "put"):
        staged = (transfer_engine.put_array(x) if transfer_engine is not None
                  else jax.device_put(x))
        staged.block_until_ready()
    reg = get_registry()
    reg.counter("data_stage_bytes_total",
                "bytes of resident splits staged into device "
                "memory").inc(int(x.nbytes))
    reg.counter("data_stage_seconds_total",
                "wall seconds staging resident splits, to the staged "
                "array's fence").inc(time.perf_counter() - t0)
    return staged


def token_batch(rows):
    """A gathered token batch ``[B, S + 1]`` as (inputs, next-token labels)."""
    return rows[:, :-1], rows[:, 1:]


def _decode(x, scale, compute_dtype):
    cdt = compute_dtype or jnp.float32
    return x.astype(cdt) * jnp.asarray(scale, cdt)


def make_batch_scan_body(base, x_all, y_all, *, num_classes, scale, cdt,
                         augment, kstep, sample_shape):
    """The gather → decode → augment → one-hot → train-step scan body, as
    ONE definition shared by the resident (this module) and streaming
    (``data/streaming.py``) feed paths — cross-path numerics parity
    (per-step rng fold-in, the 0x0A6 augment-key offset, decode scaling)
    depends on these staying identical. ``scan_in`` = (batch_indices,
    step_index, lr). With ``y_all`` None the split is a token split
    (:class:`TokenDataset`) and the batch is :func:`token_batch` of the
    gathered rows. Otherwise ``x_all`` is sample-shaped or lane-dense
    (:func:`lane_dense`); the gathered rows are reshaped to
    ``sample_shape`` (the model's ``input_shape``)."""
    def body(carry, scan_in):
        bidx, i, lr_i = scan_in
        key = jax.random.fold_in(kstep, i)
        with jax.named_scope("data"):
            if y_all is None:         # a TokenDataset: ids in, ids as labels
                xb, yb = token_batch(x_all[bidx])
            else:
                xb = _decode(as_samples(x_all[bidx], sample_shape), scale, cdt)
                if augment is not None:
                    xb = augment(xb, jax.random.fold_in(key, 0x0A6))
                yb = jax.nn.one_hot(y_all[bidx], num_classes, dtype=jnp.float32)
        new_ts, loss, _ = base(carry, xb, yb, key, lr_i)
        return new_ts, loss
    return body


def make_resident_epoch(model, loss_fn: Callable, optimizer, *,
                        num_classes: int, batch_size: int,
                        augment: Optional[Callable] = None,
                        scale: float = 1.0 / 255.0,
                        steps: Optional[int] = None,
                        num_microbatches: int = 1):
    """Build the one-dispatch-per-epoch train function.

    Returns jitted ``epoch(ts, x_all, y_all, rng, lr) -> (ts, mean_loss)``:
    shuffles on device, then ``lax.scan``s a full train step (gather → decode
    → augment → one-hot → fwd/bwd/update) over every batch. Per-step
    semantics are identical to the host loop (per-batch BN stats, per-batch
    optimizer updates, per-step folded rng); ``lr`` may be a scalar or a
    [steps] vector so per-batch LR schedules stay exact (mirrors
    ``train.make_multi_step``).
    """
    from ..core.precision import get_compute_dtype
    from ..train.trainer import make_train_step

    base = make_train_step(model, loss_fn, optimizer,
                           num_microbatches=num_microbatches, jit=False)
    cdt = get_compute_dtype()

    def epoch(ts, x_all, y_all, rng, lr):
        n = x_all.shape[0]
        if n < batch_size:
            raise ValueError(
                f"resident epoch needs at least one batch: split has {n} "
                f"samples < batch_size {batch_size}")
        k = steps if steps is not None else n // batch_size
        kperm, kstep = jax.random.split(rng)
        # with steps > n//batch_size (multi-epoch dispatch), tile extra
        # permutations so every index stays in range and coverage stays even
        need = k * batch_size
        reps = -(-need // n)  # ceil
        with jax.named_scope("shuffle"):
            perm = jnp.concatenate([
                jax.random.permutation(jax.random.fold_in(kperm, r), n)
                for r in range(reps)])
            idx = perm[:need].reshape(k, batch_size)
        lrs = jnp.broadcast_to(jnp.asarray(lr, jnp.float32), (k,))
        body = make_batch_scan_body(base, x_all, y_all,
                                    num_classes=num_classes, scale=scale,
                                    cdt=cdt, augment=augment, kstep=kstep,
                                    sample_shape=model.input_shape)
        ts, losses = jax.lax.scan(body, ts, (idx, jnp.arange(k), lrs))
        return ts, jnp.mean(losses)

    return jax.jit(epoch, donate_argnums=(0,))


def make_resident_eval(model, loss_fn: Callable, *, num_classes: int,
                       batch_size: int):
    """Build the one-dispatch eval: ``evaluate(params, state, x_all, y_all)
    -> (loss_sum, correct, n_valid)`` over the whole resident split.

    The split runs as ``n // B`` full batches under a ``lax.scan`` plus one
    statically-shaped remainder batch — no padding rows, so the result is
    exact for ANY mean-reducing loss (CE family, MSE, custom), not just the
    zero-target CE trick (review r3 finding #2). ``loss_sum / n`` is the
    sample-weighted mean, matching ``evaluate_classification`` over a host
    loader with ``drop_last=False``.
    """
    from ..core.precision import get_compute_dtype

    cdt = get_compute_dtype()

    def batch_metrics(params, state, xb_raw, yb, scale):
        xb = _decode(as_samples(xb_raw, model.input_shape), scale, cdt)
        logits, _ = model.apply(params, state, xb, training=False)
        logits = upcast_logits(logits)
        onehot = jax.nn.one_hot(yb, num_classes, dtype=jnp.float32)
        loss = loss_fn(logits, onehot)
        hit = jnp.sum(jnp.argmax(logits, axis=-1) == yb)
        return loss, hit

    def evaluate(params, state, x_all, y_all, scale=1.0 / 255.0):
        n = x_all.shape[0]
        k, rem = divmod(n, batch_size)
        loss_sum = jnp.zeros((), jnp.float32)
        correct = jnp.zeros((), jnp.int32)
        if k:
            xs = x_all[:k * batch_size].reshape(k, batch_size, *x_all.shape[1:])
            ys = y_all[:k * batch_size].reshape(k, batch_size)

            def body(carry, xy):
                ls, c = carry
                loss, hit = batch_metrics(params, state, xy[0], xy[1], scale)
                return (ls + loss * batch_size, c + hit), None

            (loss_sum, correct), _ = jax.lax.scan(
                body, (loss_sum, correct), (xs, ys))
        if rem:
            loss, hit = batch_metrics(params, state, x_all[k * batch_size:],
                                      y_all[k * batch_size:], scale)
            loss_sum = loss_sum + loss * rem
            correct = correct + hit
        return loss_sum, correct, n

    return jax.jit(evaluate)


def make_resident_epoch_dp(model, loss_fn: Callable, optimizer, *,
                           num_classes: int, batch_size: int, mesh,
                           augment: Optional[Callable] = None,
                           scale: float = 1.0 / 255.0,
                           num_microbatches: int = 1):
    """Data-parallel resident epochs: the dataset lives SHARDED across the
    mesh's ``data`` axis (each device holds ``N/D`` samples in its HBM), and
    one dispatch runs the whole epoch on every device — local shuffle +
    gather + decode + augment per shard, gradient ``pmean`` over ICI, and a
    replicated optimizer update.

    This is the distributed-sampler pattern (each rank permutes its own
    partition per epoch) fused into the device program: zero steady-state
    H2D *and* zero per-step host involvement across the whole mesh. The
    aggregate dataset capacity scales with the mesh (D × per-chip HBM) —
    Tiny-ImageNet-scale splits stay resident on a single v5e-8.

    ``batch_size`` is GLOBAL (must divide by mesh data size; each device
    computes batch_size/D samples per step). BN semantics: running stats are
    computed per shard and pmean-averaged each step — the same
    class of approximation as the reference's per-microbatch BN
    (SURVEY.md §7 hard part 4), where normalization uses sub-batch
    statistics. Loss/grad scaling is exact (equal shards → pmean of local
    means is the global mean).

    Returns jitted ``epoch(ts, x_shard, y_shard, rng, lr) -> (ts, loss)``
    where x_shard/y_shard are sharded [N, ...]/[N] arrays (use
    :func:`stage_sharded`). ``ts`` is replicated.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from ..core.mesh import DATA_AXIS
    from ..core.precision import get_compute_dtype
    from ..train.trainer import make_train_step

    d = mesh.shape[DATA_AXIS]
    if batch_size % d != 0:
        raise ValueError(f"global batch {batch_size} % data size {d} != 0")
    local_batch = batch_size // d
    cdt = get_compute_dtype()
    # the canonical train step with in-body pmean (grads/loss/state) — the
    # DP epoch shares every fwd/bwd/update detail with the single-device path
    base = make_train_step(model, loss_fn, optimizer, jit=False,
                           num_microbatches=num_microbatches,
                           reduce_axis=DATA_AXIS)

    def per_device(ts, x_local, y_local, rng, lr):
        n_local = x_local.shape[0]
        k = n_local // local_batch
        if k == 0:
            raise ValueError(
                f"resident DP epoch needs at least one local batch: shard "
                f"has {n_local} samples < local batch {local_batch} "
                f"(global {batch_size} over {d} devices)")
        dev = jax.lax.axis_index(DATA_AXIS)
        kperm, kstep = jax.random.split(rng)
        with jax.named_scope("shuffle"):
            perm = jax.random.permutation(
                jax.random.fold_in(kperm, dev), n_local)
            idx = perm[:k * local_batch].reshape(k, local_batch)
        lrs = jnp.broadcast_to(jnp.asarray(lr, jnp.float32), (k,))

        def body(carry, scan_in):
            bidx, i, lr_i = scan_in
            key = jax.random.fold_in(jax.random.fold_in(kstep, i), dev)
            with jax.named_scope("data"):
                xb = _decode(as_samples(x_local[bidx], model.input_shape),
                             scale, cdt)
                if augment is not None:
                    xb = augment(xb, jax.random.fold_in(key, 0x0A6))
                yb = jax.nn.one_hot(y_local[bidx], num_classes,
                                    dtype=jnp.float32)
            new_ts, loss, _ = base(carry, xb, yb, key, lr_i)
            return new_ts, loss

        ts, losses = jax.lax.scan(body, ts, (idx, jnp.arange(k), lrs))
        return ts, jnp.mean(losses)

    smapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), P(), P()),
        out_specs=(P(), P()),
        check_vma=False)

    def epoch(ts, x_shard, y_shard, rng, lr):
        return smapped(ts, x_shard, y_shard, rng,
                       jnp.asarray(lr, jnp.float32))

    return jax.jit(epoch, donate_argnums=(0,))


class ShardedDeviceDataset:
    """A split staged SHARDED over a mesh's data axis for
    :func:`make_resident_epoch_dp` — the Trainer routes it like a
    ``DeviceDataset`` but runs the data-parallel resident epoch (one dispatch
    per epoch on every device, grad pmean over ICI).

    ``batch_size`` is the GLOBAL batch. Validation: pass an ordinary
    (replicated) ``DeviceDataset`` as the val loader — val splits are small
    and the whole-split eval is one dispatch either way.
    """

    def __init__(self, x: np.ndarray, y: np.ndarray, num_classes: int, *,
                 batch_size: int, mesh, augment: Optional[Callable] = None,
                 scale: Optional[float] = None):
        from ..core.mesh import DATA_AXIS

        x = np.asarray(x)
        if len(x) != len(np.asarray(y)):
            raise ValueError(
                f"x/y length mismatch: {len(x)} vs {len(np.asarray(y))}")
        d = mesh.shape[DATA_AXIS]
        self.mesh = mesh
        self.num_classes = int(num_classes)
        self.batch_size = int(batch_size)
        if self.batch_size % d != 0:
            raise ValueError(f"global batch {batch_size} % data size {d} != 0")
        self.augment = augment
        self.scale = float(scale if scale is not None
                           else (1.0 / 255.0 if x.dtype == np.uint8 else 1.0))
        self.sample_shape = x.shape[1:]
        self.x_staged, self.y = stage_sharded(x, y, mesh)
        self.num_samples = int(self.x_staged.shape[0])
        self.local_samples = self.num_samples // d

    @property
    def x(self) -> jax.Array:
        """The sharded split in its sample shape (see ``DeviceDataset.x``)."""
        return as_samples(self.x_staged, self.sample_shape)

    @property
    def steps_per_epoch(self) -> int:
        from ..core.mesh import DATA_AXIS
        return self.local_samples // (self.batch_size
                                      // self.mesh.shape[DATA_AXIS])

    def __len__(self) -> int:
        return self.steps_per_epoch


@functools.lru_cache(maxsize=32)
def _resident_epoch_dp_cached(model, loss_fn, optimizer, num_classes,
                              batch_size, mesh, augment, scale,
                              num_microbatches, _mode):
    return make_resident_epoch_dp(model, loss_fn, optimizer,
                                  num_classes=num_classes,
                                  batch_size=batch_size, mesh=mesh,
                                  augment=augment, scale=scale,
                                  num_microbatches=num_microbatches)


def resident_epoch_dp(model, loss_fn, optimizer, dataset: ShardedDeviceDataset,
                      num_microbatches: int = 1):
    """Memoized DP epoch fn (precision-keyed like :func:`resident_epoch`)."""
    from ..core.precision import get_precision_mode
    return _resident_epoch_dp_cached(model, loss_fn, optimizer,
                                     dataset.num_classes, dataset.batch_size,
                                     dataset.mesh, dataset.augment,
                                     dataset.scale, num_microbatches,
                                     get_precision_mode())


def stage_sharded(x, y, mesh, *, global_shuffle_seed: Optional[int] = 0):
    """Stage a split sharded over the mesh's data axis (sample dim): each
    device holds N/D samples in its own HBM, in the :func:`lane_dense` view
    (which :func:`make_resident_epoch_dp` reshapes back per batch). Trims
    the remainder so shards are equal.

    A seeded GLOBAL host-side permutation is applied before sharding
    (``global_shuffle_seed=None`` disables it): the resident DP epoch only
    reshuffles *within* each shard, so without this a class-sorted split
    (e.g. Tiny-ImageNet directory order) would pin each device to a
    class-biased shard forever — and BN would normalize every local batch
    with class-conditional statistics (ADVICE r3 #1)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..core.mesh import DATA_AXIS

    d = mesh.shape[DATA_AXIS]
    n = (len(x) // d) * d
    x, y = np.asarray(x), np.asarray(y)
    if global_shuffle_seed is not None:
        perm = np.random.default_rng(global_shuffle_seed).permutation(len(x))
        x, y = x[perm], y[perm]
    x, y = x[:n], y[:n]
    if y.ndim == 2:
        y = y.argmax(axis=-1)
    xs = jax.device_put(lane_dense(x), NamedSharding(mesh, P(DATA_AXIS)))
    ys = jax.device_put(y.astype(np.int32), NamedSharding(mesh, P(DATA_AXIS)))
    return xs, ys


@functools.lru_cache(maxsize=32)
def _resident_epoch_cached(model, loss_fn, optimizer, num_classes, batch_size,
                           augment, scale, num_microbatches, _mode):
    return make_resident_epoch(model, loss_fn, optimizer,
                               num_classes=num_classes, batch_size=batch_size,
                               augment=augment, scale=scale,
                               num_microbatches=num_microbatches)


@functools.lru_cache(maxsize=32)
def _resident_eval_cached(model, loss_fn, num_classes, batch_size, _mode):
    return make_resident_eval(model, loss_fn, num_classes=num_classes,
                              batch_size=batch_size)


def resident_epoch(model, loss_fn, optimizer, dataset: DeviceDataset,
                   num_microbatches: int = 1):
    """Memoized epoch fn for a (model, loss, optimizer, dataset geometry,
    precision-mode) combination — repeated ``fit`` calls reuse one compiled
    executable per shape (precision-keyed per ADVICE r2 #4).

    Cache hits require the SAME model/optimizer/augment *objects* (the
    lru_cache keys on identity — per-call reconstruction compiles a fresh
    executable each time and ages live entries out of the 32-slot cache,
    ADVICE r3 #4); the Trainer holds one of each for exactly this reason."""
    from ..core.precision import get_precision_mode
    return _resident_epoch_cached(model, loss_fn, optimizer,
                                  dataset.num_classes, dataset.batch_size,
                                  dataset.augment, dataset.scale,
                                  num_microbatches, get_precision_mode())


def resident_eval(model, loss_fn, dataset: DeviceDataset):
    """Memoized whole-split eval fn (see :func:`make_resident_eval`)."""
    from ..core.precision import get_precision_mode
    return _resident_eval_cached(model, loss_fn, dataset.num_classes,
                                 dataset.batch_size, get_precision_mode())
