"""Single-device (and data-parallel) training loops.

Reference equivalent: ``include/nn/train.hpp`` — ``TrainingConfig`` (:46),
``train_class_epoch`` (:108), ``validate_class_model`` (:172),
``train_classification_model`` (:202: epoch loop, best-val snapshot save,
per-epoch LR decay), regression twins (:311-481).

TPU-native shape: one jitted ``train_step`` closes over the model spec /
loss / optimizer; params/state/opt-state live in a ``TrainState`` pytree.
Optional microbatch gradient accumulation runs as a ``lax.scan`` inside the
step — BN statistics are computed per microbatch sequentially, matching the
reference's per-microbatch BN semantics (SURVEY.md §7 hard part 4).
"""

from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.config import ProfilerType, TrainingConfig
from ..nn.sequential import Sequential
from ..obs import Dispatch, get_registry, get_tracer, log_dispatch, phase
from ..obs.xla import install_compile_listener
from ..resilience import faults as _faults
from ..ops.losses import get_loss, upcast_logits
from ..ops.metrics import correct_count
from ..optim.optimizers import Optimizer
from ..optim.schedulers import Scheduler
from .checkpoint import save_checkpoint
from .profiling import LayerProfiler


@dataclass
class TrainState:
    """Everything that changes during training, as one pytree."""

    params: Any
    state: Any        # per-layer mutable state (BN running stats)
    opt_state: Any
    step: jax.Array   # int32 scalar

    def tree_flatten(self):
        return (self.params, self.state, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


@phase("setup.state")
def create_train_state(model: Sequential, optimizer: Optimizer, key: jax.Array,
                       input_shape=None) -> TrainState:
    params, state = model.init(key, input_shape)
    return TrainState(params=params, state=state,
                      opt_state=optimizer.init(params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(model: Sequential, loss_fn: Callable, optimizer: Optimizer,
                    num_microbatches: int = 1, donate: bool = True,
                    jit: bool = True, reduce_axis: Optional[str] = None,
                    guard: bool = False):
    """Returns jitted ``step(ts, x, y, rng, lr) -> (ts, loss, logits)``.

    With ``num_microbatches > 1`` the batch is split on the leading axis and
    grads are accumulated with ``lax.scan`` (the single-jit analog of the
    reference's microbatch streaming, tensor_ops.hpp:193-225).

    ``reduce_axis``: name of a mapped mesh axis (shard_map/pmap body) to
    ``pmean`` grads, loss, and the updated layer state over before the
    optimizer update — the canonical data-parallel step; every DP wrapper
    reuses this instead of reimplementing fwd/bwd/update. Logits stay local
    to the shard.

    ``guard=True``: the step additionally returns a scalar bool ``bad`` —
    the in-graph non-finite detector (``~isfinite(loss) | ~isfinite(Σ‖g‖²)``)
    — and when it fires the returned TrainState is the *incoming* one
    (params/state/opt_state/step selected untouched via ``jnp.where``), so
    a poisoned batch can never contaminate training state; host-side
    policy (raise / skip / rollback) lives in ``resilience.StepGuard``."""

    def forward_loss(params, state, x, y, rng):
        logits, new_state = model.apply(params, state, x, training=True, rng=rng)
        # The repo losses upcast internally (ops/losses._loss_fp32 is the
        # boundary); this cast covers *custom* loss_fns and fixes the dtype
        # of the logits handed back to callers. fp64 stays fp64 (the fp64
        # precision mode must not quantize the loss/cotangent boundary).
        with jax.named_scope("loss"):
            logits = upcast_logits(logits)
            loss = loss_fn(logits, y)
        return loss, (logits, new_state)

    grad_fn = jax.value_and_grad(forward_loss, has_aux=True)

    def step(ts: TrainState, x, y, rng, lr):
        # Shapes are static at trace time: a trailing partial batch (any
        # drop_last=False loader) that doesn't divide evenly falls back to
        # one whole-batch microbatch rather than crashing the reshape. The
        # fallback changes BN batch-statistics semantics (one big batch vs
        # N small ones), so it warns — once per traced shape.
        if num_microbatches > 1 and x.shape[0] % num_microbatches != 0:
            import warnings
            warnings.warn(
                f"batch size {x.shape[0]} not divisible by num_microbatches="
                f"{num_microbatches}: training this batch unmicrobatched "
                f"(different BN statistics semantics)", stacklevel=2)
        if num_microbatches == 1 or x.shape[0] % num_microbatches != 0:
            (loss, (logits, new_state)), grads = grad_fn(ts.params, ts.state, x, y, rng)
        else:
            mb_x = x.reshape(num_microbatches, x.shape[0] // num_microbatches, *x.shape[1:])
            mb_y = y.reshape(num_microbatches, y.shape[0] // num_microbatches, *y.shape[1:])

            def body(carry, mb):
                state, grad_acc, loss_acc = carry
                xi, yi, i = mb
                (loss, (logits, new_state)), grads = grad_fn(
                    ts.params, state, xi, yi, jax.random.fold_in(rng, i))
                grad_acc = jax.tree_util.tree_map(jnp.add, grad_acc, grads)
                return (new_state, grad_acc, loss_acc + loss), logits

            zero_grads = jax.tree_util.tree_map(jnp.zeros_like, ts.params)
            idx = jnp.arange(num_microbatches)
            (new_state, grads, loss_sum), logits_all = jax.lax.scan(
                body, (ts.state, zero_grads, 0.0), (mb_x, mb_y, idx))
            grads = jax.tree_util.tree_map(lambda g: g / num_microbatches, grads)
            loss = loss_sum / num_microbatches
            logits = logits_all.reshape(x.shape[0], -1)

        if reduce_axis is not None:
            grads = jax.lax.pmean(grads, reduce_axis)
            loss = jax.lax.pmean(loss, reduce_axis)
            # per-shard batch statistics, mesh-averaged (EMA is linear, so
            # this equals an EMA of shard-mean statistics)
            new_state = jax.lax.pmean(new_state, reduce_axis)
        with jax.named_scope("optim"):
            new_params, new_opt = optimizer.update(grads, ts.opt_state,
                                                   ts.params, lr)
        if not guard:
            return (TrainState(new_params, new_state, new_opt, ts.step + 1),
                    loss, logits)
        from ..resilience.guards import global_norm_sq
        with jax.named_scope("guard"):
            bad = jnp.logical_not(jnp.isfinite(loss)
                                  & jnp.isfinite(global_norm_sq(grads)))
            keep = lambda new, old: jnp.where(bad, old, new)  # noqa: E731
            guarded = TrainState(
                jax.tree_util.tree_map(keep, new_params, ts.params),
                jax.tree_util.tree_map(keep, new_state, ts.state),
                jax.tree_util.tree_map(keep, new_opt, ts.opt_state),
                jnp.where(bad, ts.step, ts.step + 1))
        return guarded, loss, logits, bad

    if not jit:
        return step
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_multi_step(model: Sequential, loss_fn: Callable, optimizer: Optimizer,
                    num_microbatches: int = 1, donate: bool = True):
    """Returns jitted ``multi_step(ts, xs, ys, rng, lr) -> (ts, mean_loss)``
    running ``xs.shape[0]`` full train steps in ONE device dispatch via
    ``lax.scan`` (``xs``: [K, B, ...], ``ys``: [K, B, classes]).

    The TPU-idiomatic "train loop inside jit": one executable launch per K
    batches, paired with a prefetching loader that stages K batches into
    HBM while the previous chunk trains. Semantics are identical to K
    sequential ``make_train_step`` calls (per-batch BN stats, per-batch
    optimizer updates, per-step folded rng) — only the dispatch granularity
    changes. The reference has no analog (its CUDA stream dispatch is local
    and cheap); this is pure TPU-runtime design.

    ``lr`` may be a scalar or a [K] vector (one lr per inner step) — the
    latter keeps per-batch LR schedules exact under chunked dispatch."""
    base = make_train_step(model, loss_fn, optimizer,
                           num_microbatches=num_microbatches, jit=False)

    def multi_step(ts: TrainState, xs, ys, rng, lr):
        lrs = jnp.broadcast_to(jnp.asarray(lr, jnp.float32), (xs.shape[0],))

        def body(carry, xyi):
            x, y, i, lr_i = xyi
            new_ts, loss, _ = base(carry, x, y, jax.random.fold_in(rng, i), lr_i)
            return new_ts, loss

        ts, losses = jax.lax.scan(
            body, ts, (xs, ys, jnp.arange(xs.shape[0]), lrs))
        return ts, jnp.mean(losses)

    return jax.jit(multi_step, donate_argnums=(0,) if donate else ())


def make_eval_step(model: Sequential, loss_fn: Callable):
    """Jitted ``eval_step(params, state, x, y) -> (loss, correct)``
    (reference ``validate_class_model``, train.hpp:172). Memoized on
    (model, loss_fn, precision-mode) so per-epoch validation reuses one
    compiled step — and a ``set_precision`` change re-traces instead of
    silently serving the old mode's executable."""
    from ..core.precision import get_precision_mode
    return _make_eval_step_cached(model, loss_fn, get_precision_mode())


@functools.lru_cache(maxsize=64)
def _make_eval_step_cached(model: Sequential, loss_fn: Callable, _mode: str):
    @jax.jit
    def eval_step(params, state, x, y):
        logits, _ = model.apply(params, state, x, training=False)
        logits = upcast_logits(logits)
        return loss_fn(logits, y), correct_count(logits, y)

    return eval_step


def evaluate_classification(model, params, state, loss_fn, loader,
                            eval_step=None) -> Tuple[float, float]:
    from ..data.device_dataset import (
        DeviceDataset, ShardedDeviceDataset, resident_eval)
    if isinstance(loader, ShardedDeviceDataset):
        raise TypeError(
            "validation over a ShardedDeviceDataset is not supported — val "
            "splits are small: stage them replicated with DeviceDataset "
            "(whole-split eval is one dispatch either way)")
    if isinstance(loader, DeviceDataset):
        if loader.y is None:
            raise NotImplementedError(
                "validation over a TokenDataset: there is no evaluation on "
                "tokens yet (train with val_loader=None)")
        # HBM-resident split: one device dispatch for the whole validation
        # pass (full batches + exact remainder — see data/device_dataset.py)
        ev = resident_eval(model, loss_fn, loader)
        loss_sum, correct, n = ev(params, state, loader.x_staged, loader.y,
                                  scale=loader.scale)
        n = int(n)  # jit canonicalizes to Array; history/snapshots need floats
        return float(loss_sum) / n, int(correct) / n
    eval_step = eval_step if eval_step is not None else make_eval_step(model, loss_fn)
    total_loss, total_correct, total_n = 0.0, 0, 0
    # host loaders ship wire-dtype batches (uint8 pixels): decode after
    # the put, per the loader's scale contract (identity for float input)
    from ..data.wire import decode_batch, wire_scale
    scale = wire_scale(loader)
    for x, y in loader:
        loss, correct = eval_step(params, state,
                                  decode_batch(jnp.asarray(x), scale),
                                  jnp.asarray(y))
        total_loss += float(loss) * x.shape[0]
        total_correct += int(correct)
        total_n += x.shape[0]
    if total_n == 0:
        return 0.0, 0.0
    return total_loss / total_n, total_correct / total_n


class Trainer:
    """Epoch-loop driver (reference ``train_classification_model``,
    train.hpp:202-308): per-epoch train/validate, best-val snapshot, LR decay
    or scheduler, progress prints, optional per-layer profiling."""

    @phase("setup.trainer")
    def __init__(self, model: Sequential, optimizer: Optimizer,
                 loss: Callable | str, config: Optional[TrainingConfig] = None,
                 scheduler: Optional[Scheduler] = None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = get_loss(loss) if isinstance(loss, str) else loss
        self.config = config or TrainingConfig()
        if self.config.debug:
            # the 'debug build' (reference ENABLE_DEBUG -> ASan): sanitize
            # NaN/Inf production across every jitted step of this process
            from ..core.debug import enable_debug_mode
            enable_debug_mode()
        self.scheduler = scheduler
        install_compile_listener()
        self.profiler = (LayerProfiler(self.config.profiler)
                         if self.config.profiler != ProfilerType.NONE else None)
        # failure flight recorder (obs/flight.py): flight_dir enables the
        # PROCESS-GLOBAL recorder so every trigger site this trainer
        # touches — the non-finite guard, the stall watchdog, the
        # telemetry server's healthz 503 edge — dumps postmortem bundles
        # there without per-site plumbing (same semantics as the
        # DCNN_FLIGHT_DIR env var, applied at construction)
        if self.config.flight_dir:
            from ..obs.flight import configure_flight
            configure_flight(self.config.flight_dir)
        # non-finite step guard (resilience/guards.py): "off" keeps the
        # exact pre-guard graph; any policy compiles the guarded step that
        # returns (and neutralizes) the bad flag in-graph
        self._guard_on = self.config.nonfinite_policy != "off"
        if self._guard_on:
            if self.config.steps_per_dispatch > 1:
                raise ValueError(
                    "nonfinite_policy guards the per-batch step loop; with "
                    "steps_per_dispatch > 1 losses never reach the host "
                    "per-step — use steps_per_dispatch=1 or policy 'off'")
            if (self.config.nonfinite_policy == "rollback"
                    and not self.config.checkpoint_dir):
                raise ValueError(
                    "nonfinite_policy='rollback' needs checkpoint_dir set "
                    "(and checkpoint_every > 0) so there is a checkpoint "
                    "to roll back to — a rollback that can only abort is "
                    "a delayed crash, not a recovery policy")
            from ..resilience.guards import StepGuard
            self.guard = StepGuard(self.config.nonfinite_policy,
                                   rollback_after=self.config.rollback_after)
        else:
            self.guard = None
        # periodic atomic checkpointing + resume (resilience/checkpoint.py)
        if self.config.checkpoint_dir:
            from ..resilience.checkpoint import CheckpointManager
            self.checkpoints = CheckpointManager(
                self.config.checkpoint_dir, keep=self.config.checkpoint_keep)
        else:
            self.checkpoints = None
        self.watchdog = None  # created per fit() when stall_timeout_s > 0
        self.telemetry = None  # TelemetryServer, per fit() (metrics_port)
        self._tsdb = None      # TsdbSampler, per fit() (rides metrics_port)
        self._goodput = None   # GoodputMonitor, per fit() (rides metrics_port)
        self._global_step = 0
        self.train_step = make_train_step(model, self.loss_fn, optimizer,
                                          self.config.num_microbatches,
                                          guard=self._guard_on)
        # chunked fast path: one device dispatch per K train steps. The
        # loader must yield [K, B, ...] stacks (PrefetchLoader with
        # stage_batches=K); per-batch logits/accuracy are not materialized
        # in this mode (the loss is the per-chunk mean).
        self.multi_step = (make_multi_step(model, self.loss_fn, optimizer,
                                           self.config.num_microbatches)
                           if self.config.steps_per_dispatch > 1 else None)
        self.eval_step = make_eval_step(model, self.loss_fn)
        self.lr = self.config.learning_rate
        self.history: list = []
        # the resident path's host timeline (obs/hostlog.py): the epoch
        # programs this trainer has called, the last fence, from which the
        # next turn is counted, and the log entry train_epoch has yet to
        # close behind publish
        self._dispatched: set = set()
        self._t_fenced: Optional[float] = None
        self._pending_dispatch: Optional[tuple] = None

    @staticmethod
    def _epoch_samples(loader) -> Optional[int]:
        """Best-effort samples-per-epoch for the throughput gauge. None
        (gauge skipped) when the loader exposes no length — telemetry never
        guesses."""
        # steps*batch first: it is what an epoch actually consumes — a
        # drop-last loader's num_samples would overcount the tail
        spe = getattr(loader, "steps_per_epoch", None)
        bs = getattr(loader, "batch_size", None)
        if spe and bs:
            return int(spe) * int(bs)
        n = getattr(loader, "num_samples", None)
        if n:
            return int(n)
        x = getattr(loader, "x", None)
        if x is not None and hasattr(x, "shape"):
            return int(x.shape[0])
        return None

    def _rollback(self, ts: TrainState) -> TrainState:
        """'rollback' guard policy: restore training state from the newest
        valid checkpoint (the run's state may already be poisoned — one
        skipped step was not enough)."""
        if self.checkpoints is None:
            raise RuntimeError(
                "nonfinite_policy='rollback' needs checkpoint_dir set so "
                "there is a checkpoint to roll back to")
        self.checkpoints.wait()  # queued async saves must land first
        restored = self.checkpoints.restore_latest(seed=self.config.seed)
        if restored is None:
            raise RuntimeError(
                f"rollback requested but no valid checkpoint under "
                f"{self.checkpoints.directory}")
        print(f"  guard rollback: restored checkpoint step {restored.step} "
              f"from {restored.path}", flush=True)
        return TrainState(
            restored.params, restored.state, restored.opt_state,
            jnp.asarray(restored.metadata.get("global_step", 0), jnp.int32))

    def train_epoch(self, ts: TrainState, loader, rng: jax.Array,
                    epoch: int = 0) -> Tuple[TrainState, float, float]:
        """One epoch by whichever path the loader and the config select.
        Every path has fenced on its last loss when it returns, so a model
        that keeps counts in its state (``publish_state``: the state with
        those counts in the registry and back at zero) is asked for them
        here, with nothing new to wait for."""
        ts, loss, acc = self._train_epoch(ts, loader, rng, epoch)
        publish = getattr(self.model, "publish_state", None)
        if publish is not None:
            with get_tracer().span("train.publish", track="train",
                                   epoch=epoch):
                ts.state = publish(ts.state)
        pending, self._pending_dispatch = self._pending_dispatch, None
        if pending is not None:         # a resident epoch: its log entry
            entry, turn = pending
            if publish is not None:
                entry = entry._replace(t_published=time.perf_counter())
            log_dispatch(entry, turn)
        return ts, loss, acc

    def _train_epoch(self, ts: TrainState, loader, rng: jax.Array,
                     epoch: int) -> Tuple[TrainState, float, float]:
        from ..data.device_dataset import DeviceDataset, ShardedDeviceDataset
        if isinstance(loader, (DeviceDataset, ShardedDeviceDataset)) \
                and self.guard is not None:
            raise ValueError(
                "nonfinite_policy guards the per-batch step loop; resident "
                "datasets run whole epochs in one dispatch (losses never "
                "reach the host per-step) — use a host loader or policy "
                "'off'")
        if isinstance(loader, ShardedDeviceDataset):
            return self._train_epoch_resident(ts, loader, rng, epoch, dp=True)
        if isinstance(loader, DeviceDataset):
            return self._train_epoch_resident(ts, loader, rng, epoch)
        if self.multi_step is not None:
            return self._train_epoch_chunked(ts, loader, rng, epoch)
        tracer = get_tracer()
        total_loss, total_correct, total_n, batches = 0.0, 0, 0, 0
        t0 = time.perf_counter()
        # wire-dtype contract: the put above ships the loader's wire
        # dtype (uint8 pixels); decode to model domain on device, after
        # the transfer (identity for float batches)
        from ..data.wire import decode_batch, wire_scale
        scale = wire_scale(loader)
        for bi, (x, y) in enumerate(loader):
            x, y = decode_batch(jnp.asarray(x), scale), jnp.asarray(y)
            step_rng = jax.random.fold_in(rng, bi)
            self._global_step += 1
            if self.watchdog is not None:
                self.watchdog.beat()
            if _faults.active() is not None:
                # fault harness: an armed "train.nonfinite_input" poisons
                # this batch so loss/grads go NaN (same shape/dtype — no
                # retrace), proving the guard path end to end; armed as an
                # InjectedCrash it kills the run here instead (the
                # mid-epoch-preemption simulation resume tests restart from)
                try:
                    _faults.trip("train.nonfinite_input",
                                 step=self._global_step)
                except _faults.InjectedCrash:
                    raise
                except _faults.InjectedFault:
                    x = jnp.full_like(x, jnp.nan)
            # the float(loss)/correct_count reads inside the span block on
            # the device result, so step spans tile the epoch wall truthfully
            t_step = time.perf_counter()
            with tracer.span("train.step", track="train", epoch=epoch,
                             batch=bi, fenced=True):
                if self.guard is not None:
                    ts, loss, logits, bad = self.train_step(
                        ts, x, y, step_rng, self.lr)
                    action = self.guard.observe(
                        self._global_step, bool(bad), float(loss))
                    if action == "rollback":
                        ts = self._rollback(ts)
                        continue  # skipped-step metrics excluded below too
                    if action == "skipped":
                        continue  # NaN loss must not poison the epoch mean
                else:
                    ts, loss, logits = self.train_step(
                        ts, x, y, step_rng, self.lr)
                total_loss += float(loss) * x.shape[0]
                total_correct += int(correct_count(logits, y))
            if self._goodput is not None:
                self._goodput.observe_step(time.perf_counter() - t_step)
            total_n += x.shape[0]
            batches += 1
            if (self.scheduler is not None
                    and self.config.scheduler_step == "batch"):
                # per-batch cadence: what OneCycleLR/WarmupCosine are sized
                # for (total_steps = epochs * batches_per_epoch); the metric
                # is the running train loss (val loss doesn't exist mid-epoch;
                # max() guards an all-steps-skipped start under the guard)
                self.lr = self.scheduler.step(total_loss / max(total_n, 1))
            if self.config.progress_interval and (bi + 1) % self.config.progress_interval == 0:
                dt = time.perf_counter() - t0
                n = max(total_n, 1)
                print(f"  epoch {epoch} batch {bi + 1}: loss {total_loss / n:.4f} "
                      f"acc {total_correct / n:.4f} "
                      f"({total_n / dt:.1f} samples/s)", flush=True)
        return ts, (total_loss / max(total_n, 1)), (total_correct / max(total_n, 1))

    def _train_epoch_resident(self, ts: TrainState, ds, rng: jax.Array,
                              epoch: int = 0, dp: bool = False,
                              ) -> Tuple[TrainState, float, float]:
        """HBM-resident epoch: ONE device dispatch runs shuffle + gather +
        decode + augment + every train step (data/device_dataset.py). Zero
        steady-state H2D; train accuracy is not materialized (NaN — validation
        measures real accuracy), matching the chunked path's contract.
        Per-batch LR schedules ship as a [steps] vector; metric-driven
        schedulers see the previous epoch's mean train loss (per-epoch
        granularity — mid-epoch losses never reach the host in this mode).
        ``dp=True`` (ShardedDeviceDataset): the data-parallel variant — the
        dataset lives sharded over the mesh and every device runs the epoch
        with grad pmean (data/device_dataset.py:make_resident_epoch_dp);
        the scalar-lr path only (per-batch lr vectors not yet threaded)."""
        k = ds.steps_per_epoch
        batch_sched = (self.scheduler is not None
                       and self.config.scheduler_step == "batch")
        if dp:
            from ..data.device_dataset import resident_epoch_dp
            epoch_fn = resident_epoch_dp(self.model, self.loss_fn,
                                         self.optimizer, ds,
                                         self.config.num_microbatches)
            if batch_sched:
                raise NotImplementedError(
                    "per-batch LR scheduling with ShardedDeviceDataset: the "
                    "DP epoch takes a scalar lr; use scheduler_step='epoch'")
        else:
            from ..data.device_dataset import resident_epoch
            epoch_fn = resident_epoch(self.model, self.loss_fn,
                                      self.optimizer, ds,
                                      self.config.num_microbatches)
        if batch_sched:
            metric = self.history[-1]["train_loss"] if self.history else None
            lrs = []
            for si in range(k):
                lrs.append(self.lr)
                # one metric evaluation per epoch (cf. chunked path: one per
                # chunk) — plateau patience is measured in epochs here
                self.lr = self.scheduler.step(metric if si == 0 else None)
            lr_arg = jnp.asarray(lrs, jnp.float32)
        else:
            lr_arg = self.lr
        # one dispatch runs the whole epoch; float() fences, so the span is
        # the true epoch device wall. Its children split it at the call's
        # return; the stamps beside them go to the always-on dispatch log
        # (train_epoch closes the entry, behind publish)
        tracer = get_tracer()
        first = epoch_fn not in self._dispatched
        self._dispatched.add(epoch_fn)
        with tracer.span("train.resident_epoch", track="train", epoch=epoch,
                         dp=dp):
            t_call = time.perf_counter()
            with tracer.span("train.dispatch", track="train", epoch=epoch,
                             steps=k, first=first):
                ts, mean_loss = epoch_fn(ts, ds.x_staged, ds.y,
                                         jax.random.fold_in(rng, epoch),
                                         lr_arg)
            t_returned = time.perf_counter()
            with tracer.span("train.fence", track="train", epoch=epoch):
                mean_loss = float(mean_loss)
            t_fenced = time.perf_counter()
        turn = None
        if self._t_fenced is not None:
            # it crosses a return to the caller: no `with` block can hold it
            turn = t_returned - self._t_fenced
            tracer.record_span("train.turn", self._t_fenced, t_returned,
                               track="train", epoch=epoch)
        self._t_fenced = t_fenced
        self._pending_dispatch = (
            Dispatch(t_call, t_returned, t_fenced, t_fenced, k, first), turn)
        return ts, mean_loss, float("nan")

    def _train_epoch_chunked(self, ts: TrainState, loader, rng: jax.Array,
                             epoch: int = 0) -> Tuple[TrainState, float, float]:
        """K train steps per device dispatch over [K, B, ...] chunks.
        Per-batch logits are not materialized, so train accuracy is reported
        as NaN (validation still measures real accuracy). Per-batch LR
        schedules stay exact: the K per-step lrs are precomputed on the host
        and shipped as a vector into the scan (metric-driven schedulers see
        the pre-chunk running loss instead of intermediate losses — the one
        documented approximation)."""
        sample_ndim = len(self.model.input_shape)
        total_loss, total_n = 0.0, 0
        t0 = time.perf_counter()
        # decode after the put, per the loader's wire contract (identity
        # for float chunks and for PrefetchLoader's auto-decoded output)
        from ..data.wire import decode_batch, wire_scale
        scale = wire_scale(loader)
        for ci, (xs, ys) in enumerate(loader):
            if self.watchdog is not None:
                self.watchdog.beat()
            xs, ys = decode_batch(jnp.asarray(xs), scale), jnp.asarray(ys)
            if xs.ndim != sample_ndim + 2:
                raise ValueError(
                    f"steps_per_dispatch={self.config.steps_per_dispatch} "
                    f"needs [K, B, ...] chunks (got shape {xs.shape}); wrap "
                    f"the loader in PrefetchLoader(stage_batches=K) / "
                    f"examples.common.with_prefetch")
            chunk_rng = jax.random.fold_in(rng, ci)
            per_batch_sched = (self.scheduler is not None
                               and self.config.scheduler_step == "batch")
            if per_batch_sched:
                # No loss exists yet for the first chunk: pass None so
                # metric-driven schedulers (ReduceLROnPlateau) skip the
                # update instead of seeing a spurious 0.0 "perfect" loss.
                metric = (total_loss / total_n) if total_n > 0 else None
                lrs = []
                for si in range(xs.shape[0]):
                    lrs.append(self.lr)
                    # one metric evaluation per chunk: feeding the same value
                    # K times would count K-1 spurious "no improvement" steps
                    # per chunk in plateau schedulers (patience is therefore
                    # measured in chunks when steps_per_dispatch > 1)
                    self.lr = self.scheduler.step(metric if si == 0 else None)
                lr_arg = jnp.asarray(lrs, jnp.float32)
            else:
                lr_arg = self.lr
            t_chunk = time.perf_counter()
            with get_tracer().span("train.chunk", track="train",
                                   epoch=epoch, chunk=ci,
                                   steps=int(xs.shape[0]), fenced=True):
                ts, mean_loss = self.multi_step(ts, xs, ys, chunk_rng, lr_arg)
                n = xs.shape[0] * xs.shape[1]
                total_loss += float(mean_loss) * n
            if self._goodput is not None:
                # per-step anomaly granularity: a chunk is K fused steps
                self._goodput.observe_step(
                    (time.perf_counter() - t_chunk) / max(xs.shape[0], 1))
            total_n += n
            if self.config.progress_interval and (ci + 1) % max(
                    self.config.progress_interval // max(xs.shape[0], 1), 1) == 0:
                dt = time.perf_counter() - t0
                print(f"  epoch {epoch} chunk {ci + 1}: loss "
                      f"{total_loss / total_n:.4f} "
                      f"({total_n / dt:.1f} samples/s)", flush=True)
        return ts, total_loss / max(total_n, 1), float("nan")

    def fit(self, ts: TrainState, train_loader, val_loader=None,
            epochs: Optional[int] = None, seed: Optional[int] = None) -> TrainState:
        cfg = self.config
        if cfg.elastic:
            # generation-aware elastic DP fit: the membership/heartbeat
            # layer, lockstep gradient exchange, and the
            # reconfiguration-on-peer-loss protocol live in
            # parallel/elastic.py; this loop delegates so a single config
            # knob (ELASTIC=1 + ELASTIC_PEERS) turns a normal run into
            # one that survives losing a host mid-epoch. Lazy import:
            # train.trainer must stay importable without the parallel
            # package (which itself imports this module).
            from ..parallel.elastic import elastic_fit
            return elastic_fit(self, ts, train_loader, val_loader, epochs,
                               seed=seed)
        epochs = epochs or cfg.epochs
        rng = jax.random.PRNGKey(seed if seed is not None else cfg.seed)
        best_val = -1.0
        tracer = get_tracer()
        reg = get_registry()
        start_epoch = 1
        if self.checkpoints is not None and cfg.resume == "auto":
            # resume contract (docs/reliability.md): epoch rng is
            # fold_in(PRNGKey(seed), epoch) and loaders shuffle by epoch, so
            # restarting at the restored epoch+1 with restored
            # params/state/opt_state/lr replays the exact uninterrupted
            # loss trajectory (metric-driven scheduler internals are the one
            # documented exception — they see the restored history only)
            restored = self.checkpoints.restore_latest(seed=cfg.seed)
            if restored is not None:
                md = restored.metadata
                ts = TrainState(
                    restored.params, restored.state, restored.opt_state,
                    jnp.asarray(md.get("global_step", 0), jnp.int32))
                start_epoch = restored.step + 1
                self.lr = md.get("lr", self.lr)
                self.history = md.get("history", self.history) or []
                self._global_step = int(md.get("global_step", 0))
                best_val = md.get("best_val", -1.0)
                print(f"resumed from checkpoint step {restored.step} "
                      f"({restored.path}); continuing at epoch {start_epoch}",
                      flush=True)
        if cfg.stall_timeout_s > 0:
            from ..resilience.guards import StallWatchdog
            self.watchdog = StallWatchdog(cfg.stall_timeout_s).start()
        try:
            if cfg.metrics_port >= 0:
                # external telemetry plane (obs/server.py): /metrics
                # scrape + /healthz (watchdog stall and rotting-checkpoint
                # states flip it to 503) + /snapshot, live for the whole
                # fit. Inside the try: a failed bind (port in use) must
                # still stop the watchdog below
                from ..obs import (TelemetryServer, checkpoint_check,
                                   get_flight_recorder, watchdog_check)
                from ..obs.tsdb import TimeSeriesStore, TsdbSampler
                srv = TelemetryServer(registry=reg, tracer=tracer,
                                      port=cfg.metrics_port)
                srv.set_identity(component="trainer")
                srv.attach_flight(get_flight_recorder())
                if self.watchdog is not None:
                    srv.add_check("watchdog",
                                  watchdog_check(self.watchdog))
                if self.checkpoints is not None:
                    srv.add_check("checkpoint",
                                  checkpoint_check(self.checkpoints))
                self.telemetry = srv.start()
                # monitoring-plane history (obs/tsdb.py): sample the
                # registry at a cadence for the whole fit, so flight
                # bundles carry the minutes before a trigger and
                # /snapshot shows the store's shape. Telemetry off =
                # zero threads, zero per-step cost.
                store = TimeSeriesStore()
                self._tsdb = TsdbSampler(
                    store, registry=reg,
                    interval_s=float(os.environ.get(
                        "DCNN_TSDB_INTERVAL", "1.0"))).start()
                srv.add_snapshot("tsdb", store.summary)
                get_flight_recorder().attach_tsdb(store)
                # goodput plane (obs/goodput.py): every sampler pass
                # attributes the trailing window of tracer spans to
                # buckets, publishes the gauges, classifies the
                # bottleneck, and — on an EWMA step-time breach or a
                # verdict flip — fires exactly one flight bundle +
                # xprof capture (obs/anomaly.py). /goodput serves the
                # live doc. No-op attribution when tracing is disabled
                # (empty span stream ⇒ zero-wall windows).
                from ..obs.anomaly import AnomalyMonitor
                from ..obs.goodput import GoodputMonitor
                from ..obs.rules import (RuleEngine, goodput_alert_rules,
                                         gray_failure_alert_rules,
                                         rules_check)
                self._goodput = GoodputMonitor(
                    tracer=tracer, registry=reg, store=store,
                    window_s=float(os.environ.get(
                        "DCNN_GOODPUT_WINDOW", "30.0")),
                    samples_per_step=cfg.batch_size,
                    anomaly=AnomalyMonitor(
                        registry=reg,
                        profile_dir=os.environ.get("DCNN_ANOMALY_XPROF"))
                ).attach(srv)
                self._tsdb.add_after_sample(self._goodput.poll)
                engine = RuleEngine(store, registry=reg)
                for rule in goodput_alert_rules():
                    engine.add_alert(rule)
                for rule in gray_failure_alert_rules():
                    engine.add_alert(rule)
                self._tsdb.add_after_sample(lambda s: engine.evaluate())
                srv.add_check("alerts", rules_check(engine))
                print(f"telemetry: {srv.url}/metrics /healthz /snapshot"
                      f" /goodput", flush=True)
            return self._fit_loop(ts, train_loader, val_loader, epochs,
                                  start_epoch, rng, best_val, tracer, reg)
        finally:
            if self._goodput is not None:
                self._goodput.close()  # end any open anomaly xprof capture
                self._goodput = None
            if self._tsdb is not None:
                # detach OUR store only: a later bundle must not dump
                # this dead run's frozen history as if it were current,
                # but another component's newer attachment must survive
                from ..obs import get_flight_recorder
                rec = get_flight_recorder()
                if getattr(rec, "_tsdb", None) is self._tsdb.store:
                    rec.attach_tsdb(None)
                self._tsdb.stop()
                self._tsdb = None
            if self.telemetry is not None:
                self.telemetry.stop()
                self.telemetry = None
            if self.watchdog is not None:
                self.watchdog.stop()
                self.watchdog = None
            if self.checkpoints is not None:
                # abandoning queued async saves would silently lose the
                # newest checkpoint; surface any saver-thread failure here
                self.checkpoints.wait()

    def _fit_loop(self, ts, train_loader, val_loader, epochs, start_epoch,
                  rng, best_val, tracer, reg) -> TrainState:
        cfg = self.config
        for epoch in range(start_epoch, epochs + 1):
            if self.watchdog is not None:
                self.watchdog.beat()
            if hasattr(train_loader, "shuffle"):
                train_loader.shuffle(epoch)
            epoch_rng = jax.random.fold_in(rng, epoch)
            t0 = time.perf_counter()
            with tracer.span("train.epoch", track="train", epoch=epoch):
                ts, train_loss, train_acc = self.train_epoch(
                    ts, train_loader, epoch_rng, epoch)
            dt = time.perf_counter() - t0
            # per-epoch telemetry rollups on the shared registry — O(1),
            # once per epoch, live whether or not tracing is enabled
            n_epoch = self._epoch_samples(train_loader)
            reg.counter("train_epochs_total", "completed epochs").inc()
            if n_epoch:
                reg.counter("train_samples_total",
                            "samples trained on").inc(n_epoch)
                reg.gauge("train_throughput_ips",
                          "last epoch samples/sec").set(n_epoch / dt)
            reg.histogram("train_epoch_seconds",
                          "wall per epoch").observe(dt)
            # epoch-boundary HBM watermark (obs/xla): a latched no-op on
            # backends without memory stats (CPU), gauges + peak elsewhere
            from ..obs.xla import sample_hbm
            sample_hbm(reg)
            reg.gauge("train_lr", "current learning rate").set(
                float(self.lr))
            reg.gauge("train_loss", "last epoch mean train loss").set(
                float(train_loss))

            if self.profiler is not None:
                # One profiled layer-by-layer fwd/bwd per epoch (device-synced
                # per layer — a measurement pass outside the jitted fast path,
                # reference print cadence: print_profiling_summary per run,
                # sequential.hpp:323-418).
                self.profiler.maybe_clear_per_batch()
                from ..data.device_dataset import (
                    DeviceDataset, ShardedDeviceDataset, as_samples)
                _DD = (DeviceDataset, ShardedDeviceDataset)
                if isinstance(train_loader, _DD):
                    # resident mode: profile one decoded batch off the staged
                    # split (augmentation excluded — it's fused in-step there)
                    b = train_loader.batch_size
                    xb = (as_samples(train_loader.x_staged[:b],
                                     train_loader.sample_shape)
                          .astype(jnp.float32) * train_loader.scale)
                    yb = jax.nn.one_hot(train_loader.y[:b],
                                        train_loader.num_classes,
                                        dtype=jnp.float32)
                    batches = [(xb, yb)]
                else:
                    batches = train_loader
                for x, y in batches:
                    # LayerProfiler runs its own untimed warm pass per
                    # (model, shape, dtype, precision) before timing, so one
                    # profiled fwd/bwd here is steady-state.
                    if (self.multi_step is not None
                            and not isinstance(train_loader, _DD)):
                        # chunked loader yields [K, B, ...]: profile one batch
                        x, y = x[0], y[0]
                    from ..data.wire import decode_batch, wire_scale
                    x = decode_batch(jnp.asarray(x), wire_scale(train_loader))
                    logits, _ = self.profiler.profile_forward(
                        self.model, ts.params, ts.state, x,
                        training=True, rng=epoch_rng)
                    grad = jax.grad(
                        lambda out, _y=y: self.loss_fn(
                            out, jnp.asarray(_y)))(logits)
                    self.profiler.profile_backward(
                        self.model, ts.params, ts.state, x, grad,
                        rng=epoch_rng)
                    break
                print(self.profiler.summary(), flush=True)

            val_loss = val_acc = None
            if val_loader is not None:
                with tracer.span("train.eval", track="train", epoch=epoch):
                    val_loss, val_acc = evaluate_classification(
                        self.model, ts.params, ts.state, self.loss_fn,
                        val_loader, eval_step=self.eval_step)
                reg.gauge("train_val_acc", "last validation accuracy").set(
                    float(val_acc))
                # best-val snapshot (reference train.hpp:254-264)
                if cfg.snapshot_dir and val_acc > best_val:
                    best_val = val_acc
                    save_checkpoint(
                        os.path.join(cfg.snapshot_dir, self.model.name),
                        self.model, ts.params, ts.state, ts.opt_state,
                        self.optimizer,
                        {"epoch": epoch, "val_acc": val_acc, "val_loss": val_loss})

            self.history.append({"epoch": epoch, "train_loss": train_loss,
                                 "train_acc": train_acc, "val_loss": val_loss,
                                 "val_acc": val_acc, "seconds": dt, "lr": self.lr})
            msg = (f"epoch {epoch}/{epochs}: train loss {train_loss:.4f} "
                   f"acc {train_acc:.4f}")
            if val_acc is not None:
                msg += f" | val loss {val_loss:.4f} acc {val_acc:.4f}"
            print(msg + f" | {dt:.1f}s lr {self.lr:.2e}", flush=True)

            # LR schedule: scheduler wins; else multiplicative decay
            # (reference train.hpp:282-288). Per-batch schedulers already
            # stepped inside train_epoch.
            if self.scheduler is not None and cfg.scheduler_step == "epoch":
                self.lr = self.scheduler.step(val_loss if val_loss is not None else train_loss)
            elif cfg.lr_decay_factor != 1.0 and epoch % cfg.lr_decay_interval == 0:
                self.lr *= cfg.lr_decay_factor

            # periodic preemption-safe checkpoint (resilience/checkpoint.py),
            # AFTER the lr schedule so the saved lr is exactly what epoch+1
            # trains with — resume replays the uninterrupted run bit-exact.
            # Async mode's only step-loop cost is the device_get snapshot.
            if (self.checkpoints is not None and cfg.checkpoint_every
                    and epoch % cfg.checkpoint_every == 0):
                md = {"epoch": epoch, "lr": float(self.lr),
                      "history": self.history, "best_val": best_val,
                      "global_step": self._global_step}
                # fail fast on an earlier save that already failed — a run
                # whose checkpoints silently rot isn't preemption-safe
                self.checkpoints.check()
                save = (self.checkpoints.save_async if cfg.checkpoint_async
                        else self.checkpoints.save)
                save(epoch, self.model, ts.params, ts.state, ts.opt_state,
                     self.optimizer, md)
        return ts


def _make_regression_eval_step(model: Sequential, loss_fn: Callable):
    from ..core.precision import get_precision_mode
    return _make_regression_eval_step_cached(model, loss_fn, get_precision_mode())


@functools.lru_cache(maxsize=64)
def _make_regression_eval_step_cached(model: Sequential, loss_fn: Callable,
                                      _mode: str):
    @jax.jit
    def eval_step(params, state, x, y):
        pred, _ = model.apply(params, state, x, training=False)
        return loss_fn(pred, y)

    return eval_step


def evaluate_regression(model, params, state, loss_fn, loader) -> float:
    """Mean loss over a regression loader (reference
    ``validate_regression_model``, train.hpp:311-380)."""
    eval_step = _make_regression_eval_step(model, loss_fn)
    total_loss, total_n = 0.0, 0
    for x, y in loader:
        loss = eval_step(params, state, jnp.asarray(x), jnp.asarray(y))
        total_loss += float(loss) * x.shape[0]
        total_n += x.shape[0]
    return total_loss / max(total_n, 1)


def train_regression_model(model: Sequential, optimizer: Optimizer,
                           loss: Callable | str, train_loader, val_loader=None,
                           config: Optional[TrainingConfig] = None,
                           scheduler: Optional[Scheduler] = None,
                           key: Optional[jax.Array] = None) -> Tuple[TrainState, list]:
    """Regression twin of the classification loop (reference
    ``train_regression_model``, train.hpp:389-481)."""
    config = config or TrainingConfig()
    loss_fn = get_loss(loss) if isinstance(loss, str) else loss
    key = key if key is not None else jax.random.PRNGKey(config.seed)
    ts = create_train_state(model, optimizer, key)
    step = make_train_step(model, loss_fn, optimizer, config.num_microbatches)
    lr = config.learning_rate
    history = []
    sched = scheduler
    for epoch in range(1, config.epochs + 1):
        if hasattr(train_loader, "shuffle"):
            train_loader.shuffle(epoch)
        total_loss, total_n = 0.0, 0
        for bi, (x, y) in enumerate(train_loader):
            ts, loss_v, _ = step(ts, jnp.asarray(x), jnp.asarray(y),
                                 jax.random.fold_in(key, epoch * 100003 + bi), lr)
            total_loss += float(loss_v) * x.shape[0]
            total_n += x.shape[0]
        train_loss = total_loss / max(total_n, 1)
        val_loss = (evaluate_regression(model, ts.params, ts.state, loss_fn, val_loader)
                    if val_loader is not None else None)
        history.append({"epoch": epoch, "train_loss": train_loss, "val_loss": val_loss,
                        "lr": lr})
        msg = f"epoch {epoch}/{config.epochs}: train loss {train_loss:.6f}"
        if val_loss is not None:
            msg += f" | val loss {val_loss:.6f}"
        print(msg, flush=True)
        if sched is not None:
            lr = sched.step(val_loss if val_loss is not None else train_loss)
        elif config.lr_decay_factor != 1.0 and epoch % config.lr_decay_interval == 0:
            lr *= config.lr_decay_factor
    return ts, history


def train_classification_model(model: Sequential, optimizer: Optimizer,
                               loss: Callable | str, train_loader,
                               val_loader=None,
                               config: Optional[TrainingConfig] = None,
                               scheduler: Optional[Scheduler] = None,
                               key: Optional[jax.Array] = None) -> Tuple[TrainState, Trainer]:
    """Function-style entry matching the reference's
    ``train_classification_model`` (train.hpp:202)."""
    config = config or TrainingConfig()
    trainer = Trainer(model, optimizer, loss, config, scheduler)
    key = key if key is not None else jax.random.PRNGKey(config.seed)
    ts = create_train_state(model, optimizer, key)
    ts = trainer.fit(ts, train_loader, val_loader)
    return ts, trainer
