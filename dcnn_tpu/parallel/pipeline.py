"""Pipeline parallelism: stages, schedules, in-process coordinator.

Reference equivalent (SURVEY.md §2.4, §3.3-3.4): ``Coordinator`` /
``PipelineStage`` / ``InProcessCoordinator`` — a Sequential is split into
layer-range partitions, each stage holds its partition + optimizer, and
microbatch activations/gradients stream between stages; schedules are
**sync** (all forwards, then all backwards —
``sync_pipeline_coordinator.cpp:120-183``) and **semi-async** (backward
launched per-microbatch as soon as its forward returns —
``Coordinator::async_process_batch``, ``coordinator.hpp:273-326``).

TPU-native mapping:

- A stage = two jitted functions (forward; backward-with-remat) over the
  stage's params, placed on the stage's device. Inter-stage transfer =
  ``jax.device_put`` device-to-device (ICI — no host hop), replacing the
  asio TCP stack + BinarySerializer.
- The reference's per-microbatch layer caches (conv col buffers, pool argmax,
  BN saved stats — SURVEY.md §1 "Microbatch-ID plumbing") become a stored
  ``(input, state, rng)`` per microbatch id; backward **rematerializes** the
  stage forward inside one jit (the TPU-idiomatic memory/compute trade —
  cheaper in HBM than the reference's cache-everything design, and XLA
  overlaps the recompute with ICI transfers).
- Host drives the schedule; since XLA dispatch is async, consecutive
  microbatch launches on different devices overlap exactly like the
  reference's event loops — the host never blocks until results are read.
- Per-stage fwd/bwd wall-clock is tracked like ``LoadTracker``
  (``pipeline_stage.hpp:199-229``).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.fence import hard_fence
from ..nn.sequential import Sequential
from ..obs import get_tracer
from ..ops.losses import LOSSES
from ..ops.metrics import correct_count
from ..optim.optimizers import Optimizer, OptimizerFactory
from .partitioner import NaivePartitioner, Partitioner


class PipelineError(RuntimeError):
    """A stage failed mid-schedule (reference ERROR_REPORT/JOB_FAILURE,
    ``command_type.hpp:48-49``, ``pipeline_stage.hpp:276-282``). Carries
    enough context to identify the failing stage and phase; the coordinator
    aborts the batch (clears caches + partial grads) before re-raising so
    the next batch starts from a consistent state."""

    def __init__(self, stage_id: int, phase: str, mb_id: int, cause: BaseException):
        super().__init__(
            f"stage {stage_id} failed in {phase} (microbatch {mb_id}): {cause!r}")
        self.stage_id = stage_id
        self.phase = phase
        self.mb_id = mb_id


class StageLoadTracker:
    """Per-stage timing telemetry (reference ``LoadTracker``,
    ``load_tracker.hpp``; filled in ``pipeline_stage.hpp:199-229``)."""

    def __init__(self) -> None:
        self.forward_ms = 0.0
        self.backward_ms = 0.0
        self.forward_count = 0
        self.backward_count = 0

    def report(self) -> Dict[str, float]:
        return {
            "avg_forward_ms": self.forward_ms / max(self.forward_count, 1),
            "avg_backward_ms": self.backward_ms / max(self.backward_count, 1),
            "forward_count": self.forward_count,
            "backward_count": self.backward_count,
        }

    def clear(self) -> None:
        self.__init__()


_UNSET = object()


class PipelineStage:
    """One stage: partition model + params/state/opt-state on one device.

    Reference analog: ``PipelineStage`` (``pipeline_stage.hpp:29-309``) whose
    event loop dispatches FORWARD_JOB / BACKWARD_JOB / UPDATE_PARAMETERS; here
    those are the ``forward`` / ``backward`` / ``apply_updates`` methods, and
    "deploy from JSON config" is the ``from_config`` constructor — the same
    LayerFactory path a network worker uses (``pipeline_stage.hpp:231-289``).
    """

    SAMPLE_EVERY = 8

    def __init__(self, stage_id: int, model: Sequential, optimizer: Optimizer,
                 device: Optional[jax.Device] = None,
                 track_load: "bool | str" = False):
        self.stage_id = stage_id
        self.model = model
        self.optimizer = optimizer
        self.device = device
        # Accurate per-stage timing requires blocking on the device result,
        # which defeats cross-stage overlap. Modes:
        #   False    — (default) no tracking, zero fences. Tracking is
        #              opt-in because each fence blocks the host and the
        #              pre-timing backlog drain serializes the stage's
        #              dispatch queue, breaking 1F1B overlap.
        #   "sample" — fence 1 in SAMPLE_EVERY microbatches: load
        #              reports exist in production mode at ~1/8 the overlap
        #              loss (the async-safe proxy VERDICT r1 #8 asks for;
        #              the reference always collects load telemetry,
        #              pipeline_stage.hpp:199-229)
        #   True     — fence every microbatch (exact, kills overlap — the
        #              reference pays the same cost: its stages are
        #              synchronous per message)
        if track_load not in (False, True, "sample"):
            raise ValueError("track_load must be False, True, or 'sample'")
        self.track_load = track_load
        self._fwd_calls = 0
        self._bwd_calls = 0
        self._last_out: Any = None   # most recent dispatch, for join()/fences
        self.params: Any = None
        self.state: Any = None
        self.opt_state: Any = None
        # per-microbatch residuals: mb_id -> (input, state_before, rng)
        self._cache: Dict[int, Tuple[Any, Any, Any]] = {}
        self._grad_acc: Any = None
        self._grad_count = 0
        self.load = StageLoadTracker()
        # most recent microbatch (x, rng, training) — the replay probe for
        # on-demand per-layer profiling (holds ONE extra activation alive;
        # the reference's stages likewise keep per-layer timing state,
        # pipeline_stage.hpp:138-159)
        self._probe: Optional[Tuple[Any, Any, bool]] = None
        self._profiler = None
        self._build_steps()

    # -- deployment --
    @classmethod
    def from_config(cls, stage_id: int, model_cfg: Dict, optimizer_cfg: Dict,
                    device: Optional[jax.Device] = None,
                    track_load: "bool | str" = False) -> "PipelineStage":
        return cls(stage_id, Sequential.from_config(model_cfg),
                   OptimizerFactory.create_from_config(optimizer_cfg), device,
                   track_load=track_load)

    def initialize(self, key: jax.Array, input_shape=None) -> None:
        params, state = self.model.init(key, input_shape)
        self.set_weights(params, state)

    def set_weights(self, params, state, opt_state=None) -> None:
        """Install stage weights. ``opt_state=None`` (fresh deploy) inits
        the optimizer; an explicit ``opt_state`` (pipeline recovery
        re-shipping a restored/gathered commit) is installed as-is so a
        repartition preserves momentum/Adam moments exactly."""
        if self.device is not None:
            params = jax.device_put(params, self.device)
            state = jax.device_put(state, self.device)
            if opt_state is not None:
                opt_state = jax.device_put(opt_state, self.device)
        self.params, self.state = params, state
        self.opt_state = (self.optimizer.init(params) if opt_state is None
                          else opt_state)
        self._grad_acc = jax.tree_util.tree_map(jnp.zeros_like, params)

    def _build_steps(self) -> None:
        model = self.model

        def fwd(params, state, x, rng, training):
            return model.apply(params, state, x, training=training, rng=rng)

        def bwd(params, state, x, rng, g, grad_acc):
            """Recompute forward (remat), vjp against params and input."""
            def f(p, xin):
                y, _ = model.apply(p, state, xin, training=True, rng=rng)
                return y
            _, vjp_fn = jax.vjp(f, params, x)
            pgrads, xgrad = vjp_fn(g)
            new_acc = jax.tree_util.tree_map(jnp.add, grad_acc, pgrads)
            return new_acc, xgrad

        def update(params, opt_state, grad_acc, lr, scale):
            grads = jax.tree_util.tree_map(lambda a: a * scale, grad_acc)
            new_params, new_opt = self.optimizer.update(grads, opt_state, params, lr)
            zero = jax.tree_util.tree_map(jnp.zeros_like, grad_acc)
            return new_params, new_opt, zero

        self._fwd = jax.jit(fwd, static_argnames=("training",))
        self._bwd = jax.jit(bwd, donate_argnums=(5,))
        self._update = jax.jit(update, donate_argnums=(0, 1, 2))

    def _sample_now(self, calls: int) -> bool:
        # sample the 2nd call of each window, not the 1st: the very first
        # call pays jit compilation, which would dominate the average
        return (self.track_load is True
                or (self.track_load == "sample"
                    and calls % self.SAMPLE_EVERY == 2 % self.SAMPLE_EVERY))

    # -- FORWARD_JOB (pipeline_stage.hpp:97-103) --
    def forward(self, mb_id: int, x: jax.Array, rng: Optional[jax.Array] = None,
                training: bool = True) -> jax.Array:
        try:
            if self.device is not None:
                x = jax.device_put(x, self.device)  # inter-stage ICI hop
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            self._fwd_calls += 1
            sample = self._sample_now(self._fwd_calls)
            if sample:
                # drain this stage's backlog (earlier async dispatches) BEFORE
                # starting the clock, or the sampled duration absorbs up to
                # SAMPLE_EVERY-1 queued microbatches and over-reports
                hard_fence((self._last_out, x))
            t0 = time.perf_counter()
            # span on this stage's own track ("stage<i>"): the Perfetto
            # row layout that makes fill/steady/drain bubbles visible.
            # Unsampled spans measure async dispatch issue; sampled ones
            # (fenced below) are device-true — `fenced` says which.
            with get_tracer().span("pipe.fwd", track=f"stage{self.stage_id}",
                                   stage=self.stage_id, mb=mb_id,
                                   fenced=bool(sample)):
                y, new_state = self._fwd(self.params, self.state, x, rng,
                                         training)
                self._probe = (x, rng, training)
                if training:
                    # residuals for backward; BN etc. must see the
                    # pre-update state
                    self._cache[mb_id] = (x, self.state, rng)
                    self.state = new_state
                self._last_out = y
                if sample:
                    hard_fence(y)
                    self.load.forward_ms += (time.perf_counter() - t0) * 1e3
                    self.load.forward_count += 1
            return y
        except PipelineError:
            raise
        except Exception as e:
            raise PipelineError(self.stage_id, "forward", mb_id, e) from e

    # -- BACKWARD_JOB (pipeline_stage.hpp:104-110) --
    def backward(self, mb_id: int, grad: jax.Array) -> jax.Array:
        try:
            if mb_id not in self._cache:
                raise KeyError(
                    f"stage {self.stage_id}: no forward cached for microbatch {mb_id}")
            if self.device is not None:
                grad = jax.device_put(grad, self.device)
            x, state, rng = self._cache.pop(mb_id)
            self._bwd_calls += 1
            sample = self._sample_now(self._bwd_calls)
            if sample:
                # _grad_acc chains through every prior backward dispatch, so
                # fencing it drains the backlog (see forward())
                hard_fence((self._grad_acc, grad))
            t0 = time.perf_counter()
            with get_tracer().span("pipe.bwd", track=f"stage{self.stage_id}",
                                   stage=self.stage_id, mb=mb_id,
                                   fenced=bool(sample)):
                self._grad_acc, xgrad = self._bwd(self.params, state, x, rng,
                                                  grad, self._grad_acc)
                self._grad_count += 1
                self._last_out = xgrad
                if sample:
                    hard_fence(xgrad)
                    self.load.backward_ms += (time.perf_counter() - t0) * 1e3
                    self.load.backward_count += 1
            return xgrad
        except PipelineError:
            raise
        except Exception as e:
            raise PipelineError(self.stage_id, "backward", mb_id, e) from e

    def snapshot_state(self) -> Any:
        """Layer-state snapshot taken at batch start so an aborted batch can
        roll back BN running stats etc. (state trees are immutable pytrees —
        holding the old reference is the snapshot)."""
        return self.state

    def batch_open(self) -> bool:
        """True while a batch is in flight on this stage (cached
        microbatch residuals or accumulated grads pending) — the worker's
        cue that the NEXT forward starts a fresh batch and should snapshot
        layer state for abort rollback."""
        return bool(self._cache) or self._grad_count > 0

    def abort(self, state_snapshot: Any = _UNSET) -> None:
        """Return the stage to a consistent idle state after a failed batch
        (reference: stages drop in-flight jobs and report,
        pipeline_stage.hpp:276-282). Pass the batch-start ``snapshot_state()``
        to also roll back layer state mutated by completed forwards."""
        self.clear_cache()
        self.reset_gradients()
        self._last_out = None
        if state_snapshot is not _UNSET:
            self.state = state_snapshot

    # -- UPDATE_PARAMETERS (pipeline_stage.hpp:111-118) --
    def apply_updates(self, lr: float) -> None:
        if self._grad_count == 0:
            return
        scale = 1.0 / self._grad_count
        self.params, self.opt_state, self._grad_acc = self._update(
            self.params, self.opt_state, self._grad_acc,
            jnp.asarray(lr, jnp.float32), scale)
        self._grad_count = 0

    # -- per-layer profiling (reference PRINT_PROFILING/CLEAR_PROFILING,
    #    coordinator.hpp:384-403, pipeline_stage.hpp:138-159) --
    def collect_profile(self) -> Dict[str, Any]:
        """Per-layer fwd/bwd µs table for this stage's partition.

        The training fast path is a fused jit (per-layer timers inside it
        would be meaningless — XLA fuses across layers), so this replays the
        most recent microbatch through the eager fenced
        :class:`~dcnn_tpu.train.profiling.LayerProfiler` — a profiling run
        at the reference's cost model (its stages time layer-by-layer with
        device syncs too). Replay-vs-fused skew quantified once in
        RESULTS.md "Replay-vs-fused profiling skew" (ResNet-9: Spearman
        rank corr 0.44-0.51 vs the xprof trace; the replay over-credits
        elementwise/BN layers that XLA fuses into convs) — use these
        tables for inter-block load ratios, xprof for true time
        attribution. Repeated calls accumulate (CUMULATIVE mode);
        :meth:`clear_profile` resets. Returns a JSON-serializable dict:
        ``{"stage_id", "layers": [{"name","fwd_us","bwd_us","calls"}, ...]}``
        with empty layers if no microbatch has been processed yet."""
        if self._probe is None or self.params is None:
            return {"stage_id": self.stage_id, "layers": []}
        from ..train.profiling import LayerProfiler
        if self._profiler is None:
            self._profiler = LayerProfiler()
        x, rng, training = self._probe
        prof = self._profiler
        out, _ = prof.profile_forward(self.model, self.params, self.state, x,
                                      training=training, rng=rng)
        prof.profile_backward(self.model, self.params, self.state, x,
                              jnp.ones_like(out), training=training, rng=rng)
        layers = [{"name": l.name,
                   "fwd_us": round(prof.forward_us.get(l.name, 0.0), 1),
                   "bwd_us": round(prof.backward_us.get(l.name, 0.0), 1),
                   "calls": prof.counts.get(l.name, 0)}
                  for l in self.model.layers]
        return {"stage_id": self.stage_id, "layers": layers}

    def clear_profile(self) -> None:
        if self._profiler is not None:
            self._profiler.clear()

    def clear_cache(self) -> None:
        self._cache.clear()

    def reset_gradients(self) -> None:
        """Drop accumulated gradients (abort path: a failed batch must not
        leak partial grads into the next update)."""
        if self.params is not None:
            self._grad_acc = jax.tree_util.tree_map(jnp.zeros_like, self.params)
        self._grad_count = 0


def split_microbatches(x, num_microbatches: int) -> List:
    """Batch → list of microbatches (reference ``split``,
    ``tensor_ops.hpp:193-225``; remainder folded into the last microbatch)."""
    n = x.shape[0]
    if num_microbatches > n:
        raise ValueError(f"more microbatches ({num_microbatches}) than samples ({n})")
    size = n // num_microbatches
    out = []
    for i in range(num_microbatches):
        end = (i + 1) * size if i < num_microbatches - 1 else n
        out.append(x[i * size:end])
    return out


class InProcessPipelineCoordinator:
    """Coordinator owning the full model and the stage chain.

    Reference analog: ``Coordinator`` + ``InProcessCoordinator``
    (``coordinator.hpp:30-600``, ``in_process_coordinator.hpp:17-60``).
    ``deploy_stages()`` splits the model with the partitioner and ships each
    stage *as JSON config* through ``PipelineStage.from_config`` — the same
    contract the reference uses over TCP (``coordinator.hpp:456-571``) — then
    pushes the initialized weights.
    """

    def __init__(self, model: Sequential, optimizer: Optimizer, loss: str,
                 num_stages: int, partitioner: Optional[Partitioner] = None,
                 devices: Optional[Sequence[jax.Device]] = None,
                 num_microbatches: int = 4,
                 track_load: "bool | str" = False):
        self.track_load = track_load
        self.model = model
        self.optimizer = optimizer
        self.loss_name = loss
        self.loss_fn, self.loss_grad_fn = LOSSES[loss.lower()]
        self.num_stages = num_stages
        self.partitioner = partitioner or NaivePartitioner()
        self.num_microbatches = num_microbatches
        if devices is None:
            devs = jax.devices()
            devices = [devs[i % len(devs)] for i in range(num_stages)]
        if len(devices) != num_stages:
            raise ValueError("need one device per stage")
        self.devices = list(devices)
        self.partitions: List[Tuple[int, int]] = []
        self.stages: List[PipelineStage] = []
        self._join_executor = None

        # The initial backward tensor is the TRUE dL/d(output) via autodiff of
        # the loss value — NOT the reference's fused grad kernels
        # (losses.py cross_entropy_grad / log_softmax_cross_entropy_grad),
        # which fold the softmax jacobian in and assume the producing layer's
        # backward is skipped. Here the last stage's backward runs the real
        # vjp through its final layer, so a fused grad would apply the
        # jacobian twice.
        def _lg(pred, tgt):
            loss, grad = jax.value_and_grad(self.loss_fn)(pred, tgt)
            return loss, grad

        self._loss_and_grad = jax.jit(_lg)

    # -- deploy_stages (coordinator.hpp:456-514) --
    def deploy_stages(self, key: jax.Array) -> None:
        self.partitions = self.partitioner.get_partitions(self.model, self.num_stages)
        stage_models = self.model.split(self.partitions)
        # initialize the FULL model once so stage weights match a single-device
        # run exactly (parity with reference: coordinator owns the full model)
        params, state = self.model.init(key)
        sp = self.model.split_params(params, self.partitions)
        ss = self.model.split_params(state, self.partitions)
        self.stages = []
        for sid, (smodel, dev) in enumerate(zip(stage_models, self.devices)):
            # config round-trip — the worker-deployment contract
            stage = PipelineStage.from_config(
                sid, smodel.get_config(), self.optimizer.get_config(), dev,
                track_load=self.track_load)
            stage.set_weights(sp[sid], ss[sid])
            self.stages.append(stage)

    # -- schedules --
    def train_batch_sync(self, x, y, lr: float, rng: Optional[jax.Array] = None,
                         ) -> Tuple[float, jax.Array]:
        """GPipe-style: all microbatch forwards, then all backwards, then one
        update (reference sync_pipeline_coordinator.cpp:99-201)."""
        snap = [s.snapshot_state() for s in self.stages]
        try:
            with get_tracer().span("pipe.batch", track="pipeline",
                                   schedule="sync",
                                   microbatches=self.num_microbatches):
                return self._train_batch_sync(x, y, lr, rng)
        except Exception:
            self.abort_batch(snap)
            raise

    def _train_batch_sync(self, x, y, lr, rng):
        mb_x = split_microbatches(jnp.asarray(x), self.num_microbatches)
        mb_y = split_microbatches(jnp.asarray(y), self.num_microbatches)
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        outputs: List[jax.Array] = []
        for i, mx in enumerate(mb_x):
            h = mx
            for stage in self.stages:
                h = stage.forward(i, h, jax.random.fold_in(rng, i))
            outputs.append(h)

        # keep losses as device scalars until after the schedule has been
        # fully dispatched — float() here would sync and serialize the stages
        losses: List[jax.Array] = []
        for i, (out, my) in enumerate(zip(outputs, mb_y)):
            loss, grad = self._loss_and_grad(out, my)
            losses.append(loss * out.shape[0])
            g = grad
            for stage in reversed(self.stages):
                g = stage.backward(i, g)

        self.update_parameters(lr)
        logits = jnp.concatenate(outputs)
        total_loss = sum(float(l) for l in losses)
        return total_loss / x.shape[0], logits

    def train_batch_semi_async(self, x, y, lr: float,
                               rng: Optional[jax.Array] = None,
                               ) -> Tuple[float, jax.Array]:
        """Semi-async: each microbatch's backward launches as soon as its
        forward output is available (reference ``async_process_batch``,
        coordinator.hpp:273-326). With async XLA dispatch, microbatch i+1's
        forward overlaps microbatch i's backward across stage devices — the
        1F1B overlap the reference gets from its event loops."""
        snap = [s.snapshot_state() for s in self.stages]
        try:
            with get_tracer().span("pipe.batch", track="pipeline",
                                   schedule="semi_async",
                                   microbatches=self.num_microbatches):
                return self._train_batch_semi_async(x, y, lr, rng)
        except Exception:
            self.abort_batch(snap)
            raise

    def _train_batch_semi_async(self, x, y, lr, rng):
        mb_x = split_microbatches(jnp.asarray(x), self.num_microbatches)
        mb_y = split_microbatches(jnp.asarray(y), self.num_microbatches)
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        outputs: List[jax.Array] = []
        losses: List[jax.Array] = []
        for i, (mx, my) in enumerate(zip(mb_x, mb_y)):
            h = mx
            for stage in self.stages:
                h = stage.forward(i, h, jax.random.fold_in(rng, i))
            loss, grad = self._loss_and_grad(h, my)
            outputs.append(h)
            # device scalar only — float() here would block the host and
            # serialize the very overlap this schedule exists to create
            losses.append(loss * h.shape[0])
            g = grad
            for stage in reversed(self.stages):
                g = stage.backward(i, g)

        self.update_parameters(lr)
        logits = jnp.concatenate(outputs)
        total_loss = sum(float(l) for l in losses)
        return total_loss / x.shape[0], logits

    # -- failure handling (reference coordinator.hpp:253-265 timeout joins;
    #    ERROR_REPORT drop-and-reset, pipeline_stage.hpp:276-282) --
    def abort_batch(self, state_snapshots: Optional[List[Any]] = None) -> None:
        """Clear every stage's in-flight microbatch caches and partial grad
        accumulators — and, given the batch-start state snapshots, roll back
        layer state (BN running stats) mutated by the aborted batch's
        completed forwards — so the next batch starts consistent. Called
        automatically when a schedule raises."""
        if state_snapshots is None:
            state_snapshots = [_UNSET] * len(self.stages)
        for stage, snap in zip(self.stages, state_snapshots):
            stage.abort(snap)

    def join(self, timeout: Optional[float] = None) -> bool:
        """Block until all dispatched stage work has completed on-device
        (params, layer state, grad accumulators AND each stage's most recent
        output). With a ``timeout`` (seconds), returns False and warns on
        expiry instead of blocking forever — the analog of the reference's
        cv-based ``join(type, count, timeout)`` (coordinator.hpp:253-265)."""
        trees = [(s.params, s.state, s._grad_acc, s._last_out)
                 for s in self.stages]
        if timeout is None:
            hard_fence(trees)
            return True
        import warnings
        from concurrent.futures import ThreadPoolExecutor
        from concurrent.futures import TimeoutError as FutureTimeout

        # one persistent waiter thread per coordinator: a timed-out fence
        # stays queued on this executor instead of leaking a fresh blocked
        # thread per call
        if self._join_executor is None:
            self._join_executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="pipeline-join")
        fut = self._join_executor.submit(hard_fence, trees)
        try:
            fut.result(timeout=timeout)
            return True
        except FutureTimeout:
            warnings.warn(f"pipeline join timed out after {timeout}s "
                          f"(stages may still be executing)", stacklevel=2)
            return False

    def close(self) -> None:
        """Release the persistent join-waiter thread promptly instead of
        holding it for the rest of the process (an idle worker costs a
        thread + stack until the interpreter's own executor sweep).
        ``wait=False`` so a fence still stuck on a hung dispatch doesn't
        turn teardown into a hang — though note the limit: CPython's
        executor atexit hook joins pool threads regardless, so a
        *wedged* fence can still pin interpreter exit; close() cannot
        fix that, only reclaim the thread in the normal case."""
        if self._join_executor is not None:
            self._join_executor.shutdown(wait=False)
            self._join_executor = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def forward_only(self, x, training: bool = False) -> jax.Array:
        h = jnp.asarray(x)
        for stage in self.stages:
            h = stage.forward(-1, h, training=False)
        return h

    # -- update_parameters broadcast (coordinator.hpp:174-184) --
    def update_parameters(self, lr: float) -> None:
        for stage in self.stages:
            stage.apply_updates(lr)

    # -- load reports (coordinator.hpp:331-379) --
    def collect_load_reports(self) -> List[Dict[str, float]]:
        return [s.load.report() for s in self.stages]

    # -- per-layer profiling (coordinator.hpp:384-403 broadcasts
    #    PRINT_PROFILING/CLEAR_PROFILING to every stage) --
    def collect_profiling(self) -> List[Dict[str, Any]]:
        return [s.collect_profile() for s in self.stages]

    def clear_profiling(self) -> None:
        for s in self.stages:
            s.clear_profile()

    # -- gather weights back (for checkpoint/eval on one device) --
    def gathered_params(self) -> Tuple[Any, Any]:
        params: List[Any] = []
        state: List[Any] = []
        for stage in self.stages:
            params.extend(jax.device_get(stage.params))
            state.extend(jax.device_get(stage.state))
        return tuple(params), tuple(state)


def format_profiling(tables: List[Dict[str, Any]]) -> str:
    """Render per-stage per-layer profile tables (the reference's
    ``print_profiling_summary`` over all stages, coordinator.hpp:384-403).
    Accepts the output of either coordinator's ``collect_profiling()``."""
    lines = [f"{'stage':>5} {'layer':<28} {'fwd µs':>12} {'bwd µs':>12} {'calls':>7}"]
    for t in tables:
        sid = t.get("stage_id", -1)
        rows = t.get("layers", [])
        if not rows:
            lines.append(f"{sid:>5} (no microbatch processed yet)")
            continue
        for r in rows:
            lines.append(f"{sid:>5} {r['name']:<28} {r['fwd_us']:>12.1f} "
                         f"{r['bwd_us']:>12.1f} {r['calls']:>7}")
    return "\n".join(lines)


def train_pipeline_batch_sync(coord: InProcessPipelineCoordinator, x, y, lr,
                              rng=None):
    return coord.train_batch_sync(x, y, lr, rng)


def train_pipeline_epoch(coord: InProcessPipelineCoordinator, loader, lr: float,
                         rng: Optional[jax.Array] = None,
                         schedule: str = "semi_async") -> Tuple[float, float]:
    """Epoch driver (reference ``train_semi_async_epoch`` / ``train_model``,
    ``include/pipeline/train.hpp:14-58,119-136``). Returns (loss, accuracy)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    fn = (coord.train_batch_semi_async if schedule == "semi_async"
          else coord.train_batch_sync)
    total_loss, total_correct, total_n = 0.0, 0, 0
    for bi, (x, y) in enumerate(loader):
        loss, logits = fn(x, y, lr, jax.random.fold_in(rng, bi))
        total_loss += loss * x.shape[0]
        total_correct += int(correct_count(logits, jnp.asarray(y)))
        total_n += x.shape[0]
    return total_loss / max(total_n, 1), total_correct / max(total_n, 1)
