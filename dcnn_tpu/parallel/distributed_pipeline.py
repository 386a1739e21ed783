"""Multi-process pipeline coordinator over TCP stage workers.

Reference equivalent: ``DistributedCoordinator``
(``distributed_coordinator.hpp:26-50``) + the coordinator side of the message
protocol (``coordinator.hpp:30-600``): owns the full model, partitions it,
ships stage configs + weights to worker processes, then drives the sync /
semi-async schedules by streaming microbatches into stage 0 and gradients
into stage N-1.

Same public surface as :class:`~dcnn_tpu.parallel.pipeline.InProcessPipelineCoordinator`
(deploy_stages / train_batch_sync / train_batch_semi_async / forward_only /
collect_load_reports), so trainers swap coordinator classes to go from
single-process to multi-process — and both produce identical numerics, since
workers run the identical ``PipelineStage`` jit functions
(``tests/test_distributed_pipeline.py`` pins this).

Failure semantics — self-healing (ISSUE 13; elastic-DP's contract,
``parallel/elastic.py``, ported to the pipeline leg):

- **Liveness**: workers BEAT every ``PipelineTimeouts.heartbeat_s`` (the
  coordinator beats them back, so a dead coordinator cannot strand a
  worker either — see ``worker.py``). The coordinator convicts a wedged
  or partitioned stage via last-heard + probe-then-convict (silence >
  ``convict_s`` sends one HEALTH_CHECK probe; an unanswered probe past
  ``probe_s`` is a conviction) in seconds instead of waiting out the
  ``batch_s`` deadline; a closed connection (a dead worker's kernel
  cleaning up its sockets) is detected immediately via the reader
  thread's ``on_close``.
- **Recovery** (:class:`StageLostError` → ``_recover``): bump the batch
  generation (fencing both ends), sweep the full original worker address
  list — healthy channels are reused, dead workers get a
  ``respawn_s``-budget reconnect (``resilience.retry`` backoff,
  ``pipeline_reconnect_retry_attempts_total``) so a supervisor-respawned
  worker rejoins, unreachable addresses drop out — then **gather or
  checkpoint-restore** the newest consistent full-model commit:
  if every old stage is still reachable, configured, and at the
  coordinator's batch vintage, its live weights are gathered (a falsely
  convicted wedged worker costs a re-ship, not a rewind); otherwise the
  newest checksum-valid :class:`CheckpointManager` commit (or the
  initial deploy snapshot) is restored. The layer ranges are
  **repartitioned over the surviving workers**, stage configs + weights
  + optimizer state are re-shipped (``pipeline.weight_ship`` fault
  point; the per-stage jits are rebuilt, compiled or loaded from JAX's
  persistent cache), the in-memory **batch journal**
  replays every post-commit batch, and the aborted batch is retried —
  zero lost batches as long as the journal window covers the commit
  cadence.
- **Evidence**: ``pipeline_stage_death`` flight-recorder bundles,
  ``pipeline_generation`` / ``pipeline_stages`` / ``pipeline_recovering``
  gauges, ``pipeline_stages_lost_total`` / ``pipeline_recoveries_total``
  / ``pipeline_stage_respawns_total`` / ``pipeline_replayed_batches_total``
  / ``pipeline_batches_lost_total`` counters,
  ``pipeline_detection_seconds`` / ``pipeline_recovery_seconds``
  histograms, and an ``obs.server.pipeline_check`` adapter that 503s
  ``/healthz`` while a recovery is in flight.

An ERROR_REPORT from a live worker (its own exception — bad input, OOM)
still raises :class:`PipelineWorkerError` after an ``abort()``: a
deterministic remote error must surface, not spin the re-deploy loop.

Gray failure (fail-slow, ISSUE 19; docs/reliability.md §11): a stage that
stays alive but runs 10x slower defeats all of the above — it keeps
beating and answering probes while capping the whole pipeline at its
pace. :meth:`maybe_rebalance` (called between batches) feeds measured
per-stage walls (``collect_load_reports``, needs ``track_load``) into a
shared :class:`~dcnn_tpu.resilience.slowness.SlownessDetector`; a stage
convicted as a *sustained* relative outlier triggers a **rebalance, not
an eviction** (stages are unique — there is no survivor holding the same
layers): live weights are gathered (exact momentum, zero rewind), the
layer ranges are re-split proportional to the measured walls
(:class:`~dcnn_tpu.parallel.partitioner.MeasuredPartitioner`) and
re-shipped through the same generation-fenced machinery as a recovery.
A fleet-wide slowdown moves every stage's wall together — no outlier,
no rebalance. ``pipeline_rebalances_total`` /
``pipeline_stage_imbalance`` + a ``pipeline_rebalance`` flight bundle
are the evidence; the ``pipeline.slow_stage`` delay point
(``FaultPlan.slow``, worker.py dispatch) is the injection hook.
"""

from __future__ import annotations

import collections
import io
import os as _os
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.sequential import Sequential
from ..obs import get_registry, get_tracer
from ..ops.losses import LOSSES
from ..optim.optimizers import Optimizer
from ..resilience import faults as _faults
from ..resilience.slowness import SlownessConfig, SlownessDetector
from .comm import Channel, Inbox, connect, parse_addr
from .partitioner import MeasuredPartitioner, NaivePartitioner, Partitioner


class PipelineWorkerError(RuntimeError):
    """A stage worker reported an exception (reference ERROR_REPORT,
    command_type.hpp:48-49)."""

    def __init__(self, stage_id: int, remote_traceback: str):
        super().__init__(
            f"stage {stage_id} failed remotely:\n{remote_traceback}")
        self.stage_id = stage_id
        self.remote_traceback = remote_traceback


class StageLostError(RuntimeError):
    """A stage worker died (connection closed, send failed, or convicted
    by the heartbeat's probe-then-convict) — the recovery trigger type.
    Distinct from :class:`PipelineWorkerError` (a *live* worker's own
    exception), which is never recovered by re-deploying."""

    def __init__(self, stage_id: int, reason: str):
        super().__init__(f"stage {stage_id} lost: {reason}")
        self.stage_id = stage_id
        self.reason = reason


class PipelineCollapsedError(RuntimeError):
    """Fewer reachable workers than ``min_stages`` after a recovery
    sweep — the operator asked us not to limp on below this floor."""


@dataclass(frozen=True)
class PipelineTimeouts:
    """THE coordinator/worker timeout contract — every wait on either end
    derives from these fields (ISSUE 13 satellite: no more hardcoded
    ``inbox.get(timeout=60.0)`` / drain ``timeout=5.0``).

    - ``batch_s``: end-to-end deadline for any single protocol wait (the
      legacy ``timeout=`` constructor argument maps here).
    - ``heartbeat_s``: BEAT cadence, both directions (workers → coordinator
      and coordinator → workers). ``0`` disables liveness entirely and the
      coordinator degrades to the legacy single-``batch_s`` waits.
    - ``convict_s`` (default ``5 × heartbeat_s``): stage silence before the
      coordinator sends a probe; ``probe_s`` (default ``3 × heartbeat_s``):
      an unanswered probe older than this is a conviction. Detection wall
      is therefore ≤ ``convict_s + probe_s`` for a wedged stage (a closed
      connection is immediate).
    - ``worker_coord_timeout_s`` (default ``convict_s + probe_s``):
      coordinator silence before a worker declares it dead, drops the
      channel, and returns to listening for a replacement coordinator —
      shipped to workers inside CONFIG_TRANSFER so one contract configures
      both ends.
    - ``drain_s`` (default ``max(2 × heartbeat_s, 2.0)``): abort-ack drain
      budget (was the hardcoded 5.0).
    - ``poll_s``: inbox poll granularity while liveness is on.
    - ``connect_s``: bootstrap dial-in budget per worker;
      ``respawn_s``: how long a recovery sweep waits for a dead worker's
      address to come back (a supervisor respawn) before repartitioning
      over the survivors.
    - ``idle_poll_s``: the worker's idle inbox poll when liveness is off
      (was the hardcoded 60.0).
    """

    batch_s: float = 120.0
    heartbeat_s: float = 1.0
    convict_s: Optional[float] = None
    probe_s: Optional[float] = None
    worker_coord_timeout_s: Optional[float] = None
    drain_s: Optional[float] = None
    poll_s: float = 0.05
    connect_s: float = 60.0
    respawn_s: float = 5.0
    idle_poll_s: float = 60.0

    def convict(self) -> float:
        return self.convict_s if self.convict_s is not None \
            else 5.0 * self.heartbeat_s

    def probe(self) -> float:
        return self.probe_s if self.probe_s is not None \
            else 3.0 * self.heartbeat_s

    def coord_timeout(self) -> float:
        return self.worker_coord_timeout_s \
            if self.worker_coord_timeout_s is not None \
            else self.convict() + self.probe()

    def drain(self) -> float:
        return self.drain_s if self.drain_s is not None \
            else max(2.0 * self.heartbeat_s, 2.0)


def _pack_weights(params, state, opt_state=None) -> bytes:
    """One npz blob of (params ‖ state ‖ optional opt_state) leaves —
    the weight-ship wire format. ``n_params``/``n_state`` delimit the
    sections; the receiver unflattens against its own templates
    (:func:`_unpack_weights`)."""
    pl = jax.tree_util.tree_leaves(params)
    sl = jax.tree_util.tree_leaves(state)
    ol = [] if opt_state is None else jax.tree_util.tree_leaves(opt_state)
    buf = io.BytesIO()
    arrays = {f"a{i}": np.asarray(a) for i, a in enumerate(pl + sl + ol)}
    np.savez(buf, n_params=np.int64(len(pl)), n_state=np.int64(len(sl)),
             **arrays)
    return buf.getvalue()


def _unpack_weights(blob: bytes) -> Tuple[List, List, List]:
    """Inverse of :func:`_pack_weights` → (param, state, opt) leaf lists
    (opt empty when the blob carried none)."""
    npz = np.load(io.BytesIO(blob), allow_pickle=False)
    n_leaves = sum(1 for k in npz.files if k.startswith("a"))
    leaves = [npz[f"a{i}"] for i in range(n_leaves)]
    n_params = int(npz["n_params"])
    n_state = int(npz["n_state"]) if "n_state" in npz.files \
        else n_leaves - n_params
    return (leaves[:n_params], leaves[n_params:n_params + n_state],
            leaves[n_params + n_state:])


class DistributedPipelineCoordinator:
    def __init__(self, model: Sequential, optimizer: Optimizer, loss: str,
                 workers: Sequence[str],
                 partitioner: Optional[Partitioner] = None,
                 num_microbatches: int = 4,
                 track_load: "bool | str" = False,
                 compress: "bool | str" = False, timeout: float = 120.0,
                 *, timeouts: Optional[PipelineTimeouts] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 8, checkpoint_keep: int = 3,
                 recover: bool = True, max_recoveries: int = 8,
                 min_stages: int = 1, journal_limit: int = 64,
                 fault_plan: Optional[_faults.FaultPlan] = None,
                 slow_config: Optional[SlownessConfig] = None,
                 flight=None, clock=time.monotonic, registry=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn, _ = LOSSES[loss.lower()]
        self.worker_addrs = list(workers)     # original full list, immutable
        self.active_addrs = list(workers)     # index == current stage id
        self.num_stages = len(self.worker_addrs)
        self.partitioner = partitioner or NaivePartitioner()
        self.num_microbatches = num_microbatches
        self.track_load = track_load
        self.compress = compress
        self.t = timeouts if timeouts is not None \
            else PipelineTimeouts(batch_s=timeout)
        self.timeout = self.t.batch_s
        self.recover = recover
        self.max_recoveries = max_recoveries
        self.min_stages = max(min_stages, 1)
        self.checkpoint_every = checkpoint_every
        self.journal_limit = journal_limit
        if checkpoint_dir:
            from ..resilience.checkpoint import CheckpointManager
            self.checkpoints = CheckpointManager(checkpoint_dir,
                                                 keep=checkpoint_keep)
        else:
            self.checkpoints = None
        self._faults_plan = fault_plan
        self._flight = flight
        self._clock = clock
        self._reg = registry if registry is not None else get_registry()
        self.inbox = Inbox()
        self.chans: List[Channel] = []
        self.partitions: List[Tuple[int, int]] = []
        # batch generation: bumped on abort; both ends drop messages from a
        # dead generation so in-flight stragglers can't poison the next batch
        self._gen = 0
        # completed-batch counter: checkpoint metadata vintage + the
        # journal's replay coordinate
        self._batch = 0
        self._journal: "collections.deque[Dict[str, Any]]" = \
            collections.deque()
        # messages deferred by a buffering join (health_check): consumed by
        # _recv before the socket inbox so they are never lost
        self._deferred = collections.deque()
        # liveness state — shared with comm reader threads (_on_close) and
        # the beat thread, hence the lock
        self._lock = threading.Lock()
        self._live_enabled = self.t.heartbeat_s > 0
        self._chan_sid: Dict[int, int] = {}       # dcnn: guarded_by=_lock
        self._last_heard: Dict[int, float] = {}   # dcnn: guarded_by=_lock
        self._probe_at: Dict[int, float] = {}     # dcnn: guarded_by=_lock
        self._dead: Dict[int, float] = {}         # dcnn: guarded_by=_lock
        self._detections: List[Tuple[int, float]] = []  # dcnn: guarded_by=_lock
        self._closed = False                      # dcnn: guarded_by=_lock
        self._beat_stop = threading.Event()
        self._beat_thread: Optional[threading.Thread] = None
        self.recovering = False
        self._init_weights = None                 # last-resort restore target
        self._tpl_params = None                   # full-model tree templates
        self._tpl_state = None
        # gray-failure rebalance (maybe_rebalance; docs/reliability.md
        # §11): stages are unique, so min_peers relaxes to 2 — the hard
        # rule still holds (a fleet-wide slowdown moves every stage's
        # wall together, leaving no outlier to convict)
        self.slowness = SlownessDetector(
            SlownessConfig.from_env(
                slow_config if slow_config is not None
                else SlownessConfig(min_peers=2)),
            clock=clock)
        self.stats: Dict[str, Any] = {
            "recoveries": 0, "respawns": 0, "detection_s": [],
            "recovery_s": [], "replayed_batches": 0, "batches_lost": 0,
            "rebalances": 0}

        def _lg(pred, tgt):
            return jax.value_and_grad(self.loss_fn)(pred, tgt)

        self._loss_and_grad = jax.jit(_lg)

    # -- plumbing ----------------------------------------------------------
    def _trip(self, point: str, **ctx) -> None:
        if self._faults_plan is not None:
            self._faults_plan.trip(point, **ctx)
        else:
            _faults.trip(point, **ctx)

    @property
    def generation(self) -> int:
        return self._gen

    # -- deploy (reference deploy_stages, coordinator.hpp:456-514) --
    def deploy_stages(self, key: jax.Array) -> None:  # dcnn: protocol=pipe.c2w role=sender
        params, state = self.model.init(key)
        self._tpl_params, self._tpl_state = params, state
        opt0 = self.optimizer.init(params)
        # host-side snapshot: the restore target for a loss before the
        # first checkpoint commit (batch-0 vintage)
        self._init_weights = jax.device_get(
            {"p": params, "s": state, "o": opt0})

        alive: List[Tuple[str, Channel]] = []
        for addr in self.worker_addrs:
            host, port = parse_addr(addr)
            chan = connect(host, port, timeout=self.t.connect_s,
                           compress=self.compress)
            chan.send("HELLO", {"role": "coordinator"})
            if self._live_enabled:
                chan.set_send_timeout(self.t.convict() + self.t.probe())
            self.inbox.attach(chan, on_close=self._on_close)
            alive.append((addr, chan))
        self._install_workers(alive)
        self._ship_stages(params, state, None)
        self._start_beat()

    def _install_workers(self, alive: List[Tuple[str, Channel]]) -> None:
        """Adopt (addr, chan) as the current stage set (index == stage id)
        and reset the liveness tables for the new generation of workers."""
        self.active_addrs = [a for a, _ in alive]
        self.chans = [c for _, c in alive]
        self.num_stages = len(self.chans)
        with self._lock:
            now = self._clock()
            self._chan_sid = {id(c): i for i, c in enumerate(self.chans)}
            self._last_heard = {i: now for i in range(len(self.chans))}
            self._probe_at = {}
            self._dead = {}
        self._reg.gauge("pipeline_stages",
                        "current pipeline stage count").set(self.num_stages)
        self._reg.gauge("pipeline_generation",
                        "current pipeline batch generation").set(self._gen)

    def _ship_stages(self, params, state,
                     opt_state) -> None:  # dcnn: protocol=pipe.c2w role=sender
        """(Re)partition over the current worker set and ship stage
        configs + weights (+ optimizer state on a recovery re-ship — a
        repartition preserves momentum exactly via
        ``Optimizer.split_state``). The ``pipeline.weight_ship`` fault
        point fires per stage pre-send: armed with ``exc=OSError`` it is
        the torn-weight-ship simulation (recovery re-enters
        idempotently)."""
        self.partitions = self.partitioner.get_partitions(self.model,
                                                          self.num_stages)
        stage_models = self.model.split(self.partitions)
        sp = self.model.split_params(params, self.partitions)
        ss = self.model.split_params(state, self.partitions)
        so = (self.optimizer.split_state(opt_state, self.partitions)
              if opt_state is not None else [None] * self.num_stages)
        for sid in range(self.num_stages):
            blob = _pack_weights(sp[sid], ss[sid], so[sid])
            meta = {
                "stage_id": sid,
                "is_first": sid == 0,
                "is_last": sid == self.num_stages - 1,
                # the layer range this stage holds: echoed back in WEIGHTS
                # so a gather can PROVE the worker's partitioning matches
                # the coordinator's (an interrupted re-ship can leave them
                # disagreeing — such a gather must restore, not assemble)
                "layers": list(self.partitions[sid]),
                "model": stage_models[sid].get_config(),
                "optimizer": self.optimizer.get_config(),
                "track_load": self.track_load,
                "next_addr": (self.active_addrs[sid + 1]
                              if sid < self.num_stages - 1 else None),
                "gen": self._gen,
                "batch": self._batch,
                "heartbeat_s": self.t.heartbeat_s,
                "coord_timeout_s": (self.t.coord_timeout()
                                    if self._live_enabled else 0.0),
                # next-hop dial budget: fail-fast under liveness (the
                # coordinator just verified the chain; a hop dying inside
                # this window re-enters recovery via ERROR_REPORT),
                # bootstrap-generous otherwise
                "connect_s": (max(self.t.respawn_s, 2.0)
                              if self._live_enabled else self.t.connect_s),
            }
            try:
                self._trip("pipeline.weight_ship", stage=sid)
                self.chans[sid].send("CONFIG_TRANSFER", meta, raw=blob)
            except OSError as e:
                self._mark_dead(sid, f"weight ship failed: {e}")
                raise StageLostError(sid, f"weight ship failed: {e}") from e
        self._join("CONFIG_RECEIVED", self.num_stages, buffer_others=True)

    # -- liveness ----------------------------------------------------------
    def _on_close(self, chan: Channel) -> None:
        with self._lock:
            sid = self._chan_sid.get(id(chan))
            if sid is None or self._closed or sid in self._dead:
                return
            now = self._clock()
            self._dead[sid] = now
            self._detections.append(
                (sid, now - self._last_heard.get(sid, now)))
        self._reg.counter("pipeline_stages_lost_total",
                          "pipeline stage workers lost").inc()

    def _mark_dead(self, sid: int, reason: str) -> None:
        with self._lock:
            if sid in self._dead or self._closed:
                return
            now = self._clock()
            self._dead[sid] = now
            self._detections.append(
                (sid, now - self._last_heard.get(sid, now)))
        self._reg.counter("pipeline_stages_lost_total",
                          "pipeline stage workers lost").inc()

    def _heard(self, chan: Optional[Channel]) -> None:
        if chan is None or not getattr(self, "_live_enabled", False):
            return
        with self._lock:
            sid = self._chan_sid.get(id(chan))
            if sid is not None:
                self._last_heard[sid] = self._clock()
                self._probe_at.pop(sid, None)

    def _check_liveness(self) -> None:  # dcnn: protocol=pipe.c2w role=sender
        """Probe-then-convict (the elastic/router pattern): silence past
        ``convict_s`` sends one HEALTH_CHECK probe; a probe unanswered for
        ``probe_s`` convicts. A closed connection (``_on_close``) or a
        failed send is immediate. Raises :class:`StageLostError` for the
        first dead stage found."""
        if not getattr(self, "_live_enabled", False):
            return
        probes: List[int] = []
        lost: Optional[Tuple[int, str]] = None
        convicted = False  # True iff THIS call moved sid into _dead —
        #                    the counter increments exactly once per loss,
        #                    at whichever site did the insertion
        with self._lock:
            now = self._clock()
            for sid in range(len(self.chans)):
                if sid in self._dead:
                    lost = (sid, "connection closed or send failed")
                    break
                silent = now - self._last_heard.get(sid, now)
                probed = self._probe_at.get(sid)
                if probed is not None and now - probed > self.t.probe():
                    self._dead[sid] = now
                    self._detections.append((sid, silent))
                    convicted = True
                    lost = (sid, f"unanswered probe after {silent:.2f}s "
                                 f"of silence")
                    break
                if probed is None and silent > self.t.convict():
                    self._probe_at[sid] = now
                    probes.append(sid)
        if lost is not None:
            if convicted:
                self._reg.counter("pipeline_stages_lost_total",
                                  "pipeline stage workers lost").inc()
            raise StageLostError(*lost)
        for sid in probes:
            # nonce "probe": _recv drops the ack after refreshing
            # last-heard — which is the whole point of the probe
            try:
                self.chans[sid].send("HEALTH_CHECK", {"nonce": "probe"},
                                     attempts=1)
            except OSError as e:
                self._mark_dead(sid, f"probe send failed: {e}")
                raise StageLostError(sid, f"probe send failed: {e}") from e

    def _beat_targets(self) -> List[Channel]:
        with self._lock:
            return [c for i, c in enumerate(self.chans)
                    if i not in self._dead]

    def _start_beat(self) -> None:
        """Coordinator → worker BEATs: what the workers' own
        dead-coordinator conviction (``worker_coord_timeout_s``) listens
        for. Daemon thread, stopped + joined by :meth:`shutdown`."""
        if not self._live_enabled or self._beat_thread is not None:
            return
        # fresh Event per thread: shutdown() sets the old one, and a
        # coordinator redeployed after shutdown() must actually beat
        self._beat_stop = threading.Event()
        stop = self._beat_stop

        def loop() -> None:  # dcnn: protocol=pipe.c2w role=sender
            while not stop.wait(self.t.heartbeat_s):
                for ch in self._beat_targets():
                    try:
                        ch.send("BEAT", {"gen": self._gen}, attempts=1)
                    except OSError:
                        pass  # reader on_close / next probe handles it
        self._beat_thread = threading.Thread(
            target=loop, daemon=True, name="dcnn-pipe-coord-beat")
        self._beat_thread.start()

    # -- fenced receive: drops messages from aborted generations --
    # dcnn: protocol=pipe.w2c role=handler
    def _recv(self) -> Tuple[str, Dict, Any]:
        clock = getattr(self, "_clock", time.monotonic)
        deadline = clock() + self.timeout
        while True:
            if self._deferred:
                c, meta, payload = self._deferred.popleft()
            else:
                self._check_liveness()
                poll = (self.t.poll_s
                        if getattr(self, "_live_enabled", False)
                        else self.timeout)
                try:
                    c, meta, payload, chan = self.inbox.get(
                        timeout=min(poll, max(deadline - clock(), 1e-3)))
                except TimeoutError:
                    if clock() >= deadline:
                        raise TimeoutError(
                            f"no message within {self.timeout}s") from None
                    continue
                self._heard(chan)
                if c == "BEAT":
                    continue
            # fence only messages that actually carry a generation: an
            # ERROR_REPORT from a gen-less command (CONFIG_TRANSFER,
            # UPDATE_PARAMETERS) has gen=None and must never be dropped
            if c == "ABORTED":
                # only abort()'s own drain consumes these from the inbox;
                # one reaching _recv is a leftover from a drain that
                # under-counted (a dead-marked worker that was actually
                # alive still acks) — never a join's business
                continue
            g = meta.get("gen")
            if c in ("FORWARD_RESULT", "BACKWARD_DONE", "ERROR_REPORT",
                     "CONFIG_RECEIVED", "PARAMETERS_UPDATED") and \
                    g is not None and g != self._gen:
                # straggler from a dead batch — or a stale deploy/update
                # ack from before a recovery's abort bumped the
                # generation, which must never satisfy the NEW join
                continue
            if c == "HEALTH_ACK" and \
                    meta.get("nonce") != getattr(self, "_health_nonce", None):
                # straggler from a timed-out/previous health_check or a
                # liveness probe: outside a probe (_health_nonce None) or
                # with a stale nonce, drop it — it already refreshed
                # last-heard above, which is all a probe ack is for
                continue
            if c == "WEIGHTS" and \
                    meta.get("nonce") != getattr(self, "_gather_nonce", None):
                continue  # straggler from a timed-out gather round
            if c in ("PROFILING_REPORT", "PROFILING_CLEARED") and \
                    meta.get("nonce") != getattr(self, "_profiling_nonce", None):
                continue  # same staleness fence for profiling replies
            if c == "LOAD_REPORT" and \
                    meta.get("nonce") != getattr(self, "_load_nonce", None):
                # straggler from a timed-out load-report round: an old
                # reply satisfying a later join would hand the balancer
                # a stale per-stage timing table (PR02 unfenced-stamp)
                continue
            if c == "ERROR_REPORT":
                self.abort()
                raise PipelineWorkerError(meta.get("stage_id", -1),
                                          meta.get("error", "?"))
            return c, meta, payload

    # -- cv-join analog (coordinator.hpp:253-265) --
    def _join(self, cmd: str, count: int,
              buffer_others: bool = False) -> List[Tuple[Dict, Any]]:
        """Collect ``count`` messages of kind ``cmd``. With
        ``buffer_others`` (the out-of-band joins: health probes, weight
        gathers, config acks), messages of any other kind are deferred for
        the next join instead of treated as protocol errors — a probe
        racing an in-flight batch message must not drop it (ADVICE r3 #3).
        Deferred messages re-enter through _recv, so generation fencing
        still applies when they are finally consumed."""
        got: List[Tuple[Dict, Any]] = []
        deferred: List[Tuple[str, Dict, Any]] = []
        try:
            while len(got) < count:
                c, meta, payload = self._recv()
                if c == cmd:
                    got.append((meta, payload))
                elif buffer_others:
                    deferred.append((c, meta, payload))
                else:
                    raise RuntimeError(f"expected {cmd}, got {c}")
        finally:
            self._deferred.extend(deferred)
        return got

    def _send_stage(self, sid: int, cmd: str,
                    meta: Optional[Dict[str, Any]] = None,
                    array: Optional[np.ndarray] = None,
                    raw: Optional[bytes] = None) -> None:
        """Send to stage ``sid``; a failed (post-retry) send marks the
        stage dead and raises :class:`StageLostError`."""
        try:
            self.chans[sid].send(cmd, meta, array=array, raw=raw)
        except OSError as e:
            self._mark_dead(sid, f"send {cmd} failed: {e}")
            raise StageLostError(sid, f"send {cmd} failed: {e}") from e

    def _first_sid(self) -> int:
        return 0

    def _last_sid(self) -> int:
        return self.num_stages - 1

    # -- schedules (mirror InProcessPipelineCoordinator) --
    # dcnn: protocol=pipe.c2w role=sender
    def _send_forward(self, mb_id: int, x: np.ndarray, rng: jax.Array,
                      training: bool = True) -> None:
        key_data = (np.asarray(rng) if rng.dtype == np.uint32
                    else np.asarray(jax.random.key_data(rng)))
        self._send_stage(self._first_sid(), "FORWARD_JOB", {
            "mb_id": mb_id,
            "gen": self._gen,
            "rng": key_data.tolist(),
            "training": training,
        }, array=x)

    def train_batch_sync(self, x, y, lr: float,
                         rng: Optional[jax.Array] = None
                         ) -> Tuple[float, np.ndarray]:
        return self._train_batch(x, y, lr, rng, "sync")

    def train_batch_semi_async(self, x, y, lr: float,
                               rng: Optional[jax.Array] = None,
                               ) -> Tuple[float, np.ndarray]:
        """Backward dispatched per-microbatch the moment its forward result
        arrives (reference ``async_process_batch``, coordinator.hpp:273-326);
        later microbatches' forwards are already in flight downstream."""
        return self._train_batch(x, y, lr, rng, "semi_async")

    def _train_batch(self, x, y, lr, rng, schedule: str
                     ) -> Tuple[float, np.ndarray]:
        x, y = np.asarray(x), np.asarray(y)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        fn = (self._batch_sync if schedule == "sync"
              else self._batch_semi_async)
        out = self._with_recovery(lambda: fn(x, y, lr, rng))
        self._batch += 1
        self._journal_append(x, y, lr, rng, schedule)
        if (self.checkpoints is not None and self.checkpoint_every > 0
                and self._batch % self.checkpoint_every == 0):
            # a stage death during the commit gather re-enters recovery
            # (which replays this batch from the journal) and retries the
            # COMMIT, never the already-applied batch
            self._with_recovery(self._commit)
        return out

    # dcnn: protocol=pipe.c2w role=sender
    def _batch_sync(self, x, y, lr, rng,
                    bno: Optional[int] = None) -> Tuple[float, np.ndarray]:
        from .pipeline import split_microbatches

        bno = bno if bno is not None else self._batch + 1
        mb_x = split_microbatches(x, self.num_microbatches)
        mb_y = split_microbatches(y, self.num_microbatches)
        for i, mx in enumerate(mb_x):
            self._send_forward(i, mx, jax.random.fold_in(rng, i))
        results = self._join("FORWARD_RESULT", len(mb_x))
        outputs: Dict[int, np.ndarray] = {m["mb_id"]: p for m, p in results}

        total_loss = 0.0
        for i, my in enumerate(mb_y):
            loss, grad = self._loss_and_grad(jnp.asarray(outputs[i]),
                                             jnp.asarray(my))
            total_loss += float(loss) * my.shape[0]
            self._send_stage(self._last_sid(), "BACKWARD_JOB",
                             {"mb_id": i, "gen": self._gen},
                             array=np.asarray(grad))
        self._join("BACKWARD_DONE", len(mb_x))
        self.update_parameters(lr, batch=bno)
        logits = np.concatenate([outputs[i] for i in range(len(mb_x))])
        return total_loss / x.shape[0], logits

    # dcnn: protocol=pipe.c2w role=sender
    def _batch_semi_async(self, x, y, lr, rng,
                          bno: Optional[int] = None
                          ) -> Tuple[float, np.ndarray]:
        from .pipeline import split_microbatches

        bno = bno if bno is not None else self._batch + 1
        mb_x = split_microbatches(x, self.num_microbatches)
        mb_y = split_microbatches(y, self.num_microbatches)
        outputs: Dict[int, np.ndarray] = {}
        total_loss = 0.0
        backwards_done = 0
        for i, mx in enumerate(mb_x):
            self._send_forward(i, mx, jax.random.fold_in(rng, i))

        while backwards_done < len(mb_x):
            cmd, meta, payload = self._recv()
            if cmd == "FORWARD_RESULT":
                i = meta["mb_id"]
                outputs[i] = payload
                loss, grad = self._loss_and_grad(jnp.asarray(payload),
                                                 jnp.asarray(mb_y[i]))
                total_loss += float(loss) * mb_y[i].shape[0]
                self._send_stage(self._last_sid(), "BACKWARD_JOB",
                                 {"mb_id": i, "gen": self._gen},
                                 array=np.asarray(grad))
            elif cmd == "BACKWARD_DONE":
                backwards_done += 1
            else:
                raise RuntimeError(
                    f"unexpected {cmd} during semi-async batch")
        self.update_parameters(lr, batch=bno)
        logits = np.concatenate([outputs[i] for i in range(len(mb_x))])
        return total_loss / x.shape[0], logits

    def forward_only(self, x) -> np.ndarray:
        x = np.asarray(x)

        def run():
            self._send_forward(-1, x, jax.random.PRNGKey(0), training=False)
            [(m, payload)] = self._join("FORWARD_RESULT", 1)
            return payload
        return self._with_recovery(run)

    # -- parameter update broadcast (coordinator.hpp:174-184) --
    # dcnn: protocol=pipe.c2w role=sender
    def update_parameters(self, lr: float, batch: Optional[int] = None
                          ) -> None:
        for sid in range(self.num_stages):
            meta = {"lr": float(lr)}
            if batch is not None:
                meta["batch"] = int(batch)
            self._send_stage(sid, "UPDATE_PARAMETERS", meta)
        self._join("PARAMETERS_UPDATED", self.num_stages)

    # -- load reports (coordinator.hpp:331-379) --
    def collect_load_reports(self) -> List[Dict[str, float]]:  # dcnn: protocol=pipe.c2w role=sender
        """Nonce-fenced like the profiling/gather rounds: a LOAD_REPORT
        straggler from a timed-out earlier round must never satisfy a
        later join with a stale timing table."""
        nonce = int.from_bytes(_os.urandom(4), "little")
        self._load_nonce = nonce
        try:
            for sid in range(self.num_stages):
                self._send_stage(sid, "LOAD_REPORT_REQUEST",
                                 {"nonce": nonce})
            got = self._join("LOAD_REPORT", self.num_stages,
                             buffer_others=True)
        finally:
            self._load_nonce = None
        by_stage = {m["stage_id"]: m["report"] for m, _ in got}
        return [by_stage[i] for i in range(self.num_stages)]

    # -- gray-failure rebalance (resilience/slowness.py; ISSUE 19) --
    def stage_walls(self) -> List[float]:
        """Measured per-stage wall (avg fwd + bwd ms) from one
        load-report round — the rebalance cost signal. Needs
        ``track_load`` on the stages; unmeasured stages report 0."""
        reports = self.collect_load_reports()
        return [float(r.get("avg_forward_ms", 0.0))
                + float(r.get("avg_backward_ms", 0.0)) for r in reports]

    def maybe_rebalance(self) -> bool:
        """Gray-failure mitigation for the pipeline leg: poll measured
        per-stage walls into the shared slowness detector and, once a
        stage is convicted as a *sustained* relative outlier (probation
        → convict with dwell, docs/reliability.md §11), repartition the
        layer ranges proportional to the measured walls through the
        recovery machinery — gather live weights (exact momentum, zero
        rewind), re-ship under :class:`MeasuredPartitioner`. Rebalance,
        never evict: stages are unique. Call between batches (buffering
        joins). Returns True iff a rebalance actually shipped."""
        walls = self.stage_walls()
        measured = [w for w in walls if w > 0.0]
        if measured:
            s = sorted(measured)
            mid = len(s) // 2
            med = s[mid] if len(s) % 2 else 0.5 * (s[mid - 1] + s[mid])
            self._reg.gauge(
                "pipeline_stage_imbalance",
                "max/median measured per-stage wall ratio").set(
                    max(measured) / med if med > 0 else 0.0)
        for sid, w in enumerate(walls):
            if w > 0.0:
                self.slowness.observe(f"stage{sid}", w)
        convicted = [tr for tr in self.slowness.evaluate()
                     if tr["to"] == "convicted"]
        if not convicted:
            return False
        from ..obs.flight import resolve_flight_recorder
        resolve_flight_recorder(self._flight).record(
            "pipeline_rebalance",
            reasons=[f"{tr['component']} wall {tr['ewma']:.2f}ms vs "
                     f"fleet median {tr['median']:.2f}ms — sustained "
                     f"outlier" for tr in convicted],
            config={"generation": self._gen, "batch": self._batch,
                    "stages": self.num_stages,
                    "partitions": [list(p) for p in self.partitions]},
            extra={"walls_ms": walls,
                   "slowness": self.slowness.snapshot()},
            registry=self._reg)
        ok = self._with_recovery(lambda: self._do_rebalance(walls))
        if ok:
            self.stats["rebalances"] += 1
            self._reg.counter(
                "pipeline_rebalances_total",
                "gray-failure layer-range rebalances shipped").inc()
            # the partitioning changed: every stage's wall now means
            # something new, so the old scores must not linger
            for sid in range(len(walls)):
                self.slowness.forget(f"stage{sid}")
        return ok

    def _do_rebalance(self, walls: List[float]) -> bool:
        """One rebalance attempt: runs inside ``_with_recovery`` so a
        stage dying mid-gather re-enters the normal recovery (which
        replays the journal) and then retries this. No journal replay
        here — the gathered weights are at the current batch vintage."""
        part = MeasuredPartitioner(self.partitions, walls)
        new_parts = part.get_partitions(self.model, self.num_stages)
        if new_parts == self.partitions:
            return False  # layer granularity can't improve on this split
        replies = self._gather_stage_blobs()
        full = self._assemble_full(replies, self.partitions,
                                   expect_batch=self._batch)
        if full is None:
            return False  # inconsistent gather between batches: never guess
        params, state, opt = full
        self.abort()  # gen bump: same straggler fence as a recovery
        # keep the measured cost model installed: later recoveries (and
        # their repartitions over fewer workers) reuse the best-known
        # per-layer walls instead of reverting to FLOP estimates
        self.partitioner = part
        self._ship_stages(params, state, opt)
        return True

    # -- per-layer profiling broadcast (coordinator.hpp:384-403) --
    # dcnn: protocol=pipe.c2w role=sender frames=PRINT_PROFILING,CLEAR_PROFILING
    def _profiling_round(self, request: str,
                         reply: str) -> List[Tuple[Dict, Any]]:
        """One nonce-fenced broadcast/join: like HEALTH_CHECK, a reply from a
        timed-out earlier round must never satisfy a later join or leak into
        a batch join (``_recv`` drops ``reply`` messages whose nonce is not
        the currently-armed one)."""
        nonce = int.from_bytes(_os.urandom(4), "little")
        self._profiling_nonce = nonce
        try:
            for sid in range(self.num_stages):
                self._send_stage(sid, request, {"nonce": nonce})
            return self._join(reply, self.num_stages, buffer_others=True)
        finally:
            self._profiling_nonce = None

    def collect_profiling(self) -> List[Dict[str, Any]]:
        """Broadcast PRINT_PROFILING; every worker replays its latest
        microbatch through the fenced per-layer profiler and returns its
        table. Ordered by stage. Run between batches (uses a buffering join,
        so a straggling batch message is deferred, not dropped)."""
        got = self._profiling_round("PRINT_PROFILING", "PROFILING_REPORT")
        by_stage = {m["stage_id"]: m["profile"] for m, _ in got}
        return [by_stage[i] for i in range(self.num_stages)]

    def clear_profiling(self) -> None:
        self._profiling_round("CLEAR_PROFILING", "PROFILING_CLEARED")

    # -- weight gather (the pipeline analog of elastic's shared commit) --
    def _gather_stage_blobs(self) -> List[Tuple[Dict, Any]]:  # dcnn: protocol=pipe.c2w role=sender
        """Nonce-fenced GATHER_WEIGHTS broadcast over the current
        channels; returns the WEIGHTS replies (meta carries stage_id /
        configured / batch vintage)."""
        nonce = int.from_bytes(_os.urandom(4), "little")
        self._gather_nonce = nonce
        try:
            for sid in range(len(self.chans)):
                self._send_stage(sid, "GATHER_WEIGHTS", {"nonce": nonce})
            return self._join("WEIGHTS", len(self.chans),
                              buffer_others=True)
        finally:
            self._gather_nonce = None

    def _assemble_full(self, replies: List[Tuple[Dict, Any]],
                       partitions: List[Tuple[int, int]],
                       expect_batch: Optional[int]
                       ) -> Optional[Tuple[Any, Any, Any]]:
        """Rebuild full-model (params, state, opt_state) from per-stage
        WEIGHTS blobs, or None when the stage set is incomplete,
        unconfigured, or at a mixed batch vintage (a mid-update death) —
        the caller then falls back to the checkpoint restore."""
        by_sid: Dict[int, Tuple[Dict, Any]] = {}
        for meta, payload in replies:
            if not meta.get("configured"):
                return None
            by_sid[meta["stage_id"]] = (meta, payload)
        if set(by_sid) != set(range(len(partitions))):
            return None
        vintages = {m.get("batch") for m, _ in by_sid.values()}
        if expect_batch is not None and vintages != {expect_batch}:
            return None
        # the workers must hold EXACTLY the partitioning we're assembling
        # against — an interrupted re-ship leaves a worker on a different
        # layer range, and that gather must restore, not assemble
        for sid, (start, end) in enumerate(partitions):
            if by_sid[sid][0].get("layers") != [start, end]:
                return None
        sp = self.model.split_params(self._tpl_params, partitions)
        ss = self.model.split_params(self._tpl_state, partitions)
        params_leaves: List[Any] = []
        state_leaves: List[Any] = []
        stage_opts: List[Any] = []
        for sid in range(len(partitions)):
            _meta, blob = by_sid[sid]
            pl, sl, ol = _unpack_weights(blob)
            tp, ts = sp[sid], ss[sid]
            try:
                params_leaves.append(jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(tp), pl))
                state_leaves.append(jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(ts), sl))
                to = self.optimizer.init(tp)
                stage_opts.append(jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(to), ol) if ol else to)
            except ValueError:
                return None  # structural mismatch: not assemblable
        params = tuple(p for stage in params_leaves for p in stage)
        state = tuple(s for stage in state_leaves for s in stage)
        opt = self.optimizer.merge_state(stage_opts, partitions)
        return params, state, opt

    def gathered_params(self) -> Tuple[Any, Any]:
        """(params, state) of the full model gathered live from the
        workers — mirror of
        ``InProcessPipelineCoordinator.gathered_params``."""
        replies = self._gather_stage_blobs()
        full = self._assemble_full(replies, self.partitions,
                                   expect_batch=None)
        if full is None:
            raise RuntimeError("workers returned an incomplete or "
                               "unconfigured stage set")
        return full[0], full[1]

    def _commit(self) -> None:
        """Gather the live full-model weights and commit them atomically
        via :class:`CheckpointManager` (metadata carries the batch
        vintage); trim the journal to one extra commit window (insurance
        against a corrupt newest commit)."""
        with get_tracer().span("pipe.commit", track="pipeline",
                               batch=self._batch):
            replies = self._gather_stage_blobs()
            full = self._assemble_full(replies, self.partitions,
                                       expect_batch=self._batch)
            if full is None:
                raise RuntimeError(
                    "weight gather at checkpoint cadence returned an "
                    "inconsistent stage set")
            params, state, opt = full
            self.checkpoints.save(
                self._batch, self.model, params, state, opt,
                self.optimizer,
                {"batch": self._batch, "gen": self._gen,
                 "stages": self.num_stages})
        floor = self._batch - max(self.checkpoint_every, 1)
        while self._journal and self._journal[0]["batch"] <= floor:
            self._journal.popleft()

    def _journal_append(self, x, y, lr, rng, schedule: str) -> None:
        # own copies: a driver reusing one preallocated staging buffer per
        # step would otherwise alias every journal entry to the newest
        # batch, silently corrupting the replay's identical-inputs
        # contract
        self._journal.append({"batch": self._batch,
                              "x": np.array(x, copy=True),
                              "y": np.array(y, copy=True),
                              "lr": lr, "rng": rng, "schedule": schedule})
        while len(self._journal) > self.journal_limit:
            self._journal.popleft()
            self._reg.counter(
                "pipeline_journal_dropped_total",
                "journaled batches dropped past journal_limit — a "
                "recovery past this horizon loses batches").inc()

    # -- recovery ----------------------------------------------------------
    def _with_recovery(self, fn):
        """Run one protocol unit; on :class:`StageLostError` recover and
        retry it. A second loss *during* recovery re-enters the recovery
        loop with the shrunken worker set (idempotent — the generation is
        re-bumped and the sweep/restore/re-ship/replay sequence re-runs).
        A live worker's own exception (:class:`PipelineWorkerError`) and
        the legacy timeout path keep their abort-and-raise semantics."""
        attempt = 0
        while True:
            try:
                return fn()
            except PipelineWorkerError:
                raise  # _recv already aborted; the worker is alive
            except StageLostError as e:
                err: Exception = e
            except (TimeoutError, RuntimeError, OSError) as e:
                self.abort()
                raise
            while True:
                attempt += 1
                if not self.recover or attempt > self.max_recoveries:
                    try:
                        self.abort()
                    except OSError:
                        pass
                    raise err
                try:
                    self._recover(err)
                    break
                except (StageLostError, PipelineWorkerError) as again:
                    # double fault mid-recovery (a second death, or a
                    # worker error during the re-ship — e.g. its next-hop
                    # dial found the hop dead): idempotent re-entry with
                    # the shrunken set, bounded by max_recoveries
                    err = again
                except (TimeoutError, RuntimeError, OSError):
                    # a non-recoverable failure inside recovery (a join
                    # deadline on a wedged-but-beating stage, a protocol
                    # surprise, PipelineCollapsedError) must not leave
                    # stages holding the half-replayed batch's residuals
                    # — same abort-then-raise contract as the direct path
                    try:
                        self.abort()
                    except OSError:
                        pass
                    raise

    def _recover(self, err: Exception) -> None:
        """Survive a stage loss: fence the dead batch, rebuild the worker
        set (reuse survivors, respawn-or-drop the dead), gather-or-restore
        the newest consistent full-model commit, repartition + re-ship,
        replay the journal. See the module docstring for the protocol."""
        t0 = self._clock()
        self.recovering = True
        self._reg.gauge("pipeline_recovering",
                        "1 while a pipeline recovery is in flight").set(1)
        tracer = get_tracer()
        with self._lock:
            detections = list(self._detections)
            self._detections = []
            dead_now = sorted(self._dead)
        for _sid, age in detections:
            self.stats["detection_s"].append(age)
            self._reg.histogram(
                "pipeline_detection_seconds",
                "silence before a stage was declared dead").observe(age)
        from ..obs.flight import resolve_flight_recorder
        resolve_flight_recorder(self._flight).record(
            "pipeline_stage_death",
            reasons=[str(err)],
            config={"generation": self._gen, "batch": self._batch,
                    "stages": self.num_stages, "dead_stages": dead_now,
                    "active_addrs": self.active_addrs,
                    "worker_addrs": self.worker_addrs},
            registry=self._reg)
        try:
            with tracer.span("pipe.recover", track="pipeline",
                             gen_from=self._gen, dead=dead_now):
                self._recover_inner()
            wall = self._clock() - t0
            self.stats["recoveries"] += 1
            self.stats["recovery_s"].append(wall)
            self._reg.counter("pipeline_recoveries_total",
                              "completed pipeline recoveries").inc()
            self._reg.histogram(
                "pipeline_recovery_seconds",
                "stage-loss to pipeline-serving-again wall").observe(wall)
        finally:
            self.recovering = False
            self._reg.gauge("pipeline_recovering",
                            "1 while a pipeline recovery is in flight"
                            ).set(0)

    def _recover_inner(self) -> None:
        old_partitions = list(self.partitions)
        self.abort()  # gen bump: fences both ends against the dead batch
        alive = self._rebuild_channels()
        self._install_workers(alive)
        # gather-or-restore: a complete, configured, vintage-consistent
        # old stage set (a falsely convicted wedged worker, all workers
        # merely re-dialed) yields the LIVE weights — zero rewind;
        # anything less falls back to the newest valid commit
        full = None
        if len(alive) >= len(old_partitions):
            try:
                replies = self._gather_stage_blobs()
                full = self._assemble_full(replies, old_partitions,
                                           expect_batch=self._batch)
            except (StageLostError, TimeoutError, RuntimeError):
                full = None
        if full is not None:
            params, state, opt = full
            from_batch = self._batch
        else:
            params, state, opt, from_batch = self._restore_weights()
        self._ship_stages(params, state, opt)
        self._start_beat()
        self._replay_journal(from_batch)

    def _rebuild_channels(self) -> List[Tuple[str, Channel]]:  # dcnn: protocol=pipe.c2w role=sender
        """Sweep the FULL original worker address list: reuse healthy
        channels, close + re-dial dead/dropped ones under the
        ``respawn_s`` budget (``pipeline_reconnect_retry_attempts_total``
        counts the backoff; a success counts on
        ``pipeline_stage_respawns_total``), drop addresses that stay
        unreachable this generation. They are retried on every later
        recovery sweep."""
        with self._lock:
            dead_sids = set(self._dead)
        current = dict(zip(self.active_addrs, self.chans))
        dead_addrs = {self.active_addrs[sid] for sid in dead_sids
                      if sid < len(self.active_addrs)}
        alive: List[Tuple[str, Channel]] = []
        for addr in self.worker_addrs:
            ch = current.get(addr)
            if ch is not None and addr not in dead_addrs:
                alive.append((addr, ch))
                continue
            if ch is not None:
                ch.close()  # our half of a dead/broken channel
            host, port = parse_addr(addr)
            try:
                nch = connect(host, port, timeout=self.t.respawn_s,
                              compress=self.compress,
                              name="pipeline_reconnect")
            except (ConnectionError, OSError):
                continue  # unreachable this generation
            try:
                nch.send("HELLO", {"role": "coordinator"})
            except OSError:
                nch.close()
                continue
            if self._live_enabled:
                nch.set_send_timeout(self.t.convict() + self.t.probe())
            self.inbox.attach(nch, on_close=self._on_close)
            alive.append((addr, nch))
            self.stats["respawns"] += 1
            self._reg.counter(
                "pipeline_stage_respawns_total",
                "dead pipeline workers that came back on a recovery "
                "sweep").inc()
        if len(alive) < self.min_stages:
            raise PipelineCollapsedError(
                f"{len(alive)} reachable worker(s) < min_stages "
                f"{self.min_stages}")
        return alive

    def _restore_weights(self) -> Tuple[Any, Any, Any, int]:
        """Newest checksum-valid commit (torn/bit-flipped ones skipped by
        ``restore_latest``), else the initial deploy snapshot. Returns
        (params, state, opt_state, batch_vintage)."""
        restored = self.checkpoints.restore_latest() \
            if self.checkpoints is not None else None
        if restored is not None:
            md = restored.metadata
            return (restored.params, restored.state, restored.opt_state,
                    int(md.get("batch", 0)))
        snap = self._init_weights
        if snap is None:
            raise RuntimeError("no checkpoint and no initial snapshot — "
                               "deploy_stages was never called")
        return snap["p"], snap["s"], snap["o"], 0

    def _replay_journal(self, from_batch: int) -> None:
        """Re-run every journaled batch newer than the restore point —
        identical inputs + rng, so the recovered trajectory matches the
        uninterrupted one (bit-exact under an unchanged partitioning, FP
        reassociation of XLA fusion boundaries otherwise). Batches in the
        gap the journal no longer covers are counted as lost."""
        entries = [e for e in self._journal if e["batch"] > from_batch]
        lost = (self._batch - from_batch) - len(entries)
        if lost > 0:
            self.stats["batches_lost"] += lost
            self._reg.counter(
                "pipeline_batches_lost_total",
                "batches unrecoverable after a stage loss (journal "
                "horizon exceeded)").inc(lost)
        for e in entries:
            fn = (self._batch_sync if e["schedule"] == "sync"
                  else self._batch_semi_async)
            fn(e["x"], e["y"], e["lr"], e["rng"], bno=e["batch"])
            self.stats["replayed_batches"] += 1
            self._reg.counter("pipeline_replayed_batches_total",
                              "journaled batches re-run by recovery").inc()

    # -- failure handling --
    # dcnn: protocol=pipe.w2c role=handler frames=*
    def abort(self) -> None:  # dcnn: protocol=pipe.c2w role=sender
        """Bump the batch generation (fencing out every in-flight message of
        the dead batch on both ends), broadcast cache/grad reset, drain
        ABORTED acks best-effort (``PipelineTimeouts.drain()`` budget,
        expected acks = live stages only)."""
        self._gen += 1
        self._reg.gauge("pipeline_generation",
                        "current pipeline batch generation").set(self._gen)
        for chan in self.chans:
            try:
                chan.send("ABORT", {"gen": self._gen}, attempts=1)
            except OSError:
                pass
        with self._lock:
            expect = self.num_stages - len(self._dead)
        acked = 0
        deadline = self._clock() + self.t.drain()
        while acked < expect:
            remaining = deadline - self._clock()
            if remaining <= 0:
                break
            try:
                cmd, meta, _, chan = self.inbox.get(timeout=remaining)
            except TimeoutError:
                break
            self._heard(chan)
            if cmd == "ABORTED" and meta.get("gen") == self._gen:
                acked += 1

    def health_check(self) -> List[Dict[str, Any]]:  # dcnn: protocol=pipe.c2w role=sender
        """Heartbeat every worker (the HEALTH_CHECK command the reference
        reserves in its CommandType enum but never wires,
        command_type.hpp:20-68): returns one vitals dict per stage
        ({stage_id, configured, gen, batch, rss_kb}), ordered by stage.
        Raises ``TimeoutError``/:class:`StageLostError` if any worker is
        dead. Safe against a mistimed probe: batch messages arriving
        during the join are deferred, not dropped."""
        nonce = int.from_bytes(_os.urandom(4), "little")
        self._health_nonce = nonce   # _recv drops acks with any other nonce
        try:
            for sid in range(self.num_stages):
                self._send_stage(sid, "HEALTH_CHECK", {"nonce": nonce})
            acks = self._join("HEALTH_ACK", self.num_stages,
                              buffer_others=True)
        finally:
            self._health_nonce = None
        vitals = [meta for meta, _ in acks]
        return sorted(vitals, key=lambda v: v.get("stage_id", -1))

    def shutdown(self) -> None:  # dcnn: protocol=pipe.c2w role=sender
        self._beat_stop.set()
        if self._beat_thread is not None:
            self._beat_thread.join(timeout=5.0)
            self._beat_thread = None
        with self._lock:
            self._closed = True
        for chan in self.chans:
            try:
                chan.send("SHUTDOWN", {}, attempts=1)
            except OSError:
                pass
        for chan in self.chans:
            chan.close()
        self.chans = []
        if self.checkpoints is not None:
            self.checkpoints.close()

    def __del__(self):
        try:
            self._beat_stop.set()
        except Exception:
            pass
