"""Elastic, preemption-tolerant data-parallel training.

The reference framework's only multi-node story is a static
pipeline-over-TCP that dies with its weakest worker (SURVEY.md §5.8); on
the preemptible TPU fleets this repo targets, losing one host mid-epoch
must cost *seconds of re-run work*, not the run. This module composes the
parts PRs 4-7 built — atomic checksum-verified checkpoints, the shared
retry/backoff primitive, stall-watchdog-style liveness, deterministic
fault injection, and the single batch-order definition
(``BaseDataLoader.batch_indices``) — into a controller that survives host
loss:

- **Membership / heartbeat** (:class:`Membership`): a full mesh of framed
  TCP channels (``parallel/comm.py``) between the data-parallel hosts.
  Every control frame is generation-stamped; peers beat each step (plus an
  optional background beat thread for long dispatches), and peer death is
  detected two ways — immediately via connection close, and by
  ``StallWatchdog``-style last-heard timeouts for the partitioned-but-open
  case — never by hanging on a recv. Bootstrap address exchange can ride
  ``multihost.broadcast_config`` on real fleets; tests pass explicit
  ``PeerSpec`` lists over loopback.
- **Lockstep DP step**: each host computes the gradient **sum** over its
  contiguous slice of a fixed *global microbatch grid* of K microbatches
  (``data_parallel.make_elastic_grad_step``), ships it to the generation's
  leader (lowest surviving rank), which divides the total by K and
  broadcasts the global mean; every host then applies the identical
  optimizer update to identical replicated state
  (``make_elastic_apply_step``) — params stay bit-identical across hosts
  with no parameter broadcast. On multi-device hosts the local step runs
  under jit over the host's device mesh; the cross-host reduce is this
  host-side exchange.
- **Reconfiguration protocol** (on :class:`~.multihost.PeerLostError` or
  an incoming RECONF): survivors barrier on a new generation id — the new
  leader restores the newest valid :class:`CheckpointManager` commit
  (checksum-verified restore already skips torn ones), broadcasts
  ``RECONF{gen, survivors, ckpt_step, epoch, step}``, and each survivor
  restores, acks, and rebuilds its local step for the new world size. The
  batch plan is re-derived from ``BaseDataLoader.shard_batch_indices``
  with the new world size and gradient accumulation rescales over the SAME
  K-microbatch grid, so the **global batch and the optimizer trajectory
  are fixed across the reshard** (FP reassociation of the gradient sum is
  the only difference — the kill-a-host test bounds it). A second loss
  *during* recovery re-enters the protocol with the shrunken survivor set
  (idempotent by construction). A peer absent from the new survivor list
  raises :class:`EvictedError` and must exit.

What is and is not preserved across a reshard (docs/reliability.md
§"Elastic training"): global batch membership/order and size — yes,
exactly; optimizer trajectory — yes, within FP-reassociation tolerance;
per-microbatch dropout rng — yes (streams keyed by *global* microbatch
index); BN batch statistics — approximately (per-host sequential EMA,
microbatch-count-weighted mean across hosts); host-augmentation rng
streams — re-derived, not replayed.

Fault points: ``elastic.heartbeat`` (armed with ``InjectedCrash`` = the
kill-a-host simulation), ``elastic.reconfigure`` (a crash *during*
recovery), and the delay hook ``elastic.slow_peer`` (``FaultPlan.slow``
= the gray-failure simulation: this peer's local compute runs slow
without dying). Controllers accept a per-instance
:class:`~dcnn_tpu.resilience.faults.FaultPlan` so multi-peer in-process
tests can kill (or slow) one peer without arming the process-global slot.

**Straggler eviction** (``config.slow_detect``; docs/reliability.md §11):
every peer measures its *local-compute* wall per step — the window
before :meth:`ElasticController._exchange`, because the lockstep
exchange equalizes full-step walls across the fleet — and piggybacks it
as ``wall_s`` on its BEAT and GRADS frames. The generation **leader**
(and only the leader: a follower that convicted and unwound would stop
beating and be evicted as the apparently-dead one itself) feeds a
:class:`~dcnn_tpu.resilience.slowness.SlownessDetector` and, on a
conviction, marks the straggler dead and raises
:class:`~.multihost.PeerLostError` — from there the mitigation IS the
existing generation-fenced reconfiguration: reshard over survivors,
zero lost batches, the evicted host told via RECONF
(``include_dead=True``) and exiting on :class:`EvictedError`. A
fleet-wide slowdown moves the median with it and convicts nobody; a
slow *leader* is the documented blind spot (it cannot evict itself —
the fleet still makes progress at the degraded rate, and the alert pack
surfaces the verdict for the operator). An evicted host may rejoin at a
later generation via the segment-restart path (fresh controllers,
``fit(resume=True)``) once a recovery probe
(:meth:`~dcnn_tpu.resilience.slowness.SlownessDetector.probe_ok`)
passes.
"""

from __future__ import annotations

import socket
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

import jax
import jax.flatten_util
import jax.numpy as jnp

from ..data import wire as _wire
from ..obs import get_registry, get_tracer
from ..resilience import faults as _faults
from ..train.trainer import TrainState, create_train_state
from .comm import Channel, Inbox, connect, listen
from .data_parallel import make_elastic_apply_step, make_elastic_grad_step
from .multihost import PeerLostError


@dataclass(frozen=True)
class PeerSpec:
    """One data-parallel host: initial ``rank`` (stable identity for the
    whole run — survivor *positions* are re-derived per generation, ranks
    never are) and its control-plane listen address."""

    rank: int
    host: str
    port: int


def parse_peers(spec: str) -> List[PeerSpec]:
    """``"host:port,host:port,..."`` → :class:`PeerSpec` list; rank =
    position (the ``ELASTIC_PEERS`` env format)."""
    out: List[PeerSpec] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        host, _, port = part.rpartition(":")
        out.append(PeerSpec(len(out), host or "127.0.0.1", int(port)))
    return out


def microbatch_span(total: int, world: int, position: int) -> Tuple[int, int]:
    """Contiguous ``[lo, hi)`` slice of the global K-microbatch grid owned
    by survivor ``position`` of ``world`` — remainder microbatches go to
    the lowest positions, so every grid cell is owned exactly once for any
    world size (the union over positions is always ``range(total)``)."""
    if not 0 <= position < world:
        raise ValueError(f"position {position} outside world {world}")
    base, extra = divmod(total, world)
    lo = position * base + min(position, extra)
    hi = lo + base + (1 if position < extra else 0)
    return lo, hi


class EvictedError(RuntimeError):
    """This peer was declared dead by the surviving quorum (e.g. it was
    partitioned long enough to be timed out) — it must exit rather than
    fight the new generation for the checkpoint directory."""


class PreemptedError(RuntimeError):
    """This host was asked to leave the training world — its device lease
    was revoked by the autoscaler's broker (serving traffic spiked), or
    the operator is draining the host. Raised from the step loop at the
    next beat after :meth:`ElasticController.preempt`; the controller's
    ``finally`` closes its membership, so the surviving peers observe
    exactly a host death and reshape via the normal reconfiguration
    protocol. The caller surrenders the device AFTER this surfaces —
    never while the controller might still be writing checkpoints."""


class WorldCollapsedError(RuntimeError):
    """Fewer survivors than ``elastic_min_world`` — the operator asked us
    not to limp on below this statistical-efficiency floor."""


class _ReconfigureSignal(Exception):
    """Internal control flow: a RECONF frame for a newer generation
    arrived while this peer was mid-step — unwind to the fit loop and
    join that reconfiguration."""

    def __init__(self, meta: Dict[str, Any]):
        self.meta = meta
        super().__init__(f"reconfigure to generation {meta.get('gen')}")


class Membership:
    """Liveness-tracked full mesh of framed channels between DP hosts.

    Peer death is detected by (a) connection close — the reader thread's
    ``on_close`` fires the moment a dead host's kernel closes its sockets
    — and (b) ``check_peers()`` last-heard timeouts (the
    ``StallWatchdog`` pattern: injectable clock, flag-don't-kill), which
    cover the hung-but-connected case. Every mutation of the peer tables
    is lock-guarded: the beat thread, comm reader threads (via
    ``on_close``) and the controller thread all touch them.
    """

    def __init__(self, rank: int, peers: List[PeerSpec], *,
                 listen_sock: Optional[socket.socket] = None,
                 heartbeat_s: float = 0.0, peer_timeout_s: float = 10.0,
                 clock: Callable[[], float] = time.monotonic,
                 registry=None, compress: bool | str = False):
        self.rank = rank
        self.peers = {p.rank: p for p in peers}
        # frame codec for every mesh channel — False = raw, or a codec
        # name ("lz4", "shuffle-lz4", ...; utils/compression.resolve_codec).
        # Per-frame codec ids keep mixed fleets interoperable: a peer
        # configured raw still decodes a compressed sender and vice versa.
        self.compress = compress
        if rank not in self.peers:
            raise ValueError(f"rank {rank} not in peer list "
                             f"{sorted(self.peers)}")
        self.heartbeat_s = heartbeat_s
        self.peer_timeout_s = peer_timeout_s
        self._clock = clock
        self._reg = registry if registry is not None else get_registry()
        self.inbox = Inbox()
        self._listen = listen_sock
        self._lock = threading.Lock()
        self._channels: Dict[int, Channel] = {}   # dcnn: guarded_by=_lock
        # perf_counter-domain clock offsets of peers that dialed us,
        # measured from their HELLO stamp in the merge-CLI convention:
        # offset = dialer_clock - our_clock, i.e. exactly the value
        # `--offset <dialer-shard>=<secs>` takes with OUR shard as the
        # reference timeline. One-way, so biased by connect latency — an
        # alignment HINT; same-host shards align exactly without it.
        self._clock_offsets: Dict[int, float] = {}  # dcnn: guarded_by=_lock
        self._last_heard: Dict[int, float] = {}   # dcnn: guarded_by=_lock
        self._dead: Dict[int, float] = {}         # dcnn: guarded_by=_lock
        self._detections: List[Tuple[int, float]] = []  # dcnn: guarded_by=_lock
        self._beat_meta: Dict[str, Any] = {}      # dcnn: guarded_by=_lock
        self._closed = False                      # dcnn: guarded_by=_lock
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None

    # -- bootstrap ---------------------------------------------------------
    def connect_all(self, timeout: float = 60.0) -> None:  # dcnn: protocol=elastic.hello role=sender
        # dcnn: protocol=elastic.hello role=handler
        """Establish the full mesh: dial every lower rank, accept every
        higher one (each pair has exactly one dialer), HELLO-stamp each
        connection so accepted sockets map to ranks."""
        deadline = self._clock() + timeout
        for r in sorted(self.peers):
            if r >= self.rank:
                continue
            p = self.peers[r]
            ch = connect(p.host, p.port,
                         timeout=max(deadline - self._clock(), 1.0),
                         compress=self.compress)
            # t_mono: the acceptor estimates our perf_counter offset for
            # trace-shard alignment (python -m dcnn_tpu.obs.trace)
            ch.send("HELLO", {"rank": self.rank,
                              "t_mono": time.perf_counter()})
            self._register(r, ch)
        expected = {r for r in self.peers if r > self.rank}
        if expected and self._listen is None:
            me = self.peers[self.rank]
            self._listen = listen(me.port, host=me.host)
        while expected:
            remaining = deadline - self._clock()
            if remaining <= 0:
                raise PeerLostError("elastic bootstrap",
                                    f"peers never connected within "
                                    f"{timeout}s", sorted(expected))
            self._listen.settimeout(remaining)
            try:
                sock, _ = self._listen.accept()
            except socket.timeout:
                continue
            ch = Channel(sock, compress=self.compress)
            sock.settimeout(max(deadline - self._clock(), 1.0))
            cmd, meta, _ = ch.recv()
            sock.settimeout(None)
            if cmd != "HELLO" or meta.get("rank") not in expected:
                ch.close()
                continue
            if "t_mono" in meta:
                # dialer_clock - our_clock (the dialer stamped t_mono
                # just before we read our clock, so the difference IS
                # its offset onto our timeline, up to connect latency)
                off = float(meta["t_mono"]) - time.perf_counter()
                with self._lock:
                    self._clock_offsets[meta["rank"]] = off
            self._register(meta["rank"], ch)
            expected.discard(meta["rank"])
        if self._listen is not None:
            # the mesh is complete and this controller does not accept
            # late (re)joins — world size only shrinks in this design
            self._listen.close()
            self._listen = None
        if self.heartbeat_s > 0:
            self._start_beat_thread()

    def _register(self, rank: int, ch: Channel) -> None:
        # kernel-level send deadline: a silently partitioned peer whose
        # receive window fills must fail the send within peer_timeout_s,
        # not block the whole generation for TCP-retransmit timescales.
        # The raised OSError rides the normal mark-dead path.
        ch.set_send_timeout(self.peer_timeout_s)
        with self._lock:
            self._channels[rank] = ch
            self._last_heard[rank] = self._clock()
        self.inbox.attach(ch, on_close=lambda _c, r=rank: self._mark_dead(r))

    # -- liveness ----------------------------------------------------------
    def _mark_dead(self, rank: int) -> None:
        with self._lock:
            if self._closed or rank in self._dead:
                return
            now = self._clock()
            self._dead[rank] = now
            self._detections.append((rank, now - self._last_heard[rank]))
        self._reg.counter("elastic_peers_lost_total",
                          "DP peers lost (closed or timed out)").inc()

    def evict(self, rank: int) -> None:
        """Administratively declare ``rank`` dead — the gray-failure
        conviction path. The next reconfiguration's survivor set excludes
        it, and the RECONF ``include_dead`` delivery tells the (alive but
        convicted) host to exit via :class:`EvictedError`."""
        self._mark_dead(rank)

    def heard(self, rank: Optional[int]) -> None:
        if rank is None:
            return
        with self._lock:
            if rank in self._last_heard:
                self._last_heard[rank] = self._clock()

    def check_peers(self) -> List[int]:
        """Timeout-based death: peers silent for longer than
        ``peer_timeout_s`` are declared dead (the connection may still be
        open — a wedged host holds its sockets). Returns newly dead
        ranks."""
        newly: List[int] = []
        with self._lock:
            now = self._clock()
            for r in self._channels:
                if r in self._dead:
                    continue
                if now - self._last_heard[r] > self.peer_timeout_s:
                    self._dead[r] = now
                    self._detections.append((r, now - self._last_heard[r]))
                    newly.append(r)
        for _ in newly:
            self._reg.counter("elastic_peers_lost_total",
                              "DP peers lost (closed or timed out)").inc()
        return newly

    def alive(self) -> List[int]:
        """Sorted surviving ranks, always including self."""
        with self._lock:
            others = [r for r in self._channels if r not in self._dead]
        return sorted(others + [self.rank])

    def dead(self) -> Set[int]:
        with self._lock:
            return set(self._dead)

    def pop_detections(self) -> List[Tuple[int, float]]:
        """(rank, seconds-silent-before-declared-dead) pairs recorded
        since the last call — the bench's detection-latency series."""
        with self._lock:
            out, self._detections = self._detections, []
        return out

    def clock_offsets(self) -> Dict[int, float]:
        """Per-peer perf_counter offsets estimated from HELLO stamps
        (peers that dialed us only), in the merge-CLI convention —
        ``offset = peer_clock - our_clock``, passed verbatim as
        ``--offset <peer-shard>=<value>`` with our shard as the
        reference timeline on cross-host fleets."""
        with self._lock:
            return dict(self._clock_offsets)

    # -- frames ------------------------------------------------------------
    def send(self, rank: int, cmd: str, meta: Dict[str, Any],
             array: Optional[np.ndarray] = None, *,
             attempts: int = 3) -> None:
        """Send one frame to ``rank``; a failed (post-retry) send marks
        the peer dead and raises :class:`PeerLostError`."""
        with self._lock:
            ch = self._channels.get(rank)
            gone = rank in self._dead
        if ch is None or gone:
            raise PeerLostError(f"send {cmd}", "peer already dead", [rank])
        m = dict(meta)
        m["rank"] = self.rank
        try:
            ch.send(cmd, m, array=array, attempts=attempts)
        except OSError as e:
            self._mark_dead(rank)
            raise PeerLostError(f"send {cmd}", str(e), [rank]) from e

    def broadcast(self, cmd: str, meta: Dict[str, Any],
                  array: Optional[np.ndarray] = None, *,
                  attempts: int = 3, include_dead: bool = False) -> List[int]:
        """Best-effort send to every live peer; returns ranks lost during
        the broadcast (marked dead, not raised — the caller decides
        whether a partial broadcast is fatal).

        ``include_dead``: also attempt delivery to peers already marked
        dead whose channels are still open — RECONF uses this so a
        timed-out-but-merely-wedged peer still learns it was evicted
        (it raises ``EvictedError`` on receipt instead of eventually
        self-electing as a solo leader). Failures to already-dead peers
        are swallowed, never reported as new losses. A *true* network
        partition cannot be reached this way — fencing the shared
        checkpoint root against a fully partitioned writer is deployment
        policy (lease/lock on the root), not this layer's."""
        with self._lock:
            dead = set(self._dead)
            targets = [(r, ch) for r, ch in self._channels.items()
                       if include_dead or r not in dead]
        lost: List[int] = []
        for r, ch in targets:
            m = dict(meta)
            m["rank"] = self.rank
            try:
                ch.send(cmd, m, array=array, attempts=attempts)
            except OSError:
                if r not in dead:
                    self._mark_dead(r)
                    lost.append(r)
        return lost

    def set_beat_meta(self, **meta: Any) -> None:
        """What the background beat thread stamps on its BEAT frames."""
        with self._lock:
            self._beat_meta = dict(meta)

    def beat_all(self) -> None:  # dcnn: protocol=elastic.mesh role=sender frames=BEAT
        with self._lock:
            meta = dict(self._beat_meta)
        self.broadcast("BEAT", meta, attempts=1)

    def _start_beat_thread(self) -> None:
        if self._hb_thread is not None:
            return

        def loop() -> None:
            while not self._hb_stop.wait(self.heartbeat_s):
                self.beat_all()

        self._hb_thread = threading.Thread(
            target=loop, daemon=True, name=f"dcnn-elastic-beat-{self.rank}")
        self._hb_thread.start()

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        """Stop the beat thread and close every channel + the listener.
        Idempotent; also what a simulated host death calls — peers observe
        exactly what a kernel cleaning up a dead process's sockets
        produces."""
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=5.0)
            self._hb_thread = None
        with self._lock:
            self._closed = True
            chans = list(self._channels.values())
            lst, self._listen = self._listen, None
        for ch in chans:
            ch.close()
        if lst is not None:
            lst.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class ElasticController:
    """Generation-aware elastic DP trainer over a :class:`Membership`.

    One instance per host. ``fit`` runs the epoch loop in lockstep with
    the surviving peers and transparently reconfigures on peer loss —
    see the module docstring for the protocol and the numerics contract.
    Tier-1 proves the contract in-process: N controllers on threads over
    loopback sockets, one killed mid-epoch by a per-instance
    :class:`FaultPlan`, final params matching a never-interrupted
    fixed-world run within FP-reassociation tolerance
    (``tests/test_elastic.py``).
    """

    def __init__(self, model, optimizer, loss_fn: Callable, loader, *,
                 config, rank: int, peers: List[PeerSpec],
                 listen_sock: Optional[socket.socket] = None,
                 fault_plan: Optional[_faults.FaultPlan] = None,
                 feed_pool=None,
                 clock: Callable[[], float] = time.monotonic,
                 registry=None):
        from ..ops.losses import get_loss

        self.model = model
        self.optimizer = optimizer
        self.loss_fn = get_loss(loss_fn) if isinstance(loss_fn, str) \
            else loss_fn
        self.loader = loader
        self.cfg = config
        self.rank = rank
        self._clock = clock
        self._reg = registry if registry is not None else get_registry()
        self._faults_plan = fault_plan
        self._pool = feed_pool
        self.membership = Membership(
            rank, peers, listen_sock=listen_sock,
            heartbeat_s=config.elastic_heartbeat_s,
            peer_timeout_s=config.elastic_timeout_s,
            clock=clock, registry=self._reg,
            compress=getattr(config, "elastic_compress", False))
        # the global microbatch grid K is FIXED for the run: batch_size/K
        # rows per microbatch, re-partitioned (never re-gridded) across
        # whatever world survives — this is what keeps grad accumulation
        # and the global batch exactly constant through a reshard
        self.total_microbatches = config.elastic_microbatches or len(peers)
        if loader.batch_size % self.total_microbatches:
            raise ValueError(
                f"batch_size {loader.batch_size} not divisible by the "
                f"global microbatch grid K={self.total_microbatches}")
        if self.total_microbatches % len(peers):
            raise ValueError(
                f"K={self.total_microbatches} microbatches not divisible "
                f"by the initial world size {len(peers)} — start from an "
                f"even grid (uneven shares are for degraded worlds)")
        if not getattr(loader, "drop_last", True):
            raise ValueError(
                "elastic training requires drop_last=True: a ragged tail "
                "batch cannot tile the fixed microbatch grid, so the "
                "fixed-global-batch contract would break on the last "
                "step of every epoch")
        self.mb_rows = loader.batch_size // self.total_microbatches
        if len(peers) > 1 and not config.checkpoint_dir:
            import warnings
            warnings.warn(
                "elastic training without checkpoint_dir: a peer loss "
                "rewinds ALL survivors to the initial state (epoch 1, "
                "step 0) — set checkpoint_dir (+ elastic_ckpt_steps) so "
                "a reconfiguration restores recent progress instead",
                stacklevel=2)
        if config.checkpoint_dir:
            from ..resilience.checkpoint import CheckpointManager
            self.checkpoints = CheckpointManager(
                config.checkpoint_dir, keep=config.checkpoint_keep)
        else:
            self.checkpoints = None
        self.lr = config.learning_rate
        self.gen = 0
        self.survivors = sorted(self.membership.peers)
        self.world = len(self.survivors)
        self.position = self.survivors.index(rank)
        self.reconfiguring = False
        self.history: List[Dict[str, Any]] = []
        self.step_log: List[Dict[str, Any]] = []
        self.stats: Dict[str, Any] = {
            "reconfigures": 0, "peers_lost": 0, "detection_s": [],
            "restore_s": [], "reconfigure_s": [], "steps_lost": [],
            "stragglers_evicted": 0}
        self.poll_s = 0.02
        self._grad_steps: Dict[int, Callable] = {}  # local mb count -> jit
        self._apply = make_elastic_apply_step(optimizer)
        self._unravel = None
        self._flat_size = 0
        self._init_snapshot = None
        self._last_saved_step = -1
        # per-generation trace context: the leader's elastic.reconfigure
        # span, adopted by every survivor via the RECONF frame's _trace
        # carrier — a reconfiguration (and the steps of the generation it
        # establishes) renders as ONE cross-host timeline
        self._gen_ctx = None
        # gray-failure detection (docs/reliability.md §11): every peer
        # runs a detector over the wall_s metas it hears, but only the
        # LEADER convicts (see the module docstring for why)
        if getattr(config, "slow_detect", False):
            from ..resilience.slowness import (SlownessConfig,
                                               SlownessDetector)
            self.slowness: Optional[SlownessDetector] = SlownessDetector(
                SlownessConfig.from_env(SlownessConfig(
                    dwell_s=getattr(config, "slow_dwell_s", 1.0),
                    ratio=getattr(config, "slow_ratio", 2.0),
                    mad_k=getattr(config, "slow_mad_k", 4.0),
                    min_samples=getattr(config, "slow_min_samples", 3))),
                clock=clock)
        else:
            self.slowness = None
        self._last_wall: Optional[float] = None
        # set by preempt() (any thread); checked at every step beat
        self._preempt = threading.Event()
        self._preempt_reason = "preempted"

    # -- plumbing ----------------------------------------------------------
    def _trip(self, point: str, **ctx) -> None:
        if self._faults_plan is not None:
            self._faults_plan.trip(point, **ctx)
        else:
            _faults.trip(point, **ctx)

    def _slow_sleep(self, point: str, base_s: float, **ctx) -> float:
        """Delay-injection twin of :meth:`_trip` (``FaultPlan.slow``):
        sleeps the armed extra wall INSIDE the caller's timing window so
        the fleet experiences the slowness exactly as a degraded host
        would produce it. Returns the extra seconds slept."""
        extra = _faults.slowdown(point, base_s, **ctx)
        if self._faults_plan is not None:
            extra += self._faults_plan.slowdown(point, base_s, **ctx)
        if extra > 0.0:
            time.sleep(extra)
        return extra

    @property
    def generation(self) -> int:
        return self.gen

    def is_leader(self) -> bool:
        return self.position == 0

    def preempt(self, reason: str = "device lease revoked") -> None:
        """Ask this controller to leave the world at the next step beat
        (thread-safe; the device-lease twin in ``parallel/autoscale.py``
        calls this from the broker's revocation path). The step loop
        raises :class:`PreemptedError`, survivors reshape, and training
        continues without this host."""
        self._preempt_reason = reason
        self._preempt.set()

    def _leader_rank(self) -> int:
        return self.survivors[0]

    def _local_span(self) -> Tuple[int, int]:
        return microbatch_span(self.total_microbatches, self.world,
                               self.position)

    def _build(self, ts: TrainState) -> None:
        """(Re)build the local compute for the current world/position —
        the single-host analog of rebuilding the device mesh: a new local
        microbatch count re-jits the grad step (cached per count), and
        the flat gradient codec is re-anchored on the live state's
        treedef."""
        with get_tracer().span("elastic.rebuild", track="elastic",
                               rank=self.rank, gen=self.gen,
                               world=self.world):
            self._build_inner(ts)

    def _build_inner(self, ts: TrainState) -> None:
        lo, hi = self._local_span()
        a = hi - lo
        if a not in self._grad_steps:
            self._grad_steps[a] = make_elastic_grad_step(
                self.model, self.loss_fn, a)
        zero = {
            "g": jax.tree_util.tree_map(np.zeros_like,
                                        jax.device_get(ts.params)),
            "s": jax.tree_util.tree_map(np.zeros_like,
                                        jax.device_get(ts.state)),
        }
        flat, unravel = jax.flatten_util.ravel_pytree(zero)
        self._unravel = unravel
        self._flat_size = int(flat.size)
        self._reg.gauge("elastic_generation",
                        "current elastic generation id").set(self.gen)
        self._reg.gauge("elastic_world_size",
                        "surviving data-parallel world size").set(self.world)

    def _epoch_plan(self, epoch: int) -> List[np.ndarray]:
        """The epoch's global batches — THE batch-order definition
        (``BaseDataLoader.batch_indices``), identical on every host for
        every world size."""
        self.loader.shuffle(epoch)
        return [np.ascontiguousarray(b, np.int64)
                for b in self.loader.batch_indices()]

    # -- fit ---------------------------------------------------------------
    def fit(self, ts: Optional[TrainState] = None,
            epochs: Optional[int] = None, val_loader=None,
            seed: Optional[int] = None, resume: bool = False
            ) -> TrainState:
        """Run the elastic epoch loop to (global) epoch ``epochs``.

        ``resume=True`` restores the newest valid commit from the shared
        checkpoint root before the first step and continues from its
        (epoch, step, lr) — the segment-restart path the device-lease
        twin uses to RE-GROW a world: a fresh, larger fleet picks up
        exactly where the shrunken one stopped (every peer restores the
        same commit; the cross-peer agreement check still applies at any
        later reconfiguration). With no commit yet, resume is a no-op.
        """
        # every host must pass the same seed (or the same cfg.seed) — the
        # epoch/step rng derivation below is what keeps peers in lockstep
        seed = seed if seed is not None else self.cfg.seed
        epochs = epochs or self.cfg.epochs
        if ts is None:
            ts = create_train_state(self.model, self.optimizer,
                                    jax.random.PRNGKey(seed))
        # the step-0 restore target for a loss before the first commit
        self._init_snapshot = jax.device_get(
            {"params": ts.params, "state": ts.state,
             "opt_state": ts.opt_state})
        epoch, step = 1, 0
        gs = 0
        if resume and self.checkpoints is not None:
            ts, epoch, step, gs, _ = self._restore()
        self.membership.connect_all(
            timeout=max(self.cfg.elastic_timeout_s * 4, 30.0))
        self._build(ts)
        self._reg.gauge("elastic_reconfiguring",
                        "1 while a reconfiguration is in flight").set(0)
        base_rng = jax.random.PRNGKey(seed)
        try:
            while epoch <= epochs:
                plan = self._epoch_plan(epoch)
                try:
                    ts, gs = self._run_epoch(ts, plan, epoch, step, gs,
                                             base_rng)
                    self._epoch_end(ts, epoch, gs, val_loader)
                    epoch, step = epoch + 1, 0
                except (PeerLostError, _ReconfigureSignal) as sig:
                    ts, epoch, step, gs = self._reconfigure(sig, ts, gs)
        finally:
            self.membership.close()
            if self.checkpoints is not None:
                self.checkpoints.close()
        return ts

    def _run_epoch(self, ts: TrainState, plan: List[np.ndarray], epoch: int,
                   start_step: int, gs: int, base_rng) -> Tuple[TrainState,
                                                               int]:
        epoch_rng = jax.random.fold_in(base_rng, epoch)
        lo, hi = self._local_span()
        gstep = self._grad_steps[hi - lo]
        shard_iter = None
        if self._pool is not None:
            # the pool's selections are the SAME microbatch-grid slices
            # the compute path consumes (not the equal-split
            # shard_batch_indices view) so a degraded world whose share
            # of the K grid is uneven still feeds every host exactly the
            # rows its grad step was built for
            sels = [idx[lo * self.mb_rows:hi * self.mb_rows]
                    for idx in plan[start_step:]]
            shard_iter = self._pool.shards(iter(sels), epoch=epoch)
        loss_acc, n_steps = 0.0, 0
        t0 = self._clock()
        try:
            ts, gs, loss_acc, n_steps = self._step_loop(
                ts, plan, epoch, start_step, gs, epoch_rng, gstep, lo, hi,
                shard_iter)
        finally:
            if shard_iter is not None:
                # a reconfiguration abandons the iterator mid-epoch: close
                # it so the pool's slots drain and the NEXT plan (new
                # world size) can drive a fresh shards() call
                shard_iter.close()
        if n_steps:
            self.history.append({
                "epoch": epoch, "train_loss": loss_acc / n_steps,
                "seconds": self._clock() - t0, "world": self.world,
                "gen": self.gen, "lr": self.lr})
        return ts, gs

    def _step_loop(self, ts: TrainState, plan: List[np.ndarray], epoch: int,
                   start_step: int, gs: int, epoch_rng, gstep,
                   lo: int, hi: int, shard_iter):
        tracer = get_tracer()
        a = hi - lo
        loss_acc, n_steps = 0.0, 0
        for s in range(start_step, len(plan)):
            self._beat(gs)
            idx = plan[s]
            sel = idx[lo * self.mb_rows:hi * self.mb_rows]
            if shard_iter is not None:
                shard = next(shard_iter)
                x, y = shard.for_put()
            else:
                shard = None
                x, y = self.loader.rows(sel)
            step_rng = jax.random.fold_in(epoch_rng, s)
            with tracer.span("elastic.step", track="elastic",
                             parent=self._gen_ctx, rank=self.rank,
                             gen=self.gen, step=gs):
                # local-compute wall: measured BEFORE _exchange, because
                # the lockstep exchange equalizes full-step walls across
                # the fleet — only this window discriminates a straggler
                t_local = self._clock()
                # the put above shipped the loader's wire dtype (uint8
                # pixels for image loaders — 1/4 the H2D bytes); decode
                # on device per the scale contract (identity for floats)
                xd = _wire.decode_batch(jnp.asarray(x),
                                        _wire.wire_scale(self.loader))
                grad_sum, state_new, loss_sum = gstep(
                    ts.params, ts.state, xd, jnp.asarray(y),
                    step_rng, jnp.asarray(lo, jnp.int32))
                flat = np.asarray(jax.flatten_util.ravel_pytree({
                    "g": grad_sum,
                    "s": jax.tree_util.tree_map(lambda v: a * v, state_new),
                })[0])
                self._slow_sleep("elastic.slow_peer",
                                 self._clock() - t_local,
                                 gen=self.gen, step=gs)
                wall = self._clock() - t_local
                self._last_wall = wall
                if self.slowness is not None:
                    self.slowness.observe(f"rank{self.rank}", wall)
                avg_flat, mean_loss = self._exchange(
                    flat, float(loss_sum), a, gs, wall_s=wall)
                self._check_slowness()
                mean = self._unravel(jnp.asarray(avg_flat))
                new_params, new_opt = self._apply(
                    ts.params, ts.opt_state, mean["g"], self.lr)
                ts = TrainState(new_params, mean["s"], new_opt, ts.step + 1)
            if shard is not None:
                shard.release()
            gs += 1
            loss_acc += mean_loss
            n_steps += 1
            self.step_log.append({
                "gs": gs, "gen": self.gen, "world": self.world,
                "epoch": epoch, "step": s,
                "global_rows": int(len(idx))})
            if (self.is_leader() and self.checkpoints is not None
                    and self.cfg.elastic_ckpt_steps > 0
                    and gs % self.cfg.elastic_ckpt_steps == 0):
                self._save(ts, epoch, s + 1, gs)
        if shard_iter is not None:
            # the plan is sized to the loop: the iterator must be spent
            if next(shard_iter, None) is not None:
                raise RuntimeError("feed pool produced more shards than "
                                   "the epoch plan")
        return ts, gs, loss_acc, n_steps

    def _epoch_end(self, ts: TrainState, epoch: int, gs: int,
                   val_loader) -> None:
        if val_loader is not None and self.is_leader():
            from ..train.trainer import evaluate_classification
            val_loss, val_acc = evaluate_classification(
                self.model, ts.params, ts.state, self.loss_fn, val_loader)
            if self.history:
                self.history[-1]["val_loss"] = val_loss
                self.history[-1]["val_acc"] = val_acc
        if self.cfg.lr_decay_factor != 1.0 \
                and epoch % self.cfg.lr_decay_interval == 0:
            self.lr *= self.cfg.lr_decay_factor
        if self.is_leader() and self.checkpoints is not None:
            # epoch-boundary anchor AFTER the decay: resume trains epoch+1
            # with exactly the lr the uninterrupted run would use
            self._save(ts, epoch + 1, 0, gs)

    def _beat(self, gs: int) -> None:
        if self._preempt.is_set():
            # leave at a step boundary: nothing half-sent, no checkpoint
            # mid-write — peers see a clean host death on membership
            # close and reshape without this rank
            raise PreemptedError(
                f"rank {self.rank} preempted at step {gs}: "
                f"{self._preempt_reason}")
        # deterministic per-step beat — the elastic.heartbeat fault point
        # armed with InjectedCrash here IS the kill-a-host simulation
        self._trip("elastic.heartbeat", gen=self.gen, step=gs)
        # wall_s piggybacks the last local-compute wall so the leader's
        # slowness detector hears every peer even between GRADS frames
        self.membership.set_beat_meta(gen=self.gen, step=gs,
                                      wall_s=self._last_wall)
        self.membership.beat_all()

    def _check_slowness(self) -> None:
        """Leader-only gray-failure conviction sweep. Every peer's
        detector hears the fleet's walls, but only the leader acts: a
        follower that convicted and unwound to await a RECONF would stop
        beating and be timed out as the apparently-dead peer itself. A
        convicted straggler is marked dead and surfaced as
        :class:`~.multihost.PeerLostError` — the mitigation is the
        normal generation-fenced reconfiguration."""
        det = self.slowness
        if det is None:
            return
        transitions = det.evaluate()
        if not self.is_leader():
            return
        for tr in transitions:
            if tr["to"] != "convicted":
                continue
            victim = int(str(tr["component"])[len("rank"):])
            if victim == self.rank:
                # documented limitation: the leader cannot evict itself.
                # Surface the verdict (alert pack + flight bundle) and
                # keep training at the degraded rate.
                self._reg.counter(
                    "elastic_slow_leader_total",
                    "leader self-convictions (surfaced, never "
                    "self-evicted)").inc()
                from ..obs.flight import resolve_flight_recorder
                resolve_flight_recorder().record(
                    "straggler_convicted", registry=self._reg,
                    reasons=[f"leader rank {victim} is the straggler — "
                             f"cannot self-evict"],
                    extra={"victim": victim, "gen": self.gen,
                           "self_conviction": True,
                           "slowness": det.snapshot()})
                continue
            reason = (f"rank {victim} convicted as straggler: local wall "
                      f"EWMA {tr['ewma']:.6g}s vs fleet median "
                      f"{tr['median']:.6g}s")
            from ..obs.flight import resolve_flight_recorder
            resolve_flight_recorder().record(
                "straggler_convicted", registry=self._reg,
                reasons=[reason],
                config={"slow_dwell_s": det.config.dwell_s,
                        "slow_ratio": det.config.ratio,
                        "slow_mad_k": det.config.mad_k},
                extra={"victim": victim, "gen": self.gen,
                       "slowness": det.snapshot()})
            self._reg.counter(
                "elastic_stragglers_evicted_total",
                "DP peers evicted on gray-failure conviction").inc()
            self.stats["stragglers_evicted"] += 1
            det.forget(str(tr["component"]))
            self.membership.evict(victim)
            raise PeerLostError("straggler eviction", reason, [victim])

    # -- gradient exchange -------------------------------------------------
    # dcnn: protocol=elastic.mesh role=sender
    def _exchange(self, flat: np.ndarray, loss_sum: float, local_mb: int,
                  gs: int, wall_s: Optional[float] = None
                  ) -> Tuple[np.ndarray, float]:
        """All-reduce of the flat (grad-sum ‖ scaled-state) vector over the
        surviving world via the generation leader; returns the global
        /K mean. Every peer returns bit-identical bytes (the mean is
        computed once, on the leader) so replicated state never drifts."""
        k = float(self.total_microbatches)
        if self.world == 1:
            return flat / k, loss_sum / k
        deadline = self._clock() + self.cfg.elastic_timeout_s
        if self.is_leader():
            total = flat.astype(np.float32, copy=True)
            loss_total = loss_sum
            mb_total = local_mb
            expect = set(self.survivors) - {self.rank}
            while expect:
                _cmd, meta, payload = self._recv(
                    {"GRADS"}, deadline, expect,
                    match=lambda m: m.get("step") == gs)
                total += payload
                loss_total += float(meta["loss"])
                mb_total += int(meta["mb"])
                expect.discard(meta["rank"])
            if mb_total != self.total_microbatches:
                raise RuntimeError(
                    f"global batch integrity violated: {mb_total} of "
                    f"{self.total_microbatches} microbatches arrived for "
                    f"step {gs}")
            avg = (total / k).astype(np.float32)
            mean_loss = loss_total / k
            lost = self.membership.broadcast(
                "GSUM", {"gen": self.gen, "step": gs, "loss": mean_loss},
                array=avg)
            if lost:
                raise PeerLostError("GSUM broadcast",
                                    "peer died receiving the reduced "
                                    "gradients", lost)
            return avg, mean_loss
        leader = self._leader_rank()
        self.membership.send(
            leader, "GRADS",
            {"gen": self.gen, "step": gs, "loss": loss_sum,
             "mb": local_mb, "wall_s": wall_s}, array=flat)
        _cmd, meta, payload = self._recv(
            {"GSUM"}, deadline, {leader},
            match=lambda m: m.get("step") == gs)
        return payload, float(meta["loss"])

    # dcnn: protocol=elastic.mesh role=handler frames=GRADS,GSUM,RECONF_ACK
    def _recv(self, want: Set[str], deadline: float, expect: Set[int],
              match: Optional[Callable[[Dict], bool]] = None,
              accept_reconf: bool = False):
        """Generation-aware receive: BEATs refresh liveness, stale
        generations are dropped, a RECONF for a newer generation raises
        :class:`_ReconfigureSignal` (or is returned when
        ``accept_reconf``), a dead expected peer or an expired deadline
        raises :class:`PeerLostError` — this loop is why no elastic wait
        ever hangs."""
        while True:
            gone = self.membership.dead() & expect
            if gone:
                raise PeerLostError(f"waiting for {sorted(want)}",
                                    "peer connection lost", sorted(gone))
            if self._clock() > deadline:
                raise PeerLostError(
                    f"waiting for {sorted(want)}",
                    f"no frame within {self.cfg.elastic_timeout_s}s at "
                    f"generation {self.gen}", sorted(expect))
            try:
                cmd, meta, payload, _ch = self.membership.inbox.get(
                    timeout=self.poll_s)
            except TimeoutError:
                # ONLY judge peer silence when the inbox is drained: a
                # long local phase (first-step jit compile, a checkpoint
                # restore) leaves peers' BEATs queued unread, and timing
                # peers out before consuming them would split a healthy
                # fleet into solo trainers. Close-based death (the
                # ``gone`` check above) stays immediate.
                self.membership.check_peers()
                continue
            self.membership.heard(meta.get("rank"))
            if self.slowness is not None:
                # harvest the piggybacked local-compute walls (BEAT and
                # GRADS metas both carry wall_s) — feeding is universal,
                # convicting is leader-only (_check_slowness). Dead peers
                # are excluded: a convicted straggler keeps stepping (and
                # beating) until its RECONF arrives, and those stale walls
                # would re-seed the component ``forget`` just erased and
                # convict the same ghost a second time
                w, r = meta.get("wall_s"), meta.get("rank")
                if (w is not None and r is not None
                        and r not in self.membership.dead()):
                    self.slowness.observe(f"rank{r}", float(w))
            if cmd == "BEAT":
                continue
            mgen = meta.get("gen", -1)
            if cmd == "RECONF" and mgen > self.gen:
                if accept_reconf and cmd in want:
                    return cmd, meta, payload
                raise _ReconfigureSignal(meta)
            if mgen != self.gen:
                self._reg.counter(
                    "elastic_stale_frames_total",
                    "frames dropped for generation mismatch").inc()
                continue
            if cmd in want and (match is None or match(meta)):
                return cmd, meta, payload
            self._reg.counter(
                "elastic_stale_frames_total",
                "frames dropped for generation mismatch").inc()

    # -- checkpointing -----------------------------------------------------
    def _save(self, ts: TrainState, epoch: int, step_in_epoch: int,
              gs: int) -> None:
        if gs == self._last_saved_step:
            return
        self.checkpoints.save(
            gs, self.model, ts.params, ts.state, ts.opt_state,
            self.optimizer,
            {"epoch": epoch, "step_in_epoch": step_in_epoch,
             "global_step": gs, "lr": float(self.lr),
             "elastic_gen": self.gen, "world": self.world})
        self._last_saved_step = gs

    def _restore(self, expect_step: Optional[int] = None
                 ) -> Tuple[TrainState, int, int, int, int]:
        """(ts, epoch, step_in_epoch, global_step, ckpt_step) from the
        newest valid commit, or the initial snapshot when none exists.
        ``expect_step`` (from the leader's RECONF) cross-checks that every
        survivor restored the SAME commit — a mismatch means the hosts do
        not share a checkpoint root, which can only diverge the replicas."""
        t0 = self._clock()
        with get_tracer().span("elastic.restore", track="elastic",
                               rank=self.rank, gen=self.gen) as rs:
            restored = self.checkpoints.restore_latest(seed=self.cfg.seed) \
                if self.checkpoints is not None else None
            rs.set(found=restored is not None,
                   ckpt_step=getattr(restored, "step", None))
        if restored is None:
            snap = self._init_snapshot
            ts = TrainState(snap["params"], snap["state"],
                            snap["opt_state"], jnp.zeros((), jnp.int32))
            epoch, step, gs, ckpt_step = 1, 0, 0, -1
            self.lr = self.cfg.learning_rate
        else:
            md = restored.metadata
            gs = int(md.get("global_step", 0))
            ts = TrainState(restored.params, restored.state,
                            restored.opt_state,
                            jnp.asarray(gs, jnp.int32))
            epoch = int(md.get("epoch", 1))
            step = int(md.get("step_in_epoch", 0))
            self.lr = float(md.get("lr", self.lr))
            ckpt_step = restored.step
        # the restored commit already exists at ckpt_step: a fresh
        # controller resuming a finished epoch must not re-save it (the
        # committed-checkpoints-are-immutable guard would refuse)
        self._last_saved_step = ckpt_step
        if expect_step is not None and ckpt_step != expect_step:
            raise RuntimeError(
                f"survivors disagree on the restore point: leader restored "
                f"commit {expect_step}, this host found {ckpt_step} — the "
                f"hosts are not sharing one checkpoint root")
        self.stats["restore_s"].append(self._clock() - t0)
        return ts, epoch, step, gs, ckpt_step

    # -- reconfiguration ---------------------------------------------------
    def _reconfigure(self, sig, ts: TrainState, gs: int
                     ) -> Tuple[TrainState, int, int, int]:
        """Survive a peer loss: loop the single-shot protocol until a
        generation sticks — a *second* loss mid-recovery just re-enters
        with the shrunken survivor set (the reconfigure-idempotence
        contract)."""
        t0 = self._clock()
        self.reconfiguring = True
        self._reg.gauge("elastic_reconfiguring",
                        "1 while a reconfiguration is in flight").set(1)
        tracer = get_tracer()
        # the reconfiguration's root span: if this host ends up leading,
        # its context rides the RECONF broadcast (comm's _trace carrier)
        # and every survivor's restore/rebuild joins this trace; if it
        # ends up following, _join_reconf adopts the leader's instead
        rspan = tracer.begin("elastic.reconfigure", track="elastic",
                             rank=self.rank, gen_from=self.gen)
        # expected stall: the reshard makes the next steps arbitrarily
        # slow by design — fence it from the goodput anomaly detector so
        # a planned recovery never burns a capture (obs/anomaly.py)
        from ..obs.anomaly import suppress as _anomaly_suppress
        try:
            with _anomaly_suppress():
                while True:
                    try:
                        with tracer.activate(rspan):
                            out = self._reconfigure_once(sig, gs)
                        break
                    except (PeerLostError, _ReconfigureSignal) as again:
                        sig = again
            ts, epoch, step, new_gs = out
            if self._gen_ctx is None or self.rank == self.survivors[0]:
                # leader (or solo survivor): the generation's steps
                # parent under this reconfigure span
                self._gen_ctx = rspan.context()
            for _rank, age in self.membership.pop_detections():
                self.stats["detection_s"].append(age)
                self._reg.histogram(
                    "elastic_detection_seconds",
                    "silence before a peer was declared dead").observe(age)
            lost_steps = max(gs - new_gs, 0)
            self.stats["steps_lost"].append(lost_steps)
            self.stats["peers_lost"] = len(self.membership.dead())
            self.stats["reconfigures"] += 1
            self.stats["reconfigure_s"].append(self._clock() - t0)
            self._reg.counter("elastic_reconfigures_total",
                              "completed reconfigurations").inc()
            self._reg.counter("elastic_steps_lost_total",
                              "optimizer steps re-run after restores"
                              ).inc(lost_steps)
            if self.stats["restore_s"]:
                self._reg.histogram(
                    "elastic_restore_seconds",
                    "checkpoint restore wall during reconfiguration"
                ).observe(self.stats["restore_s"][-1])
            return ts, epoch, step, new_gs
        finally:
            tracer.end(rspan, gen=self.gen, world=self.world)
            self.reconfiguring = False
            self._reg.gauge("elastic_reconfiguring",
                            "1 while a reconfiguration is in flight").set(0)

    # dcnn: protocol=elastic.mesh role=sender
    def _reconfigure_once(self, sig, gs: int
                          ) -> Tuple[TrainState, int, int, int]:
        self._trip("elastic.reconfigure", gen=self.gen)
        if isinstance(sig, _ReconfigureSignal) \
                and sig.meta.get("gen", -1) > self.gen:
            # an established quorum already barriered on a new generation:
            # join it as a follower REGARDLESS of this host's own (possibly
            # stale) membership view — a wedged would-be leader that tried
            # to out-elect the quorum here would only escalate generations
            # against peers that have already moved on. Eviction (this
            # rank absent from the survivor list) is discovered inside.
            return self._join_reconf(sig.meta)
        self.membership.check_peers()
        survivors = self.membership.alive()
        floor = max(1, self.cfg.elastic_min_world)
        if len(survivors) < floor:
            raise WorldCollapsedError(
                f"{len(survivors)} survivor(s) < elastic_min_world "
                f"{floor}")
        if self.rank == survivors[0]:
            # leader path: bump the generation FIRST so every frame of
            # the old generation (including stragglers' GRADS) is stale
            new_gen = self.gen + 1
            self.gen = new_gen
            ts, epoch, step, new_gs, ckpt_step = self._restore()
            meta = {"gen": new_gen, "survivors": survivors,
                    "ckpt_step": ckpt_step, "epoch": epoch,
                    "step_in_epoch": step, "global_step": new_gs,
                    "lr": self.lr}
            # include_dead: a timed-out peer that is wedged rather than
            # gone must still receive the RECONF that evicts it
            lost = self.membership.broadcast("RECONF", meta,
                                             include_dead=True)
            if lost:
                raise PeerLostError("RECONF broadcast", "peer died while "
                                    "joining the new generation", lost)
            expect = set(survivors) - {self.rank}
            deadline = self._clock() + self.cfg.elastic_timeout_s
            while expect:
                _cmd, m, _p = self._recv({"RECONF_ACK"}, deadline, expect)
                expect.discard(m["rank"])
        else:
            leader = survivors[0]
            deadline = self._clock() + self.cfg.elastic_timeout_s
            _cmd, meta, _p = self._recv(
                {"RECONF"}, deadline, {leader}, accept_reconf=True)
            return self._join_reconf(meta)
        self.survivors = survivors
        self.world = len(survivors)
        self.position = survivors.index(self.rank)
        self._build(ts)
        return ts, epoch, step, new_gs

    # dcnn: protocol=elastic.mesh role=sender
    def _join_reconf(self, meta: Dict[str, Any]
                     ) -> Tuple[TrainState, int, int, int]:
        """Adopt an established generation as a follower: restore the
        commit the leader named, ack, rebuild for the new world — all
        under the leader's reconfiguration trace (the RECONF frame's
        ``_trace`` carrier), so the whole generation change is one
        cross-host timeline."""
        survivors = list(meta["survivors"])
        if self.rank not in survivors:
            raise EvictedError(
                f"rank {self.rank} excluded from generation "
                f"{meta['gen']} (survivors {survivors}) — the quorum "
                f"timed this host out; exiting")
        self.gen = int(meta["gen"])
        tracer = get_tracer()
        ctx = meta.get("_trace")
        if ctx is not None:
            self._gen_ctx = ctx
        with tracer.activate(ctx):
            ts, epoch, step, new_gs, _ = self._restore(
                expect_step=meta["ckpt_step"])
            self.lr = float(meta["lr"])
            self.membership.send(meta["rank"], "RECONF_ACK",
                                 {"gen": self.gen})
            self.survivors = survivors
            self.world = len(survivors)
            self.position = survivors.index(self.rank)
            self._build(ts)
        return ts, epoch, step, new_gs


def elastic_fit(trainer, ts, train_loader, val_loader=None,
                epochs: Optional[int] = None,
                seed: Optional[int] = None):
    """``Trainer.fit``'s elastic delegation: build the controller from the
    trainer's model/optimizer/loss/config, wire the telemetry plane
    (``/healthz`` reports degraded while a reconfiguration is in flight),
    run, and hand the history back to the trainer."""
    cfg = trainer.config
    peers = parse_peers(cfg.elastic_peers) if cfg.elastic_peers else []
    if not peers:
        peers = [PeerSpec(0, "127.0.0.1", 0)]
    rank = cfg.elastic_rank
    if rank < 0:
        from ..utils.env import get_env
        rank = get_env("PROCESS_ID", 0)
    pool = None
    if cfg.feed_workers > 0:
        # the PR-5 parallel input pipeline rides along under ELASTIC=1:
        # slots sized to the full global batch because a degraded world
        # can concentrate every row on one survivor
        from ..data.workers import FeedWorkerPool
        train_loader._ensure_loaded()
        pool = FeedWorkerPool(train_loader._x, train_loader._y,
                              max_rows=train_loader.batch_size,
                              num_workers=cfg.feed_workers,
                              seed=train_loader.seed)
    controller = ElasticController(
        trainer.model, trainer.optimizer, trainer.loss_fn, train_loader,
        config=cfg, rank=rank, peers=peers, feed_pool=pool)
    telemetry = None
    try:
        if cfg.metrics_port >= 0:
            from ..obs import (TelemetryServer, elastic_check,
                               get_flight_recorder)
            telemetry = TelemetryServer(port=cfg.metrics_port)
            telemetry.set_identity(component="elastic", rank=rank)
            telemetry.attach_flight(get_flight_recorder())
            telemetry.add_check("elastic", elastic_check(controller))
            if controller.checkpoints is not None:
                from ..obs import checkpoint_check
                telemetry.add_check(
                    "checkpoint", checkpoint_check(controller.checkpoints))
            telemetry.start()
            print(f"telemetry: {telemetry.url}/metrics /healthz /snapshot",
                  flush=True)
        ts = controller.fit(ts, epochs=epochs, val_loader=val_loader,
                            seed=seed)
        trainer.history = controller.history
        return ts
    finally:
        if telemetry is not None:
            telemetry.stop()
        if pool is not None:
            pool.close()
