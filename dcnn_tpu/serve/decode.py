"""Continuous-batching autoregressive decode: the generative serving stack.

One-shot classification (engine.py/batcher.py) dispatches a request once
and is done; generative decode holds a **slot** for hundreds of steps and
completes at a data-dependent length. Batching those naively — drain the
whole batch, then admit the next — leaves slots idle from the moment their
sequence finishes until the *longest* sequence in the batch does, which is
where decode throughput actually dies. This module implements the
iteration-level alternative (the Orca/vLLM line):

- :class:`DecodeEngine` — the compiled half: ONE jitted fixed-shape decode
  step (embed → per-layer scatter-K/V-into-pages → gather → causal attend
  → head → greedy argmax), lowered once per **(batch-bucket, page-bucket)**
  in the constructor (TS06-clean: one ``jax.jit``, per-bucket
  ``lower().compile()``, exactly like ``InferenceEngine``) — so
  admitting a sequence mid-flight can NEVER retrace or recompile
  (``tests/test_decode.py`` asserts a zero ``compile_total`` delta);
- :class:`KVPagePool` (``kvcache.py``) — paged KV memory with free-list
  recycling, so slot count is bounded by the *working set*, not the
  worst-case sequence length;
- :class:`ContinuousBatcher` — the scheduler: admits pending sequences
  into free slots at **step boundaries** (no drain), retires each
  sequence the step it completes, and on page exhaustion preempts the
  most-recently-admitted sequence back to the queue
  (recompute-on-readmission — greedy decode is deterministic, so the
  replay is bit-exact). Same operational contract as
  :class:`~dcnn_tpu.serve.batcher.DynamicBatcher`: bounded intake
  (:class:`~dcnn_tpu.serve.batcher.QueueFullError`), typed refusal while
  draining, an accepted-futures ledger with the no-orphan guarantee, a
  sleep-free ``start=False`` synchronous mode, and ``decode.step`` /
  ``decode.admit`` fault trip points (``resilience/faults.py``).

Determinism contract (the acceptance bar): per-row computation in the
decode step depends only on that row's token/position/page-table and the
pages that row owns — padding rows ride the null page and mask to exact
zeros — so a sequence's greedy output is **bit-identical** whether it
decoded alone (:func:`decode_reference`) or interleaved with any mix of
neighbours under any admission order. ``tests/test_decode.py`` asserts
this across interleavings; ``examples/serve_decode.py`` demos it.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import get_registry, get_tracer
from ..obs.xla import (executable_cost, install_compile_listener,
                       record_compile, sample_hbm)
from ..resilience import faults
from ..resilience.faults import InjectedCrash
from .batcher import DrainingError, QueueFullError, ShutdownError
from .engine import serve_buckets
from .kvcache import KVPagePool, OutOfPagesError, suggest_num_pages
from .metrics import DecodeMetrics


class DecodeEngine:
    """Bucketed, pre-compiled, paged decode steps over one
    :class:`~dcnn_tpu.models.decoder.MHADecoder` checkpoint.

    The step function is written once and lowered per
    ``(batch_bucket, page_bucket)``: batch buckets are
    :func:`~dcnn_tpu.serve.engine.serve_buckets` of ``max_slots``; page
    buckets the same powers-of-two ladder over ``max_pages_per_seq``
    (page-table width — context grows through wider tables, not
    recompiles). ``num_pages=None`` sizes the pool from live HBM headroom
    (:func:`~dcnn_tpu.serve.kvcache.suggest_num_pages`), with a CPU
    default of every slot at full context.
    """

    def __init__(self, model, params, *, max_slots: int = 4,
                 page_size: int = 8, max_pages_per_seq: int = 4,
                 num_pages: Optional[int] = None,
                 donate: Optional[bool] = None, warmup: bool = True,
                 name: str = "decode", registry=None):
        self.model = model
        self.params = params
        self.name = name
        self.registry = registry if registry is not None else get_registry()
        install_compile_listener()
        self.bucket_sizes = serve_buckets(max_slots)
        self.max_slots = self.bucket_sizes[-1]
        self.page_buckets = serve_buckets(max_pages_per_seq)
        self.max_pages_per_seq = self.page_buckets[-1]
        self.page_size = int(page_size)
        self.max_context = self.max_pages_per_seq * self.page_size
        if self.max_context > model.max_seq_len:
            raise ValueError(
                f"max context {self.max_context} "
                f"({self.max_pages_per_seq} pages x {self.page_size}) "
                f"exceeds model max_seq_len {model.max_seq_len}")
        if num_pages is None:
            # worst case every slot at full context, + the null page; the
            # HBM-headroom suggestion can only grow it (more slack for
            # admission before preemption kicks in)
            floor = 1 + self.max_slots * self.max_pages_per_seq
            probe = KVPagePool(num_layers=model.num_layers,
                               embed_dim=model.embed_dim,
                               page_size=self.page_size, num_pages=2)
            num_pages = max(floor, suggest_num_pages(
                probe.page_bytes, default=floor, registry=self.registry))
        self.pool = KVPagePool(num_layers=model.num_layers,
                               embed_dim=model.embed_dim,
                               page_size=self.page_size,
                               num_pages=num_pages)
        if donate is None:
            # donation is a no-op (plus a warning per compile) on CPU
            donate = jax.default_backend() in ("tpu", "gpu")
        self._donate = bool(donate)

        page_size_ = self.page_size
        blocks, bparams = model.blocks, params["blocks"]

        def step_fn(tokens, positions, page_table, pool_k, pool_v):
            b = tokens.shape[0]
            mp = page_table.shape[1]
            x = model.embed_tokens(params, tokens)
            active = positions >= 0
            pos_c = jnp.maximum(positions, 0)
            pg, slot = pos_c // page_size_, pos_c % page_size_
            rows = jnp.arange(b)
            # inactive rows scatter onto the null page (kvcache.py) —
            # colliding writes land where nothing ever reads
            phys = jnp.where(active, page_table[rows, pg], 0)
            for li, (blk, bp) in enumerate(zip(blocks, bparams)):
                q, k_t, v_t = blk.decode_qkv(bp, x)
                pool_k = pool_k.at[li, phys, slot].set(k_t)
                pool_v = pool_v.at[li, phys, slot].set(v_t)
                # gather each row's pages into a (b, mp*page, E) context;
                # table padding gathers the null page, masked to exact 0
                # by decode_attend (positions past pos are NEG_INF'd)
                ctx_k = pool_k[li][page_table].reshape(b, mp * page_size_,
                                                       -1)
                ctx_v = pool_v[li][page_table].reshape(b, mp * page_size_,
                                                       -1)
                y = blk.decode_attend(bp, q, ctx_k, ctx_v, positions)
                x = jax.nn.relu(y + x)
            logits = model.head(params, x)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, logits, pool_k, pool_v

        donate_argnums = (3, 4) if self._donate else ()
        jitted = jax.jit(step_fn, donate_argnums=donate_argnums)
        pool_spec = jax.ShapeDtypeStruct(self.pool.k.shape, self.pool.dtype)
        self._sessions: Dict[Tuple[int, int], Any] = {}
        self.compile_stats: Dict[Tuple[int, int], Dict[str, float]] = {}
        tracer = get_tracer()
        for b in self.bucket_sizes:
            for mp in self.page_buckets:
                specs = (jax.ShapeDtypeStruct((b,), jnp.int32),
                         jax.ShapeDtypeStruct((b,), jnp.int32),
                         jax.ShapeDtypeStruct((b, mp), jnp.int32),
                         pool_spec, pool_spec)
                t0 = time.perf_counter()
                with tracer.span("serve.compile", track="serve",
                                 engine=name, bucket=b, pages=mp):
                    session = jitted.lower(*specs).compile()
                compile_s = time.perf_counter() - t0
                record_compile(compile_s, what="decode",
                               registry=self.registry)
                t0 = time.perf_counter()
                if warmup:
                    with tracer.span("serve.warmup", track="serve",
                                     engine=name, bucket=b, pages=mp):
                        # all-inactive warmup batch: writes touch only
                        # the null page, so warmed sessions never dirty
                        # real cache state
                        jax.block_until_ready(session(
                            jnp.zeros((b,), jnp.int32),
                            jnp.full((b,), -1, jnp.int32),
                            jnp.zeros((b, mp), jnp.int32),
                            jnp.zeros(self.pool.k.shape, self.pool.dtype),
                            jnp.zeros(self.pool.k.shape, self.pool.dtype)))
                self._sessions[(b, mp)] = session
                st = {"compile_s": round(compile_s, 4),
                      "warmup_s": round(time.perf_counter() - t0, 4)}
                cost = executable_cost(session)
                if cost is not None:
                    st.update({k: cost[k] for k in
                               ("flops", "bytes_accessed", "temp_bytes")
                               if k in cost})
                self.compile_stats[(b, mp)] = st
        # post-compile HBM watermark: pool + every bucket's executables
        # is the decode-side allocation spike; no-op without memory stats
        sample_hbm(self.registry)

    # -- bucket math --
    def bucket_for(self, n: int) -> int:
        """Smallest batch bucket >= n active slots."""
        if not 1 <= n <= self.max_slots:
            raise ValueError(f"active count {n} outside [1, "
                             f"{self.max_slots}]")
        for b in self.bucket_sizes:
            if b >= n:
                return b
        raise AssertionError("unreachable: last bucket is max_slots")

    def page_bucket_for(self, pages: int) -> int:
        """Smallest page-table width bucket >= pages (min 1: even a
        0-length table dispatches at width 1, all null-page)."""
        pages = max(pages, 1)
        if pages > self.max_pages_per_seq:
            raise ValueError(f"{pages} pages exceeds max_pages_per_seq "
                             f"{self.max_pages_per_seq}")
        for mp in self.page_buckets:
            if mp >= pages:
                return mp
        raise AssertionError("unreachable: last bucket is max_pages_per_seq")

    # -- dispatch --
    def run_step(self, tokens, positions, page_table, pool_k, pool_v):
        """Pure bucketed step: shapes must already be exact buckets.
        Returns ``(next_tokens, logits, pool_k, pool_v)`` — the caller
        owns the pool handoff (on accelerator backends the input pools
        are DONATED/consumed). :func:`decode_reference` runs on private
        pools through this; :meth:`step` wraps it over :attr:`pool`."""
        key = (int(tokens.shape[0]), int(page_table.shape[1]))
        session = self._sessions.get(key)
        if session is None:
            raise ValueError(f"no session for (batch, pages)={key}; have "
                             f"{sorted(self._sessions)}")
        return session(jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(positions, jnp.int32),
                       jnp.asarray(page_table, jnp.int32), pool_k, pool_v)

    def step(self, tokens, positions, page_table):
        """One decode step against the engine's own page pool; updates
        :attr:`pool` in place and returns ``(next_tokens, logits)`` as
        host arrays."""
        nxt, logits, k, v = self.run_step(tokens, positions, page_table,
                                          self.pool.k, self.pool.v)
        self.pool.k, self.pool.v = k, v
        return np.asarray(nxt), np.asarray(logits)

    def __repr__(self) -> str:
        return (f"DecodeEngine({self.name!r}, slots={self.bucket_sizes}, "
                f"page_buckets={self.page_buckets}, "
                f"page_size={self.page_size}, "
                f"pool_pages={self.pool.num_pages})")


def decode_reference(engine: DecodeEngine, prompt: Sequence[int], *,
                     max_new_tokens: int = 16,
                     eos_id: Optional[int] = None) -> np.ndarray:
    """Batch-of-one greedy decode of ``prompt`` through the SAME compiled
    sessions the continuous batcher uses — batch bucket 1, page bucket
    following the sequence's own length — on a private zeroed pool (the
    engine's live pool and allocator are untouched). This is the
    per-sequence oracle the bit-identity tests compare the continuous
    batcher against, and the naive baseline the ``BENCH_DECODE`` block
    measures."""
    prompt = [int(t) for t in prompt]
    if not prompt:
        raise ValueError("empty prompt")
    if len(prompt) + max_new_tokens > engine.max_context:
        raise ValueError(f"prompt {len(prompt)} + max_new {max_new_tokens} "
                         f"exceeds max context {engine.max_context}")
    pool_k = jnp.zeros(engine.pool.k.shape, engine.pool.dtype)
    pool_v = jnp.zeros(engine.pool.k.shape, engine.pool.dtype)
    ps = engine.page_size
    tokens = list(prompt)
    generated: List[int] = []
    pos = 0
    while True:
        mp = engine.page_bucket_for(-(-(pos + 1) // ps))
        table = np.zeros((1, mp), np.int32)
        npages = -(-(pos + 1) // ps)
        table[0, :npages] = np.arange(1, npages + 1)
        nxt, _, pool_k, pool_v = engine.run_step(
            np.asarray([tokens[pos]], np.int32),
            np.asarray([pos], np.int32), table, pool_k, pool_v)
        emit = pos == len(tokens) - 1
        pos += 1
        if emit:
            tok = int(np.asarray(nxt)[0])
            tokens.append(tok)
            generated.append(tok)
            if len(generated) >= max_new_tokens or tok == eos_id:
                return np.asarray(generated, np.int32)


class _Seq:
    """One accepted decode request and its slot-resident state."""

    __slots__ = ("seq_id", "tokens", "prompt_len", "max_new_tokens",
                 "eos_id", "future", "t_submit", "first_emit",
                 "generated", "pos")

    def __init__(self, seq_id, prompt, max_new_tokens, eos_id, future,
                 t_submit):
        self.seq_id = seq_id
        self.tokens: List[int] = list(prompt)
        self.prompt_len = len(prompt)
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.future = future
        self.t_submit = t_submit
        self.first_emit = False
        self.generated: List[int] = []
        self.pos = 0  # tokens consumed; a step emits iff pos==len(tokens)-1


class ContinuousBatcher:
    """Iteration-level scheduler over a :class:`DecodeEngine`.

    Each :meth:`step` (one fixed-shape engine dispatch): retire finished
    sequences → admit pending ones into free slots (``decode.admit`` trip
    point) → extend page allocations (preempting the most-recently-
    admitted sequence on :class:`~dcnn_tpu.serve.kvcache.OutOfPagesError`
    — it re-queues and recomputes bit-identically) → dispatch at the
    smallest (batch, page) bucket covering the active set (``decode.step``
    trip point; zero compiles — every bucket pair was built in the engine
    constructor).

    Failure contract mirrors ``DynamicBatcher``: every accepted future is
    ledgered and ALWAYS resolved — completion, typed rejection
    (:class:`~dcnn_tpu.serve.batcher.ShutdownError` on teardown), or the
    step's exception. A crash mid-step (``InjectedCrash``) fails every
    pending + active sequence typed before propagating: no silent drops.

    ``start=False`` runs no thread — tests drive :meth:`step` with an
    injected ``clock``, sleep-free.
    """

    def __init__(self, engine: DecodeEngine, *,
                 max_slots: Optional[int] = None,
                 queue_capacity: int = 64,
                 metrics: Optional[DecodeMetrics] = None,
                 clock: Callable[[], float] = time.monotonic,
                 start: bool = True):
        if queue_capacity < 1:
            raise ValueError(f"queue_capacity must be >= 1, "
                             f"got {queue_capacity}")
        self.engine = engine
        self.max_slots = min(max_slots or engine.max_slots,
                             engine.max_slots)
        self.queue_capacity = queue_capacity
        self.metrics = metrics if metrics is not None else DecodeMetrics(
            clock=clock)
        self._clock = clock
        self._cond = threading.Condition()
        self._pending: deque = deque()  # dcnn: guarded_by=_cond
        self._active: List[_Seq] = []  # dcnn: guarded_by=_cond
        # every accepted, unresolved future: the no-orphan ledger
        self._accepted: set = set()  # dcnn: guarded_by=_cond
        self._closing = False  # dcnn: guarded_by=_cond
        self._steps = 0
        self._next_id = 0
        self._thread: Optional[threading.Thread] = None
        if start:
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name=f"dcnn-decode-batcher-{engine.name}")
            self._thread.start()

    # -- intake --
    def submit(self, prompt: Sequence[int], *, max_new_tokens: int = 16,
               eos_id: Optional[int] = None) -> Future:
        """Enqueue one greedy-decode request. The future resolves to the
        generated token ids as an int32 array (EOS token included when it
        fired). Raises :class:`~dcnn_tpu.serve.batcher.QueueFullError` at
        capacity and :class:`~dcnn_tpu.serve.batcher.DrainingError` after
        :meth:`drain`/:meth:`shutdown`."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        vocab = self.engine.model.vocab_size
        if any(not 0 <= t < vocab for t in prompt):
            raise ValueError(f"prompt tokens outside [0, {vocab})")
        if len(prompt) + max_new_tokens > self.engine.max_context:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                f"exceeds engine max context {self.engine.max_context}")
        fut: Future = Future()
        with self._cond:
            if self._closing:
                raise DrainingError(
                    "decode batcher is draining or shut down")
            if len(self._pending) >= self.queue_capacity:
                self.metrics.record_shed()
                raise QueueFullError(
                    f"decode queue at capacity ({len(self._pending)}/"
                    f"{self.queue_capacity} sequences)")
            seq = _Seq(self._next_id, prompt, max_new_tokens, eos_id, fut,
                       self._clock())
            self._next_id += 1
            self._pending.append(seq)
            self._accepted.add(fut)
            self.metrics.record_submit()
            self.metrics.record_queue_depth(len(self._pending))
            self._cond.notify_all()
        return fut

    @property
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._pending)

    @property
    def active_slots(self) -> int:
        with self._cond:
            return len(self._active)

    def health_reason(self) -> Optional[str]:
        """``None`` while accepting traffic, else the machine-readable
        refusal — the same ``/healthz`` contract as ``DynamicBatcher``."""
        with self._cond:
            closing = self._closing
        if closing:
            return "draining or shut down: not accepting sequences"
        if self._thread is not None and not self._thread.is_alive():
            return "decode scheduler thread dead"
        return None

    # -- scheduling core --
    def _admit(self) -> None:
        """Move pending sequences into free slots (a step-boundary
        operation — never mid-step). An ``InjectedFault`` at
        ``decode.admit`` fails just that sequence, typed; a crash
        propagates to :meth:`step`'s fail-everything handler."""
        with self._cond:
            while self._pending and len(self._active) < self.max_slots:
                seq = self._pending[0]
                try:
                    faults.trip("decode.admit", seq=seq.seq_id)
                except InjectedCrash:
                    raise
                except Exception as e:
                    self._pending.popleft()
                    self._accepted.discard(seq.future)
                    try:
                        seq.future.set_exception(e)
                    except InvalidStateError:
                        pass
                    continue
                try:
                    self.engine.pool.ensure(seq.seq_id, 1)
                except OutOfPagesError:
                    break  # no room for even one page: admit next step
                self._pending.popleft()
                self._active.append(seq)
                self.metrics.record_admit()
            self.metrics.record_queue_depth(len(self._pending))

    def _preempt_last(self) -> bool:
        """Recompute-preemption: release the most-recently-admitted active
        sequence's pages and re-queue it at the FRONT of pending (it
        re-admits first; greedy decode replays its tokens bit-exactly).
        Returns False when there is nothing to preempt."""
        with self._cond:
            if not self._active:
                return False
            victim = self._active.pop()
            self.engine.pool.release(victim.seq_id)
            victim.pos = 0  # replay prompt + already-generated tokens
            self._pending.appendleft(victim)
            self.metrics.record_evict()
            self.metrics.record_queue_depth(len(self._pending))
        return True

    def _fail_all(self, exc: BaseException) -> int:
        """Fail every accepted, unresolved future with ``exc`` and release
        all pages — the no-orphan guarantee when a step dies. Returns how
        many futures this call failed."""
        with self._cond:
            seqs = list(self._active) + list(self._pending)
            self._active.clear()
            self._pending.clear()
            pending = set(self._accepted)
            self._accepted.clear()
            self.metrics.record_queue_depth(0)
        for s in seqs:
            self.engine.pool.release(s.seq_id)
        failed = 0
        for fut in pending:
            try:
                fut.set_exception(exc if isinstance(exc, Exception)
                                  else ShutdownError(str(exc)))
                failed += 1
            except InvalidStateError:
                pass  # resolved/cancelled while we swept
        return failed

    def step(self) -> int:
        """One scheduler iteration: admit, allocate, dispatch one engine
        step, retire completions. Returns the number of active sequences
        stepped (0 = nothing to do). Any dispatch exception — including
        an injected crash — fails every accepted sequence typed and then
        propagates: the batcher never silently drops work it accepted."""
        self._admit()
        with self._cond:
            active = list(self._active)
        if not active:
            return 0
        try:
            # page allocation for this step's positions, preempting the
            # newest sequence (possibly the grower itself) until it fits
            i = 0
            while i < len(active):
                seq = active[i]
                try:
                    self.engine.pool.ensure(seq.seq_id, seq.pos + 1)
                    i += 1
                except OutOfPagesError:
                    if not self._preempt_last():
                        raise
                    with self._cond:
                        active = [s for s in active if s in self._active]
                    i = min(i, len(active))
            if not active:
                return 0
            b = self.engine.bucket_for(len(active))
            mp = self.engine.page_bucket_for(max(
                self.engine.pool.num_seq_pages(s.seq_id) for s in active))
            tokens = np.zeros(b, np.int32)
            positions = np.full(b, -1, np.int32)
            table = np.zeros((b, mp), np.int32)
            for i, seq in enumerate(active):
                tokens[i] = seq.tokens[seq.pos]
                positions[i] = seq.pos
                table[i] = self.engine.pool.table(seq.seq_id, mp)
            faults.trip("decode.step", step=self._steps)
            tracer = get_tracer()
            with tracer.span("decode.step", track="decode",
                             active=len(active), bucket=b, pages=mp):
                nxt, _ = self.engine.step(tokens, positions, table)
        except BaseException as e:
            # fail-everything-typed, then propagate (an InjectedCrash is
            # the process dying: the thread/test sees it re-raised, and
            # every accepted future is already resolved — no orphans)
            with self._cond:
                self._closing = True
            self._fail_all(e)
            raise
        self._steps += 1
        now = self._clock()
        done: List[_Seq] = []
        for i, seq in enumerate(active):
            emit = seq.pos == len(seq.tokens) - 1
            seq.pos += 1
            if not emit:
                # prefill (or post-preemption replay): KV written, output
                # already known
                self.metrics.record_prefill()
                continue
            tok = int(nxt[i])
            seq.tokens.append(tok)
            seq.generated.append(tok)
            self.metrics.record_token()
            if not seq.first_emit:
                seq.first_emit = True
                self.metrics.record_ttft(max(now - seq.t_submit, 0.0))
            if (len(seq.generated) >= seq.max_new_tokens
                    or tok == seq.eos_id):
                done.append(seq)
        for seq in done:
            self.engine.pool.release(seq.seq_id)
            with self._cond:
                if seq in self._active:
                    self._active.remove(seq)
                self._accepted.discard(seq.future)
            try:
                seq.future.set_result(np.asarray(seq.generated, np.int32))
            except InvalidStateError:
                pass  # failed by a timed-out drain racing this step
            self.metrics.record_complete()
        self.metrics.record_step(len(active), self.max_slots)
        self.metrics.record_pages(self.engine.pool.pages_in_use)
        return len(active)

    def _loop(self) -> None:
        while True:
            with self._cond:
                while (not self._pending and not self._active
                       and not self._closing):
                    self._cond.wait()
                if self._closing and not self._pending and not self._active:
                    return
            try:
                self.step()
            except BaseException:
                # step() already failed every accepted future typed; a
                # crashed scheduler thread reports through health_reason
                return

    # -- teardown --
    def drain(self, timeout: Optional[float] = None) -> None:
        """Stop intake; decode everything already accepted to completion.
        If ``timeout`` trips, still-pending futures fail with
        :class:`~dcnn_tpu.serve.batcher.ShutdownError` (never orphaned)
        and ``TimeoutError`` raises."""
        with self._cond:
            self._closing = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                n = self._fail_all(ShutdownError(
                    f"decode drain timed out after {timeout}s"))
                raise TimeoutError(
                    f"decode drain did not finish in {timeout}s "
                    f"({n} pending sequence(s) failed with ShutdownError)")
            self._thread = None
        else:
            while self.step():
                pass

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """``drain=True``: :meth:`drain`. ``drain=False``: fail every
        accepted, unfinished sequence with
        :class:`~dcnn_tpu.serve.batcher.ShutdownError`."""
        if drain:
            self.drain(timeout)
            return
        with self._cond:
            self._closing = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        self._fail_all(ShutdownError("decode batcher shut down without "
                                     "drain"))

    def __enter__(self) -> "ContinuousBatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=exc == (None, None, None))

    def __repr__(self) -> str:
        return (f"ContinuousBatcher(engine={self.engine.name!r}, "
                f"max_slots={self.max_slots}, "
                f"capacity={self.queue_capacity})")
