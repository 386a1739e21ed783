"""Chunked multi-stream host→device transfer engine.

A per-shard blocking ``device_put`` — one serial gather + one serial wire
transfer per shard — puts the whole wire time on the epoch wall. The
reference DCNN hides exactly
this cost with a chunk-threaded batch loader
(``include/data_loading/data_loader.hpp`` prepare_batches + to_device);
this module is the TPU-native analog for the H2D wire itself.

Each shard is split into C row-range chunks. A small pool of transfer
threads gathers each chunk (chunk-parallel native memcpy,
``native.gather_rows``, numpy fallback) and ships it with its own
``device_put`` + hard fence, so **multiple H2D copies are in flight
concurrently** and the gather for chunk k+1 overlaps the wire time of
chunk k. The chunks are then either

- handed to the consumer as a tuple (``reassemble="chunks"``) — a jitted
  consumer (``streaming.make_shard_step``) concatenates them inside its own
  dispatch, so no separate device-side copy runs; or
- reassembled by one jitted on-device concatenate (``reassemble="concat"``)
  for consumers that need a single array (``PrefetchLoader``).

Numerics: chunking is pure data movement — ``concat(split(x)) == x`` bytes —
so the chunked feed is bit-identical to the monolithic ``device_put`` path
(asserted in ``tests/test_transfer.py``).

Measurement surface: every shipment returns a stats dict with per-chunk
spans (gather/put walls + absolute start/end), the peak number of
concurrently in-flight transfers, and the effective H2D rate over the union
of the put spans — the inputs the overlap accounting in RESULTS.md needs to
attribute the win.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import native
from ..core.fence import hard_fence
from ..obs import get_registry, get_tracer


def chunk_bounds(n: int, num_chunks: int) -> List[Tuple[int, int]]:
    """Split ``range(n)`` into up to ``num_chunks`` contiguous, non-empty,
    balanced spans. When ``num_chunks`` does not divide ``n`` the remainder
    is spread one row at a time over the leading chunks (sizes differ by at
    most 1 — no pathological ragged tail); when ``n < num_chunks`` only
    ``n`` single-row chunks are produced."""
    if n < 0:
        raise ValueError(f"chunk_bounds: negative n {n}")
    if num_chunks < 1:
        raise ValueError(f"chunk_bounds: num_chunks must be >= 1, "
                         f"got {num_chunks}")
    c = min(num_chunks, n)
    if c == 0:
        return []
    base, extra = divmod(n, c)
    bounds, lo = [], 0
    for k in range(c):
        hi = lo + base + (1 if k < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def max_inflight(spans: Sequence[dict]) -> int:
    """Peak number of simultaneously open ``[put_start_t, put_end_t)``
    intervals — post-hoc concurrency evidence from recorded chunk spans."""
    events = []
    for s in spans:
        events.append((s["put_start_t"], 1))
        events.append((s["put_end_t"], -1))
    events.sort()
    cur = peak = 0
    for _, d in events:
        cur += d
        peak = max(peak, cur)
    return peak


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total wall covered by the union of ``(lo, hi)`` intervals —
    overlapping spans must not double-count toward an effective-rate wall.
    Shared by the transfer engine's put accounting and bench.py's
    worker-prep accounting."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


@jax.jit
def _device_concat(parts):
    return jnp.concatenate(parts, axis=0)


class TransferEngine:
    """A pool of transfer threads shipping host arrays to device in chunks.

    Args:
      num_chunks: chunks per shipment (C). 1 + ``reassemble="concat"``
        degenerates to exactly the monolithic gather-then-one-``device_put``
        path (the bit-identity reference in tests).
      num_threads: pool size — the bound on concurrently in-flight H2D
        copies. 2 is enough to pipeline a latency-bound wire; more mostly
        grows host-side pinning.
      device: target ``jax.Device`` (default: ``jax.devices()[0]``).
      reassemble: ``"chunks"`` returns the chunk tuple (a jitted consumer
        concatenates in its own dispatch — zero extra device copies);
        ``"concat"`` returns one array via a jitted on-device concatenate.
      fence: hard-fence each chunk on its transfer thread (default).
        ``device_put`` returns at issue; fencing on the pool thread makes
        the spans measure the transfer and paces the pool on real
        completion, while the caller's dispatches still overlap it.
    """

    def __init__(self, *, num_chunks: int = 4, num_threads: int = 2,
                 device=None, reassemble: str = "chunks", fence: bool = True):
        if num_chunks < 1:
            raise ValueError(f"num_chunks must be >= 1, got {num_chunks}")
        if num_threads < 1:
            raise ValueError(f"num_threads must be >= 1, got {num_threads}")
        if reassemble not in ("chunks", "concat"):
            raise ValueError(f"reassemble must be 'chunks' or 'concat', "
                             f"got {reassemble!r}")
        self.num_chunks = int(num_chunks)
        self.num_threads = int(num_threads)
        self.reassemble = reassemble
        self.fence = fence
        self._device = device if device is not None else jax.devices()[0]
        self._pool = ThreadPoolExecutor(max_workers=self.num_threads,
                                        thread_name_prefix="h2d-xfer")
        self._lock = threading.Lock()
        self._inflight = 0
        self._closed = False
        # registry instruments hoisted: fixed names, resolved once — the
        # per-shipment path only touches the instruments' own O(1) ops
        reg = get_registry()
        self._m_bytes = reg.counter("h2d_bytes_total",
                                    "bytes shipped host->device")
        self._m_chunks = reg.counter("h2d_chunks_total",
                                     "chunk transfers issued")
        self._m_put_s = reg.histogram("h2d_put_seconds",
                                      "per-shipment union of put spans")
        self._m_inflight = reg.gauge("h2d_inflight_max",
                                     "peak concurrent puts, last shipment")
        self._m_gbps = reg.gauge("h2d_gbps",
                                 "effective H2D rate, last shipment")

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._pool.shutdown(wait=True)

    def __enter__(self) -> "TransferEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- internals ---------------------------------------------------------
    def _ship_chunk(self, k: int, arr: np.ndarray, sel, lo: int, hi: int,
                    t_base: float, peak: list):
        """One pool task: gather rows [lo, hi) (of ``sel`` when given, of
        ``arr`` itself otherwise) and push them through their own
        ``device_put``. Returns (device_chunk, span_dict).

        Each phase is also a tracer span (``h2d.gather`` / ``h2d.put``,
        ``dcnn_tpu.obs``): the pool threads give each in-flight chunk its
        own labeled track in the Chrome trace, so transfer overlap is
        *visible*, not just summarized by ``inflight_max``. The local
        span-dict bookkeeping stays — ``inflight_max``/``h2d_gbps`` are
        derived per shipment from it and must work with tracing off."""
        tracer = get_tracer()
        t0 = time.perf_counter()
        with tracer.span("h2d.gather", chunk=k, rows=hi - lo):
            if sel is not None:
                part = native.gather_rows(arr, sel[lo:hi])
            else:
                part = arr[lo:hi]  # contiguous view — no host copy
        t1 = time.perf_counter()
        with self._lock:
            self._inflight += 1
            peak[0] = max(peak[0], self._inflight)
        try:
            # fenced on this pool thread, so the span measures the actual
            # transfer, not async dispatch (module docstring / fence=)
            with tracer.span("h2d.put", chunk=k, rows=hi - lo,
                             bytes=int(part.nbytes)):
                d = jax.device_put(part, self._device)
                if self.fence:
                    hard_fence(d)
        finally:
            with self._lock:
                self._inflight -= 1
        t2 = time.perf_counter()
        span = {"chunk": k, "rows": hi - lo, "bytes": int(part.nbytes),
                "gather_s": t1 - t0, "put_s": t2 - t1,
                "put_start_t": t1 - t_base, "put_end_t": t2 - t_base}
        return d, span

    def _submit(self, arr: np.ndarray, sel, t_base: float, peak: list):
        """Queue the chunk tasks and return their futures without waiting —
        the caller can overlap its own host work (e.g. the label put) with
        the in-flight chunk transfers before collecting."""
        if self._closed:
            raise RuntimeError("TransferEngine is closed")
        n = int(sel.shape[0]) if sel is not None else int(arr.shape[0])
        # zero rows (an empty tail from a filtering loader) still ships one
        # empty chunk so the caller always gets a well-formed device array /
        # 1-tuple back, exactly like a bare device_put of the empty array
        bounds = chunk_bounds(n, self.num_chunks) or [(0, 0)]
        return [self._pool.submit(self._ship_chunk, k, arr, sel, lo, hi,
                                  t_base, peak)
                for k, (lo, hi) in enumerate(bounds)]

    @staticmethod
    def _collect(futs):
        """Await all chunk futures. A failure in any task (gather error,
        transfer OOM) re-raises here after the remaining tasks
        settle — never a silent partial shard."""
        results, first_err = [], None
        for f in futs:
            try:
                results.append(f.result())
            except BaseException as e:  # noqa: BLE001 — re-raised below
                if first_err is None:
                    first_err = e
        if first_err is not None:
            raise first_err
        chunks = tuple(d for d, _ in results)
        spans = [s for _, s in results]
        return chunks, spans

    @staticmethod
    def _stats(spans: List[dict], peak: int, wall_s: float) -> dict:
        total_bytes = sum(s["bytes"] for s in spans)
        put_union = union_seconds(
            [(s["put_start_t"], s["put_end_t"]) for s in spans])
        return {
            "chunks": spans,
            "gather_s": sum(s["gather_s"] for s in spans),
            "put_s": put_union,
            "wall_s": wall_s,
            "bytes": total_bytes,
            "inflight_max": peak,
            "h2d_gbps": (total_bytes / put_union / 1e9) if put_union > 0
                        else None,
        }

    # -- API ---------------------------------------------------------------
    def put_shard(self, x: np.ndarray, y: Optional[np.ndarray] = None,
                  sel: Optional[np.ndarray] = None, *,
                  t_base: Optional[float] = None):
        """Ship one shard: ``x`` chunked across the pool, ``y`` (labels —
        a few KB next to multi-MB image payloads) as a single put issued on
        the calling thread while the chunks fly. ``sel`` selects rows of
        both (the per-epoch shard permutation); each chunk gathers its own
        row range inside its pool task, so the gather itself is
        chunk-parallel.

        Returns ``(dx, dy, stats)`` where ``dx`` is a chunk tuple or one
        concatenated array per ``reassemble`` and ``stats`` carries the
        per-chunk spans / ``inflight_max`` / effective ``h2d_gbps``."""
        t_base = time.perf_counter() if t_base is None else t_base
        t_call0 = time.perf_counter()
        tracer = get_tracer()
        shard_span = tracer.begin("h2d.shard", track="h2d",
                                  rows=int(sel.shape[0] if sel is not None
                                           else x.shape[0]))
        try:
            peak = [0]
            futs = self._submit(x, sel, t_base, peak)
            dy = None
            if y is not None:
                with tracer.span("h2d.put_labels", track="h2d"):
                    yy = y if sel is None else native.gather_rows(y, sel)
                    dy = jax.device_put(yy, self._device)
                    if self.fence:
                        hard_fence(dy)
            chunks, spans = self._collect(futs)
            if self.reassemble == "concat":
                dx = (chunks[0] if len(chunks) == 1
                      else _device_concat(chunks))
            else:
                dx = chunks
            wall = time.perf_counter() - t_call0
            stats = self._stats(spans, peak[0], wall)
        except BaseException as e:
            # close the cross-thread span on the failure path too (incl.
            # reassembly OOM) — the shipment being debugged must not be
            # the one missing from the trace
            tracer.end(shard_span, error=type(e).__name__)
            raise
        tracer.end(shard_span, bytes=stats["bytes"],
                   inflight_max=stats["inflight_max"])
        # shared-registry rollups: the cumulative cross-shipment view the
        # per-call stats dict cannot give (docs/observability.md)
        self._m_bytes.inc(stats["bytes"])
        self._m_chunks.inc(len(spans))
        self._m_put_s.observe(stats["put_s"])
        self._m_inflight.set(stats["inflight_max"])
        if stats["h2d_gbps"] is not None:
            self._m_gbps.set(stats["h2d_gbps"])
        return dx, dy, stats

    def put_array(self, arr: np.ndarray):
        """Ship one array chunk-pipelined and return a SINGLE device array
        (jitted on-device concatenate) — the drop-in replacement for a bare
        ``jax.device_put`` used by ``PrefetchLoader`` and ``DeviceDataset``
        staging. NB: the reassembly transiently holds the chunks AND the
        concatenated output (~2x the array in device memory) — for a split
        sized close to HBM capacity, stage with a plain ``device_put``
        instead."""
        peak = [0]
        futs = self._submit(np.asarray(arr), None, time.perf_counter(), peak)
        chunks, _ = self._collect(futs)
        return chunks[0] if len(chunks) == 1 else _device_concat(chunks)
