"""The host's always-on logs: every resident dispatch of a trainer, and the
phases of set-up, stamped with ``time.perf_counter()``.

Both are ``compile_log()``'s make (``obs/xla.py``): a bounded deque the
program appends to whether or not the tracer's ring is on, which a reader
copies and filters by its own window on the same clock. A span says where a
second went while a capture or the ring is running; these say it for every
run, at one ``append`` a resident epoch and one a phase.

- :func:`dispatch_log`: one :class:`Dispatch` a resident epoch
  (``Trainer.train_epoch`` over a ``DeviceDataset``), from the call of the
  epoch's program to the state being published.
- :func:`phase`: the context manager (and decorator) round a phase of
  set-up: the tracer's span of that name, and ``(name, t0, t1)`` in
  :func:`phase_log`.

Stdlib-only, like the rest of ``dcnn_tpu.obs``.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import List, NamedTuple, Optional, Tuple

from .registry import get_registry
from .tracer import get_tracer

_LOG_CAP = 1024


class Dispatch(NamedTuple):
    """One resident epoch on the host's clock. ``t_published`` is
    ``t_fenced`` where the model publishes nothing."""

    t_call: float        # the epoch's program is called
    t_returned: float    # ... and has returned: traced, compiled or loaded
                         # on a first call, its arguments up, launched
    t_fenced: float      # float(mean_loss) has returned: the device is done
    t_published: float   # the model's publish_state has returned
    steps: int
    first: bool          # the trainer's first call of that program


_dispatch_log: deque = deque(maxlen=_LOG_CAP)
_phase_log: deque = deque(maxlen=_LOG_CAP)


def log_dispatch(entry: Dispatch, turn: Optional[float] = None) -> None:
    """Append ``entry`` and count it: ``train_dispatches_total``,
    ``train_dispatch_seconds_total`` (call to return),
    ``train_fence_seconds_total`` (return to fence) and, where the trainer
    had fenced on a dispatch before this one, ``train_turn_seconds_total``
    (that fence to this return: ``turn``). Once an epoch."""
    _dispatch_log.append(entry)
    reg = get_registry()
    reg.counter("train_dispatches_total",
                "resident epochs dispatched (one program call each)").inc()
    reg.counter("train_dispatch_seconds_total",
                "wall seconds in the calls of resident epochs' programs, "
                "to their return: arguments up, launch, and on a first call "
                "tracing, lowering and the compile or cache load"
                ).inc(max(entry.t_returned - entry.t_call, 0.0))
    reg.counter("train_fence_seconds_total",
                "wall seconds from a resident dispatch's return to its "
                "loss on the host: the device running the epoch"
                ).inc(max(entry.t_fenced - entry.t_returned, 0.0))
    if turn is not None:
        reg.counter("train_turn_seconds_total",
                    "wall seconds from one resident epoch's fence to the "
                    "next one's dispatch having returned: publish, the "
                    "caller's own work between two epochs, and the dispatch "
                    "call").inc(max(turn, 0.0))


def dispatch_log() -> List[Dispatch]:
    """The newest 1,024 resident dispatches of this process, oldest first.
    The host's turn before an entry is its ``t_returned`` less the
    ``t_fenced`` of the entry before it. The log keeps no trainer's name: in
    a process that drives several trainers at once, read one's turns from
    its ``train.turn`` spans."""
    return list(_dispatch_log)


@contextlib.contextmanager
def phase(name: str):
    """A phase of set-up: ``with phase("setup.model"):`` or
    ``@phase("setup.model")`` on the function that is the phase. Opens the
    tracer's span ``name`` (so the phase lies in the ring and in a running
    capture) and appends ``(name, t0, t1)`` to :func:`phase_log`."""
    t0 = time.perf_counter()
    try:
        # the names are the callers' literals; "setup.*" has its row in
        # goodput.SPAN_BUCKETS
        with get_tracer().span(name, track="setup"):  # dcnn: disable=GP01
            yield
    finally:
        _phase_log.append((name, t0, time.perf_counter()))


def phase_log() -> List[Tuple[str, float, float]]:
    """The newest 1,024 phases :func:`phase` closed, oldest first:
    ``(name, t0, t1)``."""
    return list(_phase_log)
