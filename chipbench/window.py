"""The measured window, as arithmetic on an injectable clock.

A window opens at a step boundary and closes at the first step boundary at
or after ``seconds``: ``boundary()`` is called by the driver's loop (or the
loader wrapper it hands the program) at every fence between steps, and
returns False once the window is over. The rate divides the images counted
by the wall time actually elapsed to that last fence, never by the nominal
length, so a step more or less changes nothing.

The same object tells a traced run when to stop the profiler: the traced
stretch is the first ``trace_seconds`` of the window, closed at a boundary
too. ``on_boundary`` is called at every boundary while the window is open:
the harness reads the chip's memory there, at one instant inside the window.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Tuple


class Window:
    def __init__(self, seconds: float, clock: Callable[[], float] = time.perf_counter,
                 trace_seconds: float = 0.0,
                 on_trace_end: Optional[Callable[[], None]] = None,
                 on_boundary: Optional[Callable[[], None]] = None):
        self.seconds = float(seconds)
        self.clock = clock
        self.trace_seconds = float(trace_seconds)
        self.on_trace_end = on_trace_end
        self.on_boundary = on_boundary     # a reading taken inside the window
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.t_trace_end: Optional[float] = None
        self.traced_images = 0
        self.images = 0
        self.steps = 0
        self.epoch_turns = 0
        self.marks: List[float] = []   # the clock at every boundary

    # -- life cycle --
    def open(self) -> None:
        self.t_open = self.clock()

    @property
    def is_open(self) -> bool:
        return self.t_open is not None and self.t_close is None

    def boundary(self, images_done: int = 0) -> bool:
        """A fence between two steps: ``images_done`` images finished since
        the last boundary. True while the window goes on."""
        if not self.is_open:
            return False
        now = self.clock()
        self.marks.append(now)
        if images_done:
            self.images += images_done
            self.steps += 1
        if self.on_boundary is not None:
            self.on_boundary()
        if (self.on_trace_end is not None and self.t_trace_end is None
                and now - self.t_open >= self.trace_seconds):
            self.t_trace_end = now
            self.traced_images = self.images
            self.on_trace_end()
        if now - self.t_open >= self.seconds:
            self.t_close = now
            return False
        return True

    def epoch_turn(self) -> None:
        if self.is_open:
            self.epoch_turns += 1

    # -- readings --
    @property
    def elapsed(self) -> float:
        return self.t_close - self.t_open

    @property
    def images_per_s(self) -> float:
        return self.images / self.elapsed

    @property
    def traced_s(self) -> float:
        return self.t_trace_end - self.t_open

    def step_seconds(self) -> List[float]:
        """The time between consecutive boundaries (a step, or an epoch turn
        where one falls between two steps)."""
        return [b - a for a, b in zip(self.marks, self.marks[1:])]


def epoch_turns_in_window(start_step: int, steps_per_epoch: int,
                          steps_in_window: int) -> int:
    """How many epoch turn-overs a window holds that opens before step
    ``start_step`` (0-based) of an epoch and runs ``steps_in_window`` steps:
    fixed by construction, since the window always opens at the same step."""
    return (start_step + steps_in_window - 1) // steps_per_epoch
