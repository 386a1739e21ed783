"""The loader wrapper the harness hands the program in the ``fed`` driver. It ends iteration when the window is over (or after the
few batches of a checked step), tells the window of every step boundary, and
in a traced run puts a span round every wait for the wrapped loader and round
every step. In a ``--trace 0`` run it only counts."""

from __future__ import annotations

import numpy as np


class LoaderTap:
    """The loader the program's epoch driver sees. Everything it does not
    define is the wrapped loader's (``scale``, ``batch_size``, ``shuffle`` ...)."""

    def __init__(self, inner, bench):
        self._inner = inner
        self._bench = bench
        self._it = None
        self.limit = None          # batches to hand out in this call, or None
        self.window = None
        self.exhausted = False     # the wrapped loader's epoch ran out

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __len__(self):
        return len(self._inner)

    def restart(self):
        self.close()
        self._it = iter(self._inner)
        self.exhausted = False

    def close(self):
        if self._it is not None and hasattr(self._it, "close"):
            self._it.close()       # stops and joins the producer thread
        self._it = None

    def __iter__(self):
        if self._it is None:
            self.restart()
        handed, last = 0, 0
        while True:
            if self.window is not None:
                if not self.window.boundary(last):
                    return
            if self.limit is not None and handed >= self.limit:
                return
            with self._bench.span("next_batch"):
                try:
                    item = next(self._it)
                except StopIteration:
                    self.exhausted = True
                    return
            handed += 1
            # a batch is [B, C, H, W]; a staged chunk [K, B, C, H, W]
            last = int(np.prod(item[0].shape[:-3]))
            with self._bench.span("step"):
                yield item
