"""Driver ``fed``: every batch comes from host memory through
``dcnn_tpu/data``'s input pipeline — the uint8 loader with host crop-4 + flip,
wrapped by ``examples/common.with_prefetch`` at the trainer's defaults, and
consumed by ``Trainer.train_epoch``'s per-batch loop.

The harness hands the trainer a wrapper round that loader (``tap.LoaderTap``). It
ends iteration when the window is over (or after the few batches of a
checked step), tells the window of every step boundary, and in a traced run
puts a span round every wait for the loader. In a ``--trace 0`` run it only
counts.

The window opens at a fixed step of the first epoch (``warmup_steps`` of the
traffic file), so a window holds the same number of epoch turn-overs in every
run. The steps between the checked ones and the window (``warmup_steps`` less
``checked_steps``, more than the prefetch depth + 1) run back to back and
empty the queue that filled while the checked steps were read back, so the
window opens in the feed's steady state and counts no batch that was made
before it.

The reference rebuilds the checked steps' batches from the seeded arrays by
the loader's stated recipe: ``rng = default_rng(loader_seed + epoch)``; the
epoch's order is ``rng.permutation(n)``; for each batch in turn the crop
draws ``rng.random(B)`` (the apply mask, p = 1), ``rng.integers(0, 9, B)``
twice (row and column offsets into the batch zero-padded by 4) and the flip
draws ``rng.random(B) < 0.5`` (mirror the width axis); pixels decode as
``uint8 * (1/255)``.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tap import LoaderTap  # noqa: E402
from trainer_common import TrainerJob, one_hot  # noqa: E402


class Job(TrainerJob):

    def warm(self):
        import jax

        t, tr = self.trainer, self.bench.traffic
        leaves = jax.tree_util.tree_leaves
        self.tap = LoaderTap(self.loader, self.bench)
        self.tap.shuffle(self.epoch)
        self.tap.limit = 1
        losses, grad = [], None
        b1 = t.optimizer.beta1
        for _ in range(int(tr["checked_steps"])):
            self.state, loss, _ = t.train_epoch(self.state, self.tap,
                                                self.epoch_key(), self.epoch)
            losses.append(float(loss))
            if grad is None:
                m1 = jax.device_get(leaves(self.state.opt_state["m"]))
                grad = [a / (1.0 - b1) for a in m1]
        p = jax.device_get(leaves(self.state.params))
        p0 = leaves(self.params0)
        self.program = {"losses": losses, "grad": grad,
                        "change": [a - c for a, c in zip(p, p0)]}
        self.checked_epoch = self.epoch
        # back to back, no pause before the window: the queue is drained
        extra = int(tr["warmup_steps"]) - int(tr["checked_steps"])
        if extra <= int(tr["prefetch_depth"]) + 1:
            raise ValueError("warmup_steps has to leave more than prefetch_depth + 1 "
                             "steps after the checked ones, to drain the queue")
        if extra > 0:
            self.tap.limit = extra
            self.state, _, _ = t.train_epoch(self.state, self.tap,
                                             self.epoch_key(), self.epoch)
        self.batch = int(self.tap.batch_size)

    def run_window(self, window):
        t, tap = self.trainer, self.tap
        tap.limit, tap.window = None, window
        window.open()
        while window.is_open:
            self.state, loss, _ = t.train_epoch(self.state, tap,
                                                self.epoch_key(), self.epoch)
            self.losses.append(float(loss))
            if tap.exhausted and window.is_open:
                window.epoch_turn()
                with self.bench.span("epoch_turn"):
                    self.turn_epoch(float(loss))
                    tap.restart()

    def program_readings(self):
        return self.program

    def close(self):
        self.tap.close()
        del self.tap
        super().close()

    def reference_batches(self):
        """The first ``checked_steps`` batches of the checked epoch, by the
        recipe in this file's docstring, in float32."""
        tr, cfg = self.bench.traffic, self.bench.cfg
        n, bsz, pad = len(self.x), self.batch, 4
        rng = np.random.default_rng(int(self.tcfg.seed) + self.checked_epoch)
        order = rng.permutation(n)
        labels = one_hot(self.y, cfg["num_classes"])
        out = []
        for s in range(int(tr["checked_steps"])):
            take = order[s * bsz:(s + 1) * bsz]
            xb = self.x[take]
            rng.random(bsz)                       # the crop's apply mask, p = 1
            oy = rng.integers(0, 2 * pad + 1, size=bsz)
            ox = rng.integers(0, 2 * pad + 1, size=bsz)
            flip = rng.random(bsz) < 0.5
            padded = np.pad(xb, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
            h, w = xb.shape[2], xb.shape[3]
            rows_ = oy[:, None] + np.arange(h)[None, :]
            cols_ = ox[:, None] + np.arange(w)[None, :]
            crop = padded[np.arange(bsz)[:, None, None, None],
                          np.arange(xb.shape[1])[None, :, None, None],
                          rows_[:, None, :, None], cols_[:, None, None, :]]
            crop = np.where(flip[:, None, None, None], crop[..., ::-1], crop)
            out.append((crop.astype(np.float32) * np.float32(1.0 / 255.0),
                        labels[take]))
        return out

    def reference_readings(self, quantize_name=None, rows=None, skip_update=False):
        import jax

        import refrun
        return refrun.steps(self.bench.cfg, jax.device_put(self.params0),
                            jax.device_put(self.state0), self.reference_batches(),
                            self.lr0, quantize_name, rows, skip_update)
