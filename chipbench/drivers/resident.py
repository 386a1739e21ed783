"""Driver ``resident``: the train split staged once to HBM, each epoch one
dispatch (``RESIDENT=1``: ``DeviceDataset.from_loader`` with the example's
on-device crop-4 + flip, ``Trainer.train_epoch`` ->
``_train_epoch_resident``). The window is whole epochs.

The output check follows the first epoch, which is also the warm-up: the
timed call is one dispatch of ``steps_per_epoch`` optimizer steps, so what it
exposes is the epoch's mean loss and the state after it. The reference
repeats that epoch from the same weights on the same batches, which it
derives from the seed by the feed's stated recipe:

    kperm, kstep = split(epoch_key);  perm = permutation(fold_in(kperm, 0), n)
    batch i = rows perm[i*B:(i+1)*B], decoded pixel * (1/255)
    key_i = fold_in(kstep, i);  ka = fold_in(key_i, 0x0A6)
    crop:  km, ky, kx = split(fold_in(ka, 0), 3); offsets randint(ky|kx, 0, 9)
           into the batch zero-padded by 4
    flip:  uniform(fold_in(ka, 1)) < 0.5 mirrors the width axis

where ``epoch_key = fold_in(fold_in(PRNGKey(seed), epoch), epoch)``.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from trainer_common import TrainerJob  # noqa: E402


class Job(TrainerJob):

    def warm(self):
        import jax

        t = self.trainer
        if hasattr(self.loader, "shuffle"):
            self.loader.shuffle(self.epoch)
        self.first_key = np.asarray(jax.random.fold_in(self.epoch_key(), self.epoch))
        self.state, loss, _ = t.train_epoch(self.state, self.loader,
                                            self.epoch_key(), self.epoch)
        leaves = jax.tree_util.tree_leaves
        p1 = jax.device_get(leaves(self.state.params))
        m1 = jax.device_get(leaves(self.state.opt_state["m"]))
        p0 = leaves(self.params0)
        self.program = {"losses": [float(loss)], "moment": m1,
                        "change": [a - b for a, b in zip(p1, p0)]}
        self.steps_per_epoch = int(self.loader.steps_per_epoch)
        self.batch = int(self.loader.batch_size)
        self.turn_epoch(float(loss))

    def run_window(self, window):
        t, b = self.trainer, self.bench
        window.open()
        going = window.boundary()
        while going:
            with b.span("epoch"):
                self.state, loss, _ = t.train_epoch(self.state, self.loader,
                                                    self.epoch_key(), self.epoch)
            self.losses.append(float(loss))
            going = window.boundary(self.steps_per_epoch * self.batch)
            window.epoch_turn()
            with b.span("epoch_turn"):
                self.turn_epoch(float(loss))

    def program_readings(self):
        return self.program

    def reference_readings(self, quantize_name=None, rows=None):
        import jax
        import jax.numpy as jnp

        import refrun

        cfg = self.bench.cfg
        x_all = jax.device_put(self.x)
        y_all = jax.device_put(self.y.astype(np.int32))
        n, bsz, classes = x_all.shape[0], self.batch, cfg["num_classes"]
        kperm, kstep = jax.random.split(jnp.asarray(self.first_key))
        perm = jax.random.permutation(jax.random.fold_in(kperm, 0), n)
        idx = perm[:self.steps_per_epoch * bsz].reshape(self.steps_per_epoch, bsz)
        pad = 4

        def batch_fn(data, i):
            x_all, y_all, idx, kstep = data
            rows_i = idx[i]
            xb = x_all[rows_i].astype(jnp.float32) * jnp.float32(1.0 / 255.0)
            ka = jax.random.fold_in(jax.random.fold_in(kstep, i), 0x0A6)
            _, ky, kx = jax.random.split(jax.random.fold_in(ka, 0), 3)
            oy = jax.random.randint(ky, (bsz,), 0, 2 * pad + 1)
            ox = jax.random.randint(kx, (bsz,), 0, 2 * pad + 1)
            padded = jnp.pad(xb, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
            h, w = xb.shape[2], xb.shape[3]
            xb = jax.vmap(lambda img, a, c: jax.lax.dynamic_slice(
                img, (0, a, c), (img.shape[0], h, w)))(padded, oy, ox)
            flip = jax.random.uniform(jax.random.fold_in(ka, 1), (bsz,)) < 0.5
            xb = jnp.where(flip[:, None, None, None], xb[..., ::-1], xb)
            return xb, jax.nn.one_hot(y_all[rows_i], classes, dtype=jnp.float32)

        return refrun.epoch(cfg, jax.device_put(self.params0),
                            jax.device_put(self.state0),
                            (x_all, y_all, idx, kstep), batch_fn,
                            self.steps_per_epoch, self.lr0, quantize_name, rows)
