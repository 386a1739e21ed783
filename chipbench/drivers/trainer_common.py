"""What the ``resident`` and ``fed`` drivers share: the ResNet trainer built
as ``examples/tiny_imagenet_trainer.train`` builds it, over arrays drawn from
the seed, with the harness's weights installed.

The example's ``train()`` constructs and fits in one call, so its
construction is repeated here line for line: ``common.setup``,
``common.prepare_input`` (which picks the HBM-resident dataset or the
prefetching host pipeline from ``RESIDENT``), ``create_model``, AdamW +
``WarmupCosineAnnealing``, and a ``Trainer`` as ``train_classification_model``
makes it. The loop of ``Trainer._fit_loop`` that matters to a step (shuffle,
epoch key, ``train_epoch``, the scheduler's per-epoch step) is ``one_epoch``.
"""

from __future__ import annotations

import os

import numpy as np


def seeded_split(seed: int, n: int, shape, num_classes: int):
    """uint8 pixels and integer labels from one vectorised draw (64 random
    bits a draw, seen as eight pixels: ten times faster than ``rng.bytes``)."""
    rng = np.random.default_rng(seed)
    count = int(n) * int(np.prod(shape))
    words = rng.integers(0, 2 ** 64 - 1, size=-(-count // 8), dtype=np.uint64,
                         endpoint=True)
    x = words.view(np.uint8)[:count].reshape(n, *shape)
    y = rng.integers(0, num_classes, size=n)
    return x, y


def one_hot(labels, num_classes: int):
    out = np.zeros((len(labels), num_classes), np.float32)
    out[np.arange(len(labels)), labels] = 1
    return out


class TrainerJob:
    """Construction shared by the two single-chip drivers."""

    def __init__(self, bench):
        self.bench = bench
        self.state = None          # the TrainState, handed from warm-up to the window
        self.losses = []           # every loss the window saw (finite or not)

    def build(self):
        import jax
        import jax.numpy as jnp
        from common import prepare_input, setup
        from dcnn_tpu.data import (ArrayDataLoader, AugmentationBuilder,
                                   DeviceAugmentBuilder)
        from dcnn_tpu.models import create_model
        from dcnn_tpu.optim import AdamW, WarmupCosineAnnealing
        from dcnn_tpu.train.trainer import Trainer, TrainState

        import refrun

        b, cfg = self.bench, self.bench.cfg
        ds = cfg["dataset"]
        os.environ["EPOCHS"] = str(cfg["scheduler"]["epochs"])
        os.environ["LEARNING_RATE"] = str(cfg["optimizer"]["learning_rate"])
        self.tcfg = setup("chipbench " + b.cell["name"])
        shape, classes = tuple(cfg["input_shape"]), cfg["num_classes"]
        self.x, self.y = seeded_split(b.seed, ds["train_images"], shape, classes)
        xv, yv = seeded_split(b.seed + 1, ds["val_images"], shape, classes)
        aug = AugmentationBuilder().random_crop(4).horizontal_flip(0.5).build()
        # TinyImageNetDataLoader is a BaseDataLoader whose load_data() decodes
        # the dataset's files; ArrayDataLoader is the same loader over arrays
        train = ArrayDataLoader(self.x, one_hot(self.y, classes),
                                batch_size=self.tcfg.batch_size,
                                seed=self.tcfg.seed, augmentation=aug)
        val = ArrayDataLoader(xv, one_hot(yv, classes),
                              batch_size=self.tcfg.batch_size, shuffle=False)
        dev_aug = (DeviceAugmentBuilder("NCHW")
                   .random_crop(4).horizontal_flip(0.5).build())
        self.loader, self.val_loader = prepare_input(
            train, val, classes, self.tcfg, device_augment=dev_aug)
        self.host_loader = train
        model = create_model(cfg["program_model"])
        opt = cfg["optimizer"]
        sched = WarmupCosineAnnealing(self.tcfg.learning_rate, warmup_steps=2,
                                      total_steps=self.tcfg.epochs)
        self.trainer = Trainer(model, AdamW(self.tcfg.learning_rate,
                                            weight_decay=opt["weight_decay"]),
                               cfg["loss"], self.tcfg, sched)
        params, bn_state = refrun.seeded_weights(cfg, b.seed)
        want = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
        got = jax.tree_util.tree_map(lambda a: a.shape, (params, bn_state))
        if jax.tree_util.tree_map(lambda a: a.shape, want) != got:
            raise RuntimeError("the configuration's layer list does not give "
                               "the program's parameter tree")
        self.params0 = jax.device_get(params)
        self.state0 = jax.device_get(bn_state)
        self.state = TrainState(params, bn_state,
                                self.trainer.optimizer.init(params),
                                jnp.zeros((), jnp.int32))
        self.rng = jax.random.PRNGKey(self.tcfg.seed)
        self.epoch = 1
        self.lr0 = float(self.trainer.lr)

    def epoch_key(self):
        import jax
        return jax.random.fold_in(self.rng, self.epoch)

    def turn_epoch(self, train_loss: float):
        """What ``_fit_loop`` does between two epochs, as far as a step sees."""
        t = self.trainer
        if t.scheduler is not None and self.tcfg.scheduler_step == "epoch":
            t.lr = t.scheduler.step(train_loss)
        self.epoch += 1
        if hasattr(self.loader, "shuffle"):
            self.loader.shuffle(self.epoch)

    def failed_steps(self) -> int:
        return int(sum(1 for v in self.losses if not np.isfinite(v)))

    def close(self):
        for name in ("trainer", "loader", "val_loader", "host_loader"):
            if hasattr(self, name):
                delattr(self, name)
