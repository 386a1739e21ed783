"""Shared example-trainer plumbing (reference ``examples/*.cpp`` all follow
load_env → load data → build model → train; SURVEY.md §3.1)."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dcnn_tpu.core.config import TrainingConfig
from dcnn_tpu.core.device import require_tpu
from dcnn_tpu.data import SyntheticClassificationLoader
from dcnn_tpu.obs import phase
from dcnn_tpu.utils import enable_compile_cache
from dcnn_tpu.utils.env import get_env, load_env_file
from dcnn_tpu.utils.hardware import HardwareInfo


@phase("setup.config")
def setup(name: str) -> TrainingConfig:
    """Every example trainer's first call: env file -> config, then the two
    things that must precede the first compile — the backend guard (TPU, or
    the CPU only under JAX_PLATFORMS=cpu) and the persistent compile cache."""
    load_env_file(os.environ.get("ENV_FILE", "./.env"))
    cfg = TrainingConfig.load_from_env()
    require_tpu(name)
    enable_compile_cache()
    print(f"=== {name} ===")
    HardwareInfo.print_info()
    print(f"config: {cfg.to_dict()}")
    return cfg


def with_prefetch(loader, cfg):
    """Wrap the train loader in the prefetching input pipeline: background
    batch prep + H2D overlap, and — when cfg.steps_per_dispatch > 1 — K-batch
    chunked staging feeding the Trainer's multi-step fast path. With
    cfg.feed_workers > 0 (FEED_WORKERS env) the host side of the producer
    (gather + collate) runs on a shared-memory worker pool
    (dcnn_tpu/data/workers.py; tuning guide docs/performance.md)."""
    from dcnn_tpu.data import PrefetchLoader

    return PrefetchLoader(loader, depth=2,
                          stage_batches=max(cfg.steps_per_dispatch, 1),
                          feed_workers=max(cfg.feed_workers, 0))


def prepare_input(train_loader, val_loader, num_classes, cfg,
                  device_augment=None):
    """Input-pipeline selection for the example trainers.

    RESIDENT=1 stages both splits into device memory (``DeviceDataset``) so
    the Trainer runs each epoch as ONE device dispatch — the fastest path
    whenever the dataset fits HBM (measured feed_efficiency ~1.0; the digits
    gate's wall-clock dropped 5× switching over). ``device_augment`` is the
    on-device augmentation recipe (host loaders' numpy hooks don't transfer
    — rebuild with ``DeviceAugmentBuilder``).

    Otherwise the train loader is wrapped in the prefetching host pipeline
    (background batch prep + H2D overlap, chunked staging when
    cfg.steps_per_dispatch > 1).
    """
    if get_env("RESIDENT", "0") == "1":
        from dcnn_tpu.data import DeviceDataset

        train = DeviceDataset.from_loader(train_loader, num_classes,
                                          augment=device_augment)
        val = DeviceDataset.from_loader(val_loader, num_classes)
        print(f"input: HBM-resident ({train.hbm_bytes / 1e6:.0f} MB train + "
              f"{val.hbm_bytes / 1e6:.0f} MB val staged to device)")
        return train, val
    return with_prefetch(train_loader, cfg), val_loader


def loader_or_synthetic(make_real, image_shape, num_classes, cfg,
                        n_train=2048, n_val=512):
    """Use the real dataset if its path exists, else synthetic data so every
    trainer runs end-to-end in any environment."""
    try:
        return make_real()
    except (FileNotFoundError, OSError, TypeError) as e:
        print(f"dataset unavailable ({e}); using synthetic data")
        train = SyntheticClassificationLoader(
            n_train, image_shape, num_classes, batch_size=cfg.batch_size,
            seed=cfg.seed)
        val = SyntheticClassificationLoader(
            n_val, image_shape, num_classes, batch_size=cfg.batch_size,
            seed=cfg.seed + 1)
        return train, val
