"""Tiny-ImageNet trainers (reference ``examples/tiny_imagenet_resnet18.cpp``
/ ``resnet34`` / ``resnet50``). Pick the depth with MODEL=resnet18|resnet34|
resnet50|resnet9|cnn (env), dataset root with TINY_IMAGENET_DIR."""

from common import loader_or_synthetic, prepare_input, setup

from dcnn_tpu.data import (AugmentationBuilder, DeviceAugmentBuilder,
                           TinyImageNetDataLoader)
from dcnn_tpu.models import create_model
from dcnn_tpu.optim import AdamW, WarmupCosineAnnealing
from dcnn_tpu.train import train_classification_model
from dcnn_tpu.utils.env import get_env


def train(cfg, model_name, train_loader, val_loader):
    """The trainer proper, from loaders to a fitted ``(TrainState, Trainer)``
    — ``main`` below and ``chip_smoke.py`` both go through here."""
    # RESIDENT=1: stage the whole split to HBM (~1.2 GB uint8) and run each
    # epoch in one dispatch; same crop/flip recipe, on device
    dev_aug = (DeviceAugmentBuilder("NCHW")
               .random_crop(4).horizontal_flip(0.5).build())
    train_loader, val_loader = prepare_input(train_loader, val_loader, 200,
                                             cfg, device_augment=dev_aug)
    model = create_model(model_name)
    print(model.summary())
    sched = WarmupCosineAnnealing(cfg.learning_rate, warmup_steps=2,
                                  total_steps=cfg.epochs)
    return train_classification_model(
        model, AdamW(cfg.learning_rate, weight_decay=1e-4),
        "softmax_crossentropy", train_loader, val_loader, config=cfg,
        scheduler=sched)


def main():
    cfg = setup("tiny_imagenet_trainer")
    depth = get_env("MODEL", "resnet18")
    model_name = f"{depth}_tiny_imagenet" if not depth.startswith("cnn") else "cnn_tiny_imagenet"
    aug = (AugmentationBuilder()
           .random_crop(4)
           .horizontal_flip(0.5)
           .build())

    def real():
        root = get_env("TINY_IMAGENET_DIR", "data/tiny-imagenet-200")
        train = TinyImageNetDataLoader(root, "train", batch_size=cfg.batch_size,
                                       seed=cfg.seed, augmentation=aug)
        val = TinyImageNetDataLoader(root, "val", batch_size=cfg.batch_size,
                                     shuffle=False)
        train.load_data()
        val.load_data()
        return train, val

    train_loader, val_loader = loader_or_synthetic(real, (3, 64, 64), 200, cfg)
    train(cfg, model_name, train_loader, val_loader)


if __name__ == "__main__":
    main()
