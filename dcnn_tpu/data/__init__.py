"""Data loading + augmentation (reference ``include/data_loading/``,
``include/data_augmentation/``)."""

from .loader import BaseDataLoader, ArrayDataLoader, one_hot
from .mnist import MNISTDataLoader
from .cifar import CIFAR10DataLoader, CIFAR100DataLoader
from .tiny_imagenet import TinyImageNetDataLoader
from .regression import RegressionDataLoader
from .wifi import UJIWiFiDataLoader
from .synthetic import SyntheticClassificationLoader
from .prefetch import PrefetchLoader
from .wire import (
    WIRE_SCALE_U8, decode_batch, decode_host, default_decode_transform,
    wire_scale,
)
from .streaming import (
    StreamingDeviceDataset, make_shard_step, train_streaming_epoch,
)
from .transfer import TransferEngine, chunk_bounds, max_inflight
from .workers import (
    FeedWorkerPool, LocalSlots, PreparedShard, ShmSlots, prepare_shard,
    serial_shards, shard_rng,
)
from .augment import (
    AugmentationBuilder, AugmentationStrategy,
    brightness, contrast, cutout, gaussian_noise, horizontal_flip,
    normalization, random_crop, rotation, vertical_flip,
)
from .augment_device import DeviceAugment, DeviceAugmentBuilder
from .device_dataset import (
    DeviceDataset, ShardedDeviceDataset, TokenDataset, make_resident_epoch,
    make_resident_epoch_dp, make_resident_eval, resident_epoch,
    resident_epoch_dp, resident_eval, stage_sharded,
)

__all__ = [
    "BaseDataLoader", "ArrayDataLoader", "one_hot",
    "MNISTDataLoader", "CIFAR10DataLoader", "CIFAR100DataLoader",
    "TinyImageNetDataLoader", "RegressionDataLoader", "UJIWiFiDataLoader",
    "SyntheticClassificationLoader",
    "PrefetchLoader",
    "WIRE_SCALE_U8", "decode_batch", "decode_host",
    "default_decode_transform", "wire_scale",
    "StreamingDeviceDataset", "make_shard_step", "train_streaming_epoch",
    "TransferEngine", "chunk_bounds", "max_inflight",
    "FeedWorkerPool", "LocalSlots", "PreparedShard", "ShmSlots",
    "prepare_shard", "serial_shards", "shard_rng",
    "AugmentationStrategy", "AugmentationBuilder",
    "brightness", "contrast", "cutout", "gaussian_noise", "horizontal_flip",
    "vertical_flip", "normalization", "random_crop", "rotation",
    "DeviceAugment", "DeviceAugmentBuilder",
    "DeviceDataset", "ShardedDeviceDataset", "TokenDataset",
    "make_resident_epoch", "make_resident_epoch_dp", "make_resident_eval", "resident_epoch",
    "resident_epoch_dp", "resident_eval", "stage_sharded",
]
