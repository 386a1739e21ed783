"""Model zoo (reference ``include/nn/example_models.hpp:13-404``) plus the
generative-serving decoder family (``decoder.py``) and the config-built
language model (``latent_moe.py``), neither with a reference analog."""

from .decoder import MHADecoder, create_mha_decoder
from .latent_moe import (LatentMoEDecoder, create_deepseek_v2_lite_ep8,
                         create_kimi_linear_48b_ep32)
from .zoo import (
    MODEL_ZOO, create_cifar10_trainer_v1, create_cifar10_trainer_v2,
    create_cnn_cifar100, create_cnn_tiny_imagenet, create_mha_classifier,
    create_mnist_trainer, create_model,
    create_resnet9_cifar10, create_resnet9_tiny_imagenet,
    create_resnet18_cifar10, create_resnet18_tiny_imagenet,
    create_resnet20_cifar10, create_resnet34_tiny_imagenet,
    create_resnet50_cifar10, create_resnet50_imagenet,
    create_resnet50_tiny_imagenet,
)

__all__ = [
    "MODEL_ZOO", "create_model",
    "MHADecoder", "create_mha_decoder",
    "LatentMoEDecoder", "create_deepseek_v2_lite_ep8", "create_kimi_linear_48b_ep32",
    "create_mnist_trainer", "create_cifar10_trainer_v1", "create_cifar10_trainer_v2",
    "create_cnn_cifar100", "create_mha_classifier",
    "create_resnet9_cifar10", "create_resnet18_cifar10", "create_resnet20_cifar10",
    "create_resnet50_cifar10", "create_resnet9_tiny_imagenet", "create_cnn_tiny_imagenet",
    "create_resnet18_tiny_imagenet", "create_resnet34_tiny_imagenet",
    "create_resnet50_tiny_imagenet", "create_resnet50_imagenet",
]
