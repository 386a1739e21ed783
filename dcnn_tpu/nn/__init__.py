"""NN library: layers, residual blocks, Sequential container, builder, factory.

Reference equivalent: ``include/nn/`` (SURVEY.md §2.3) — ``Layer<T>`` virtual
base with hand-written forward/backward, ``Sequential`` container,
``SequentialBuilder``/``LayerBuilder`` fluent API, string-keyed
``LayerFactory`` for JSON config round-trips.

TPU-native design: a layer is an immutable *spec* object; parameters and
mutable state (BN running stats, dropout counters) live in pytrees threaded
functionally through jit-compiled ``apply`` functions. Backward is autodiff —
the reference's hand-written ``backward`` methods have no analog because
``jax.vjp`` of ``apply`` *is* the backward, including the per-microbatch
activation caches the reference manages by hand (vjp residuals).
"""

from .layer import Layer, ParameterizedLayer, StatelessLayer
from .layers import (
    ActivationLayer, AvgPool2DLayer, BatchNormLayer, Conv2DLayer, DenseLayer,
    DropoutLayer, FlattenLayer, GroupNormLayer, MaxPool2DLayer,
)
from .attention_layer import MultiHeadAttentionLayer
from .delta_attention import DeltaAttentionLayer
from .latent_attention import LatentAttentionLayer
from .moe import MoELayer
from .residual import ResidualBlock
from .sequential import Sequential
from .factory import LayerFactory, register_layer, layer_from_config
from .builder import SequentialBuilder
from .fold import fold_batchnorm
from .quantize import (QuantConv2DLayer, QuantDenseLayer,
                       QuantMultiHeadAttentionLayer, quantize_model)
from .export import export_inference, load_inference

__all__ = [
    "Layer", "ParameterizedLayer", "StatelessLayer",
    "Conv2DLayer", "DenseLayer", "BatchNormLayer", "GroupNormLayer",
    "MaxPool2DLayer", "AvgPool2DLayer", "DropoutLayer", "FlattenLayer",
    "ActivationLayer", "ResidualBlock", "MultiHeadAttentionLayer",
    "DeltaAttentionLayer", "LatentAttentionLayer", "MoELayer",
    "Sequential", "SequentialBuilder",
    "LayerFactory", "register_layer", "layer_from_config",
    "fold_batchnorm",
    "QuantConv2DLayer", "QuantDenseLayer", "QuantMultiHeadAttentionLayer",
    "quantize_model",
    "export_inference", "load_inference",
]
