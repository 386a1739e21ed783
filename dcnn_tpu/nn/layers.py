"""Concrete layers.

Reference equivalents (SURVEY.md §2.3): ``Conv2DLayer``
(``conv2d_layer.tpp``), ``DenseLayer`` (``dense_layer.tpp``),
``BatchNormLayer`` (``batchnorm_layer.tpp``), ``GroupNormLayer``
(``groupnorm_layer.tpp``), ``MaxPool2DLayer``/``AvgPool2DLayer``
(``maxpool2d_layer.tpp``/``avgpool2d_layer.tpp``), ``DropoutLayer``,
``FlattenLayer``, ``ActivationLayer``.

Parity choices: Kaiming-uniform init with bound 1/√fan_in for weights *and*
biases (conv2d_layer.tpp:71-85); BN eps 1e-5 / momentum 0.1; GN eps 1e-5;
LeakyReLU 0.01 / ELU 1.0 defaults. ``in_channels``/``in_features`` may be
omitted and are inferred at ``init`` from the input shape (the reference's
SequentialBuilder does the same inference at build time,
``sequential.hpp:1154``).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.precision import get_precision
from ..ops import activations as act_ops
from ..ops import conv as conv_ops
from ..ops import norm as norm_ops
from ..ops import pool as pool_ops
from . import initializers as init
from .factory import register_layer
from .layer import ParameterizedLayer, Shape, StatelessLayer


def _pair(v) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else (int(v[0]), int(v[1]))


def _feature_axis(data_format: str) -> int:
    return 0 if data_format == "NCHW" else 2


class Conv2DGeometryMixin:
    """Geometry/config contract shared by ``Conv2DLayer`` and its int8 PTQ
    twin (``nn/quantize.py``) — one implementation so the two cannot drift.
    (The twin is deliberately NOT a subclass of ``Conv2DLayer``: the
    isinstance walks in fold/quantize must not re-capture it.)"""

    def _set_conv_geometry(self, out_channels, kernel_size, stride, padding,
                           use_bias, in_channels, data_format):
        self.out_channels = int(out_channels)
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride)
        self.padding = _pair(padding)
        self.use_bias = bool(use_bias)
        self.in_channels = in_channels
        self.data_format = data_format

    def _cin(self, input_shape: Shape) -> int:
        cin = input_shape[_feature_axis(self.data_format)]
        if self.in_channels is not None and self.in_channels != cin:
            raise ValueError(f"{self.name}: expected {self.in_channels} input channels, got {cin}")
        return cin

    def output_shape(self, input_shape):
        if self.data_format == "NCHW":
            _, h, w = input_shape
            oh, ow = conv_ops.conv2d_output_shape((h, w), self.kernel_size, self.stride, self.padding)
            return (self.out_channels, oh, ow)
        h, w, _ = input_shape
        oh, ow = conv_ops.conv2d_output_shape((h, w), self.kernel_size, self.stride, self.padding)
        return (oh, ow, self.out_channels)

    def forward_complexity(self, input_shape):
        cin = input_shape[_feature_axis(self.data_format)]
        out = self.output_shape(input_shape)
        oh, ow = (out[1], out[2]) if self.data_format == "NCHW" else (out[0], out[1])
        return 2 * self.out_channels * cin * self.kernel_size[0] * self.kernel_size[1] * oh * ow

    def param_count(self, input_shape):
        cin = input_shape[_feature_axis(self.data_format)]
        n = self.out_channels * cin * self.kernel_size[0] * self.kernel_size[1]
        return n + (self.out_channels if self.use_bias else 0)

    def get_config(self):
        return {
            "type": self.type_name, "name": self.name,
            "out_channels": self.out_channels, "kernel_size": list(self.kernel_size),
            "stride": list(self.stride), "padding": list(self.padding),
            "use_bias": self.use_bias, "in_channels": self.in_channels,
            "data_format": self.data_format,
        }


class DenseGeometryMixin:
    """Geometry/config contract shared by ``DenseLayer`` and its int8 PTQ
    twin (same non-subclassing rationale as ``Conv2DGeometryMixin``)."""

    def _set_dense_geometry(self, out_features, use_bias, in_features):
        self.out_features = int(out_features)
        self.use_bias = bool(use_bias)
        self.in_features = in_features

    def _fan_in(self, input_shape: Shape) -> int:
        if len(input_shape) != 1:
            raise ValueError(f"{self.name}: dense expects flat input, got {input_shape}; "
                             "add a Flatten layer first")
        fan_in = input_shape[0]
        if self.in_features is not None and self.in_features != fan_in:
            raise ValueError(f"{self.name}: expected {self.in_features} features, got {fan_in}")
        return fan_in

    def output_shape(self, input_shape):
        return (self.out_features,)

    def forward_complexity(self, input_shape):
        return 2 * input_shape[0] * self.out_features

    def param_count(self, input_shape):
        return input_shape[0] * self.out_features + (self.out_features if self.use_bias else 0)

    def get_config(self):
        return {"type": self.type_name, "name": self.name,
                "out_features": self.out_features, "use_bias": self.use_bias,
                "in_features": self.in_features}


@register_layer("conv2d")
class Conv2DLayer(Conv2DGeometryMixin, ParameterizedLayer):
    """2-D convolution (reference ``conv2d_layer.tpp:140-241``): on TPU the
    im2col→GEMM→cnhw→nchw pipeline collapses to one MXU conv."""

    def __init__(self, out_channels: int, kernel_size, stride=1, padding=0,
                 use_bias: bool = True, in_channels: Optional[int] = None,
                 data_format: str = "NCHW", name: Optional[str] = None):
        super().__init__(name)
        self._set_conv_geometry(out_channels, kernel_size, stride, padding,
                                use_bias, in_channels, data_format)

    def init(self, key, input_shape):
        cin = self._cin(input_shape)
        self.in_channels = cin
        fan_in = init.conv_fan_in(cin, self.kernel_size)
        wkey, bkey = jax.random.split(key)
        params = {"w": init.kaiming_uniform(
            wkey, (self.out_channels, cin, *self.kernel_size), fan_in)}
        if self.use_bias:
            params["b"] = init.kaiming_uniform(bkey, (self.out_channels,), fan_in)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        y = conv_ops.conv2d(
            x, params["w"], params.get("b"),
            stride=self.stride, padding=self.padding, data_format=self.data_format)
        return y, state


@register_layer("dense")
class DenseLayer(DenseGeometryMixin, ParameterizedLayer):
    """Fully-connected layer (reference ``dense_layer.tpp``): y = x·Wᵀ + b.
    Weight stored (out, in) like the reference so checkpoints are auditable."""

    def __init__(self, out_features: int, use_bias: bool = True,
                 in_features: Optional[int] = None, name: Optional[str] = None):
        super().__init__(name)
        self._set_dense_geometry(out_features, use_bias, in_features)

    def init(self, key, input_shape):
        fan_in = self._fan_in(input_shape)
        self.in_features = fan_in
        wkey, bkey = jax.random.split(key)
        params = {"w": init.kaiming_uniform(wkey, (self.out_features, fan_in), fan_in)}
        if self.use_bias:
            params["b"] = init.kaiming_uniform(bkey, (self.out_features,), fan_in)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        y = jnp.matmul(x, params["w"].T, precision=get_precision())
        if self.use_bias:
            y = y + params["b"]
        return y, state


@register_layer("batchnorm")
class BatchNormLayer(ParameterizedLayer):
    """BatchNorm2d (reference ``batchnorm_layer.tpp``; eps 1e-5, momentum 0.1).
    Running stats live in ``state`` and are updated functionally."""

    def __init__(self, num_features: Optional[int] = None, epsilon: float = 1e-5,
                 momentum: float = 0.1, affine: bool = True,
                 data_format: str = "NCHW", name: Optional[str] = None):
        super().__init__(name)
        self.num_features = num_features
        self.epsilon = float(epsilon)
        self.momentum = float(momentum)
        self.affine = bool(affine)
        self.data_format = data_format

    def init(self, key, input_shape):
        c = input_shape[_feature_axis(self.data_format)] if len(input_shape) == 3 else input_shape[0]
        if self.num_features is not None and self.num_features != c:
            raise ValueError(f"{self.name}: expected {self.num_features} features, got {c}")
        self.num_features = c
        params = {"gamma": init.ones((c,)), "beta": init.zeros((c,))} if self.affine else {}
        state = {"running_mean": init.zeros((c,)), "running_var": init.ones((c,))}
        return params, state

    def apply(self, params, state, x, *, training=False, rng=None):
        (y,), new_state = self.apply_parts(params, state, (x,), training=training)
        return y, new_state

    def apply_parts(self, params, state, xs, *, training=False):
        """``apply`` on one activation handed over as same-shaped parts
        (``norm_ops.batch_norm_parts``): one set of statistics, a list of
        normalized parts."""
        x = xs[0]
        gamma = params.get("gamma", jnp.ones((x.shape[1 if self.data_format == 'NCHW' else -1],), x.dtype))
        beta = params.get("beta", jnp.zeros_like(gamma))
        if x.ndim == 2:
            # dense BN: treat features as channels over (N,)
            xs = [xi[:, :, None, None] if self.data_format == "NCHW" else xi[:, None, None, :]
                  for xi in xs]
        ys, new_mean, new_var = norm_ops.batch_norm_parts(
            xs, gamma, beta, state["running_mean"], state["running_var"],
            training=training, momentum=self.momentum, eps=self.epsilon,
            data_format=self.data_format)
        if x.ndim == 2:
            ys = [y.reshape(x.shape) for y in ys]
        return ys, {"running_mean": new_mean, "running_var": new_var}

    def forward_complexity(self, input_shape):
        n = 1
        for d in input_shape:
            n *= d
        return 8 * n  # mean/var/normalize/affine passes

    def param_count(self, input_shape):
        c = input_shape[_feature_axis(self.data_format)] if len(input_shape) == 3 else input_shape[0]
        return 2 * c if self.affine else 0

    def get_config(self):
        return {"type": self.type_name, "name": self.name,
                "num_features": self.num_features, "epsilon": self.epsilon,
                "momentum": self.momentum, "affine": self.affine,
                "data_format": self.data_format}


@register_layer("groupnorm")
class GroupNormLayer(ParameterizedLayer):
    """GroupNorm (reference ``groupnorm_layer.tpp``; eps 1e-5)."""

    def __init__(self, num_groups: int, num_channels: Optional[int] = None,
                 epsilon: float = 1e-5, affine: bool = True,
                 data_format: str = "NCHW", name: Optional[str] = None):
        super().__init__(name)
        self.num_groups = int(num_groups)
        self.num_channels = num_channels
        self.epsilon = float(epsilon)
        self.affine = bool(affine)
        self.data_format = data_format

    def init(self, key, input_shape):
        c = input_shape[_feature_axis(self.data_format)]
        if self.num_channels is not None and self.num_channels != c:
            raise ValueError(f"{self.name}: expected {self.num_channels} channels, got {c}")
        self.num_channels = c
        params = {"gamma": init.ones((c,)), "beta": init.zeros((c,))} if self.affine else {}
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        y = norm_ops.group_norm(
            x, params.get("gamma"), params.get("beta"), self.num_groups,
            eps=self.epsilon, data_format=self.data_format)
        return y, state

    def forward_complexity(self, input_shape):
        n = 1
        for d in input_shape:
            n *= d
        return 8 * n

    def param_count(self, input_shape):
        return 2 * input_shape[_feature_axis(self.data_format)] if self.affine else 0

    def get_config(self):
        return {"type": self.type_name, "name": self.name,
                "num_groups": self.num_groups, "num_channels": self.num_channels,
                "epsilon": self.epsilon, "affine": self.affine,
                "data_format": self.data_format}


class _Pool2DLayer(StatelessLayer):
    def __init__(self, kernel_size, stride=None, padding=0,
                 data_format: str = "NCHW", name: Optional[str] = None):
        super().__init__(name)
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride) if stride is not None else self.kernel_size
        self.padding = _pair(padding)
        self.data_format = data_format

    def output_shape(self, input_shape):
        if self.data_format == "NCHW":
            c, h, w = input_shape
            oh, ow = pool_ops.pool_output_shape((h, w), self.kernel_size, self.stride, self.padding)
            return (c, oh, ow)
        h, w, c = input_shape
        oh, ow = pool_ops.pool_output_shape((h, w), self.kernel_size, self.stride, self.padding)
        return (oh, ow, c)

    def forward_complexity(self, input_shape):
        out = self.output_shape(input_shape)
        n = 1
        for d in out:
            n *= d
        return n * self.kernel_size[0] * self.kernel_size[1]

    def get_config(self):
        return {"type": self.type_name, "name": self.name,
                "kernel_size": list(self.kernel_size), "stride": list(self.stride),
                "padding": list(self.padding), "data_format": self.data_format}


@register_layer("maxpool2d")
class MaxPool2DLayer(_Pool2DLayer):
    """Max pooling (reference ``maxpool2d_layer.tpp``; argmax cache replaced
    by the autodiff transpose of ``reduce_window``)."""

    def forward(self, x, *, training=False, rng=None):
        return pool_ops.max_pool2d(x, self.kernel_size, self.stride, self.padding,
                                   data_format=self.data_format)


@register_layer("avgpool2d")
class AvgPool2DLayer(_Pool2DLayer):
    """Average pooling (reference ``avgpool2d_layer.tpp``)."""

    def forward(self, x, *, training=False, rng=None):
        return pool_ops.avg_pool2d(x, self.kernel_size, self.stride, self.padding,
                                   data_format=self.data_format)


@register_layer("dropout")
class DropoutLayer(StatelessLayer):
    """Inverted dropout with an explicit PRNG key (reference
    ``dropout_layer.tpp`` uses a seeded mask kernel; explicit keys are the
    functional equivalent)."""

    def __init__(self, rate: float = 0.5, name: Optional[str] = None):
        super().__init__(name)
        self.rate = float(rate)

    def forward(self, x, *, training=False, rng=None):
        if not training or self.rate <= 0.0:
            return x
        if rng is None:
            raise ValueError(f"{self.name}: dropout in training mode needs an rng key")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)

    def forward_complexity(self, input_shape):
        n = 1
        for d in input_shape:
            n *= d
        return 2 * n

    def get_config(self):
        return {"type": self.type_name, "name": self.name, "rate": self.rate}


@register_layer("flatten")
class FlattenLayer(StatelessLayer):
    """Flatten per-sample dims (reference ``flatten_layer.tpp`` — shape-only)."""

    def forward(self, x, *, training=False, rng=None):
        return x.reshape(x.shape[0], -1)

    def output_shape(self, input_shape):
        n = 1
        for d in input_shape:
            n *= d
        return (n,)


@register_layer("activation")
class ActivationLayer(StatelessLayer):
    """Standalone activation (reference ``activation_layer.tpp`` +
    ``ActivationFactory``)."""

    def __init__(self, activation: str = "relu", negative_slope: float = 0.01,
                 alpha: float = 1.0, name: Optional[str] = None):
        super().__init__(name)
        self.activation = activation.lower()
        self.negative_slope = float(negative_slope)
        self.alpha = float(alpha)
        if self.activation not in act_ops.ACTIVATIONS:
            raise ValueError(f"unknown activation {activation!r}")

    def forward(self, x, *, training=False, rng=None):
        if self.activation == "leaky_relu":
            return act_ops.leaky_relu(x, self.negative_slope)
        if self.activation == "elu":
            return act_ops.elu(x, self.alpha)
        return act_ops.ACTIVATIONS[self.activation](x)

    def forward_complexity(self, input_shape):
        n = 1
        for d in input_shape:
            n *= d
        return n

    def get_config(self):
        return {"type": self.type_name, "name": self.name,
                "activation": self.activation,
                "negative_slope": self.negative_slope, "alpha": self.alpha}


@register_layer("log_softmax")
class LogSoftmaxLayer(StatelessLayer):
    """Log-softmax output layer pairing with ``log_softmax_cross_entropy``
    (reference models end with activation "softmax"/log-softmax before the
    LogSoftmaxCE loss, ``example_models.hpp``)."""

    def forward(self, x, *, training=False, rng=None):
        return jax.nn.log_softmax(x, axis=-1)
