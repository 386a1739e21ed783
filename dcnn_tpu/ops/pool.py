"""Pooling ops.

Reference equivalent: MaxPool/AvgPool forward + backward-scatter kernels with
an argmax-index cache per microbatch (``src/nn/layers_impl/cpu/maxpool_ops.cpp``,
``avgpool_ops.cpp`` and CUDA twins; layers ``maxpool2d_layer.tpp:264``,
``avgpool2d_layer.tpp:253``).

On TPU both are ``lax.reduce_window`` — XLA generates the backward scatter from
the autodiff transpose rule, so no argmax cache is needed (its job is done by
the VJP residuals). For max-pooling that scatter is a ``select-and-scatter``,
which XLA:TPU fuses with nothing and feeds from memory. Where the pooled array
can be produced as the four positions of a 2x2/2 window instead
(``conv.conv2d_pool_phases``, at the head of a model in training:
``nn/sequential.py``), ``max_pool2d_phases`` is the same pool with the same
tie rule as an elementwise maximum, and its backward is elementwise too.
"""

from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

IntOrPair = Union[int, Tuple[int, int]]


def _pair(v: IntOrPair) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else (int(v[0]), int(v[1]))


def _window(kernel, stride, padding, data_format):
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride if stride is not None else kernel)
    ph, pw = _pair(padding)
    if data_format == "NCHW":
        dims = (1, 1, kh, kw)
        strides = (1, 1, sh, sw)
        pads = ((0, 0), (0, 0), (ph, ph), (pw, pw))
    elif data_format == "NHWC":
        dims = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        pads = ((0, 0), (ph, ph), (pw, pw), (0, 0))
    else:
        raise ValueError(f"unsupported data_format {data_format!r}")
    return dims, strides, pads


def max_pool2d(
    x: jax.Array,
    kernel: IntOrPair,
    stride: IntOrPair | None = None,
    padding: IntOrPair = 0,
    *,
    data_format: str = "NCHW",
) -> jax.Array:
    dims, strides, pads = _window(kernel, stride, padding, data_format)
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    return lax.reduce_window(x, init, lax.max, dims, strides, pads)


@jax.custom_jvp
def max_pool2d_phases(a00: jax.Array, a01: jax.Array, a10: jax.Array,
                      a11: jax.Array) -> jax.Array:
    """``max_pool2d(x, 2)`` of an ``x`` given as its four window positions
    (``aIJ`` holds rows ``I::2`` and columns ``J::2``, as
    ``conv2d_pool_phases`` computes them): an elementwise maximum. The
    gradient goes to the first maximum of the window in row-major order,
    which is what ``max_pool2d``'s select-and-scatter (select ``ge``) does;
    ``jnp.maximum``'s own rule halves ties and is another function."""
    return jnp.maximum(jnp.maximum(a00, a01), jnp.maximum(a10, a11))


@max_pool2d_phases.defjvp
def _max_pool2d_phases_jvp(primals, tangents):
    a00, a01, a10, _ = primals
    t00, t01, t10, t11 = tangents
    m = max_pool2d_phases(*primals)
    t = jnp.where(a00 == m, t00, jnp.where(a01 == m, t01, jnp.where(a10 == m, t10, t11)))
    return m, t


def avg_pool2d(
    x: jax.Array,
    kernel: IntOrPair,
    stride: IntOrPair | None = None,
    padding: IntOrPair = 0,
    *,
    data_format: str = "NCHW",
    count_include_pad: bool = True,
) -> jax.Array:
    """Average pool. The reference divides by the full window size including
    padded cells (``avgpool_ops.cpp``), i.e. ``count_include_pad=True`` — keep
    that default for parity."""
    dims, strides, pads = _window(kernel, stride, padding, data_format)
    summed = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
    kh, kw = _pair(kernel)
    if count_include_pad:
        return summed / (kh * kw)
    ones = jnp.ones_like(x)
    counts = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pads)
    return summed / counts


def global_avg_pool2d(x: jax.Array, *, data_format: str = "NCHW") -> jax.Array:
    axes = (2, 3) if data_format == "NCHW" else (1, 2)
    return jnp.mean(x, axis=axes, keepdims=True)


def pool_output_shape(
    input_hw: Tuple[int, int],
    kernel: IntOrPair,
    stride: IntOrPair | None = None,
    padding: IntOrPair = 0,
) -> Tuple[int, int]:
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride if stride is not None else kernel)
    ph, pw = _pair(padding)
    return ((input_hw[0] + 2 * ph - kh) // sh + 1, (input_hw[1] + 2 * pw - kw) // sw + 1)
