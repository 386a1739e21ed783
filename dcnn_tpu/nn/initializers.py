"""Parameter initializers.

Reference parity: conv and dense weights AND biases use
``Uniform(-bound, bound)`` with ``bound = 1/sqrt(fan_in)`` (the PyTorch
default Kaiming-uniform; ``conv2d_layer.tpp:71-85``,
``dense_layer.tpp``). BatchNorm/GroupNorm start at gamma=1, beta=0.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def _default_dtype():
    """Param storage dtype: float64 under the fp64 precision mode (the
    reference's double-kernel path), float32 otherwise (bf16 mixed precision
    keeps fp32 master params and casts at point of use)."""
    from ..core.precision import get_precision_mode
    return jnp.float64 if get_precision_mode() == "fp64" else jnp.float32


def kaiming_uniform(key: jax.Array, shape: Sequence[int], fan_in: int,
                    dtype: Optional[jnp.dtype] = None) -> jax.Array:
    bound = 1.0 / math.sqrt(float(fan_in))
    return jax.random.uniform(key, tuple(shape), dtype=dtype or _default_dtype(),
                              minval=-bound, maxval=bound)


def conv_fan_in(in_channels: int, kernel_hw: Tuple[int, int]) -> int:
    return in_channels * kernel_hw[0] * kernel_hw[1]


def zeros(shape, dtype: Optional[jnp.dtype] = None) -> jax.Array:
    return jnp.zeros(shape, dtype or _default_dtype())


def ones(shape, dtype: Optional[jnp.dtype] = None) -> jax.Array:
    return jnp.ones(shape, dtype or _default_dtype())


def normal(key: jax.Array, shape: Sequence[int], std: float,
           dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """``N(0, std^2)``: the initializer of decoder language models."""
    return std * jax.random.normal(key, tuple(shape), dtype or _default_dtype())
