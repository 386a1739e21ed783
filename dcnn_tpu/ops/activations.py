"""Activation functions.

Reference equivalent: the 7 activation kernel families (apply + in-place
gradient, CPU+CUDA pairs) under ``src/nn/activations_impl/`` with class
wrappers and an ``ActivationFactory`` (``include/nn/activations.hpp``,
``base_activation.hpp:13-23``). Defaults for parity: LeakyReLU slope 0.01,
ELU alpha 1.0 (``activations_impl/leaky_relu.hpp:17``, ``elu.hpp:17``).

Gradients come from autodiff; the string registry replaces the factory so JSON
model configs can name activations the same way the reference does.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp


def relu(x: jax.Array) -> jax.Array:
    return jnp.maximum(x, 0)


def leaky_relu(x: jax.Array, negative_slope: float = 0.01) -> jax.Array:
    return jnp.where(x >= 0, x, negative_slope * x)


def elu(x: jax.Array, alpha: float = 1.0) -> jax.Array:
    safe = jnp.minimum(x, 0.0)  # avoid overflow in exp for large positives
    return jnp.where(x > 0, x, alpha * (jnp.exp(safe) - 1.0))


def sigmoid(x: jax.Array) -> jax.Array:
    return jax.nn.sigmoid(x)


def tanh(x: jax.Array) -> jax.Array:
    return jnp.tanh(x)


def softmax(x: jax.Array, axis: int = -1) -> jax.Array:
    """Numerically-stable softmax (reference subtracts the row max the same
    way, ``softmax_kernels.cpp``)."""
    return jax.nn.softmax(x, axis=axis)


def linear(x: jax.Array) -> jax.Array:
    return x


ACTIVATIONS: Dict[str, Callable] = {
    "relu": relu,
    "leaky_relu": leaky_relu,
    "elu": elu,
    "sigmoid": sigmoid,
    "tanh": tanh,
    "softmax": softmax,
    "linear": linear,
    "none": linear,
}


# activations that act on each element alone, whatever the array's layout or
# how it is cut into parts (softmax is not)
ELEMENTWISE = frozenset({"relu", "leaky_relu", "elu", "sigmoid", "tanh", "linear", "none"})


def apply_activation(name: Optional[str], x: jax.Array, **kwargs) -> jax.Array:
    """String-keyed dispatch (reference ``ActivationFactory``,
    ``include/nn/activations.hpp``)."""
    if name is None:
        return x
    try:
        fn = ACTIVATIONS[name.lower()]
    except KeyError:
        raise ValueError(f"unknown activation {name!r}; known: {sorted(ACTIVATIONS)}") from None
    return fn(x, **kwargs)
