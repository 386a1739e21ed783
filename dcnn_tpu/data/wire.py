"""The uint8 feed-wire decode (docs/performance.md §"The wire-dtype
contract").

Image loaders ship pixels as **uint8** — 4x fewer bytes across the H2D
(and TCP) wire than float32 — and the consumer decodes AFTER the put:

    decoded = x.astype(float32) * scale        # scale = loader.scale

The multiply form is the contract, not ``x / 255``: it is exactly what
the device-side ``_decode`` (``device_dataset.py``), ``make_shard_step``
(``streaming.py``) and the native ``u8_to_f32`` kernel compute, so every
feed path — serial iteration, ``FeedWorkerPool``, ``PrefetchLoader``,
streaming shards — lands on bit-identical float32 pixels. (Division can
differ from the multiply by 1 ulp via double rounding; bit-parity across
paths is a tier-1 gate, ``tests/test_wire_parity.py``.)

Decode callables are jitted once per ``scale`` (lru_cache — the TS06
retrace lint forbids a fresh closure per call) and are identity for
non-uint8 inputs, so tabular/regression loaders flow through unchanged.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["WIRE_SCALE_U8", "decode_batch", "decode_host",
           "default_decode_transform", "decode_fn", "wire_scale"]

# the uint8 pixel decode multiplier — float32-rounded 1/255, the value
# every decode path multiplies by
WIRE_SCALE_U8 = 1.0 / 255.0


def wire_scale(loader, default: float = WIRE_SCALE_U8) -> float:
    """The decode multiplier for ``loader``'s batches: its ``scale``
    contract when it publishes one, ``default`` otherwise (pre-contract
    loaders shipped model-domain floats, where the identity decode below
    makes any default harmless)."""
    return float(getattr(loader, "scale", default))


@functools.lru_cache(maxsize=16)
def decode_fn(scale: float):
    """Jitted ``uint8 -> float32 * scale`` decode, cached per scale.

    Identity for non-uint8 inputs (already decoded / tabular floats), so
    callers can apply it unconditionally on any feed path.
    """
    @jax.jit
    def dec(x):
        if x.dtype == jnp.uint8:
            with jax.named_scope("data"):
                return x.astype(jnp.float32) * jnp.asarray(scale,
                                                           jnp.float32)
        return x
    return dec


def decode_batch(x, scale: float = WIRE_SCALE_U8):
    """Decode one wire batch (device array or numpy) to model domain."""
    return decode_fn(float(scale))(x)


@functools.lru_cache(maxsize=16)
def default_decode_transform(scale: float):
    """The ``(x, y) -> (decoded_x, y)`` device transform a
    ``PrefetchLoader`` installs when its inner loader declares a uint8
    wire and the caller passed no explicit ``device_transform`` — labels
    pass through untouched (one-hot/cast stays in the train step)."""
    dec = decode_fn(float(scale))

    def transform(x, y):
        return dec(x), y
    return transform


def decode_host(x: np.ndarray, scale: float = WIRE_SCALE_U8) -> np.ndarray:
    """Host-side (numpy) reference decode — the float32 multiply the
    bit-parity tests compare every wire path against."""
    x = np.asarray(x)
    if x.dtype == np.uint8:
        return x.astype(np.float32) * np.float32(scale)
    return x
