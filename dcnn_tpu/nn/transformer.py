"""The parts of a current decoder block that are not attention: RMSNorm,
rotary positions with YaRN frequencies, and the gated MLP.

No reference analog (the reference is CNN-only, SURVEY.md §5.7). Per-sample
shape convention as in ``attention_layer``: ``(S, E)``; batched apply sees
``(B, S, E)``. Parameters are float32 masters; ``cast_to_compute`` at the
point of use gives the bf16 mode its operands, and every product accumulates
in float32 on the MXU whatever the operands' dtype.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.precision import get_precision
from . import initializers as init


def matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` under the precision policy, in ``x``'s dtype."""
    return jnp.matmul(x, w, precision=get_precision())


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """``x / sqrt(mean(x^2) + eps) * w`` over the last axis; the statistics
    in float32, the result in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------ rotary

def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(scale) + 1``."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(beta_fast: float, beta_slow: float, dim: int,
                          theta: float, original_max: int) -> Tuple[int, int]:
    """The pair indices between which YaRN blends interpolated and
    extrapolated frequencies: the pair that turns ``beta`` times over the
    original context, for ``beta_fast`` (floor) and ``beta_slow`` (ceil)."""
    def pair(turns):
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), dim - 1)
    return low, high


def rotary_inv_freq(dim: int, theta: float = 10000.0,
                    scaling: Optional[dict] = None) -> jax.Array:
    """``dim // 2`` inverse frequencies. ``scaling`` is a published
    ``rope_scaling`` group; ``type: yarn`` blends ``theta^(-2i/dim)``
    (extrapolated, fast pairs) with the same over ``factor`` (interpolated,
    slow pairs) by a linear ramp over the correction range."""
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not scaling:
        return extra
    if scaling.get("type") != "yarn":
        raise ValueError(f"unknown rope_scaling type {scaling.get('type')!r}")
    low, high = yarn_correction_range(
        scaling["beta_fast"], scaling["beta_slow"], dim, theta,
        scaling["original_max_position_embeddings"])
    span = max(high - low, 1e-3)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / span, 0, 1)
    keep = 1.0 - ramp                      # 1: extrapolate, 0: interpolate
    return extra / scaling["factor"] * (1.0 - keep) + extra * keep


def rotary_tables(seq_len: int, inv_freq: jax.Array,
                  scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """cos and sin ``[seq_len, dim // 2]`` in float32, times ``scale``."""
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the pairs (2i, 2i+1) of ``x [..., S, dim]`` by position; the
    rotation in float32, the result in ``x``'s dtype and pair order."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ------------------------------------------------------------------ gated MLP

def gated_mlp(params, x: jax.Array) -> jax.Array:
    """``(silu(x W_gate) * x W_up) W_down``."""
    return matmul(jax.nn.silu(matmul(x, params["gate"])) * matmul(x, params["up"]),
                  params["down"])


def init_gated_mlp(key, hidden: int, width: int, std: float):
    kg, ku, kd = jax.random.split(key, 3)
    return {"gate": init.normal(kg, (hidden, width), std),
            "up": init.normal(ku, (hidden, width), std),
            "down": init.normal(kd, (width, hidden), std)}
