"""Normalization ops.

Reference equivalent: fused BatchNorm forward (mean/inv-std/running-stat
update/normalize), fused backward, inference path
(``src/nn/layers_impl/cpu/batchnorm_ops.cpp``, ``cuda/batchnorm_ops.cu``,
layer ``batchnorm_layer.tpp``) and the per-group GroupNorm twins
(``groupnorm_ops.cpp``/``.cu``). Defaults for parity: eps 1e-5, BN momentum
0.1 (``batchnorm_layer.hpp:67``, ``groupnorm_layer.hpp:56``).

XLA fuses the normalize-scale-shift chain into neighboring ops, so these are
plain jnp expressions; backward comes from autodiff (numerically the same
reduction tree as the reference's hand-fused backward).
"""

from __future__ import annotations

from functools import reduce
from operator import add
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def batch_norm(
    x: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    running_mean: jax.Array,
    running_var: jax.Array,
    *,
    training: bool,
    momentum: float = 0.1,
    eps: float = 1e-5,
    data_format: str = "NCHW",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (y, new_running_mean, new_running_var).

    Training mode normalizes with batch statistics over (N,H,W) and updates
    running stats as ``running = (1-momentum)*running + momentum*batch``
    (reference semantics: batchnorm_layer.tpp, momentum 0.1). Eval mode uses
    running stats. The reference computes BN per microbatch independently
    (SURVEY.md §7 hard part 4); callers get that behavior for free by invoking
    this once per microbatch.
    """
    (y,), new_mean, new_var = batch_norm_parts(
        (x,), gamma, beta, running_mean, running_var, training=training,
        momentum=momentum, eps=eps, data_format=data_format)
    return y, new_mean, new_var


def batch_norm_parts(
    xs: Sequence[jax.Array],
    gamma: jax.Array,
    beta: jax.Array,
    running_mean: jax.Array,
    running_var: jax.Array,
    *,
    training: bool,
    momentum: float = 0.1,
    eps: float = 1e-5,
    data_format: str = "NCHW",
) -> Tuple[List[jax.Array], jax.Array, jax.Array]:
    """:func:`batch_norm` of one activation handed over as same-shaped parts
    that tile it (the four window positions of ``conv2d_pool_phases``): the
    statistics are taken over all parts together, each part is normalized
    with them. Returns (ys, new_running_mean, new_running_var).
    """
    x = xs[0]
    c_axis = 1 if data_format == "NCHW" else 3
    reduce_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]

    # Statistics always accumulate in at-least-fp32, whatever the activation
    # dtype — with bf16 activations (mixed-precision mode) a bf16 mean/var
    # over N*H*W elements would lose most of its mantissa; fp64 inputs (the
    # fp64 mode) keep full double statistics. XLA fuses the upcast into the
    # reduction, so no widened copy of x is materialized.
    stat_dt = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    xfs = [xi.astype(stat_dt) for xi in xs]
    if training:
        # ONE-pass statistics: sum and sum-of-squares reduce together, so XLA
        # emits a single multi-output reduction over x. The naive
        # mean-then-var form costs two full HBM reads; measured on v5e
        # [1024,64,64,64] bf16: 758 GB/s effective (93% HBM peak) vs
        # 373 GB/s for mean/var — 2.0x. (A hand-written Pallas one-pass
        # stats kernel was also measured and LOSES to this: 378 GB/s best —
        # same conclusion as the r2 epilogue-fusion study: restructure for
        # XLA, don't replace it.)
        #
        # Cancellation control: raw E[x2]-mean^2 loses precision when
        # |mean| >> std (the reference's two-pass kernel is immune,
        # batchnorm_ops.cpp:62-85, at 2x the HBM cost). The sums are
        # therefore taken over x - running_mean: the pivot is an *independent
        # input* (not derived from x), so the subtract fuses into the same
        # single reduction pass — measured identical to the raw form
        # (1.43 ms vs 1.42 on the shape above), while any x-derived pivot
        # (e.g. first-sample mean) forces XLA to materialize the centered
        # tensor (3x slower, measured). Once running_mean tracks the batch
        # mean (~10 steps at momentum 0.1) the residual cancellation term
        # ((mean-rm)/std)^2 is O(1) and fp32 error is ~1e-7 relative.
        # Residual caveat: during the first few steps on inputs with
        # |mean|/std > ~1e3 the variance is imprecise (clamped >= 0, outputs
        # finite) — the same regime cuDNN's single-pass BN accepts; steady
        # state matches the reference's stable kernel.
        n = len(xs) * x.size // x.shape[c_axis]
        pivot = running_mean.astype(stat_dt)
        centered = [xf - pivot.reshape(shape) for xf in xfs]
        s1 = reduce(add, [jnp.sum(c, axis=reduce_axes) for c in centered])
        s2 = reduce(add, [jnp.sum(c * c, axis=reduce_axes) for c in centered])
        mean_c = s1 / n
        var = jnp.maximum(s2 / n - mean_c * mean_c, 0.0)
        mean = mean_c + pivot
        unbiased = var * (n / max(n - 1, 1))
        new_mean = ((1 - momentum) * running_mean + momentum * mean).astype(running_mean.dtype)
        new_var = ((1 - momentum) * running_var + momentum * unbiased).astype(running_var.dtype)
    else:
        mean, var = (running_mean.astype(stat_dt),
                     running_var.astype(stat_dt))
        new_mean, new_var = running_mean, running_var

    inv = jax.lax.rsqrt(var + eps)
    ys = []
    for xf in xfs:
        y = (xf - mean.reshape(shape)) * inv.reshape(shape)
        y = y * gamma.astype(stat_dt).reshape(shape) + beta.astype(stat_dt).reshape(shape)
        ys.append(y.astype(x.dtype))
    return ys, new_mean, new_var


def group_norm(
    x: jax.Array,
    gamma: Optional[jax.Array],
    beta: Optional[jax.Array],
    num_groups: int,
    *,
    eps: float = 1e-5,
    data_format: str = "NCHW",
) -> jax.Array:
    """Per-sample, per-group normalization over (C/G, H, W)
    (reference ``groupnorm_ops.cpp``; eps 1e-5)."""
    if data_format == "NHWC":
        x_nchw = jnp.transpose(x, (0, 3, 1, 2))
        y = group_norm(x_nchw, gamma, beta, num_groups, eps=eps, data_format="NCHW")
        return jnp.transpose(y, (0, 2, 3, 1))

    n, c, h, w = x.shape
    if c % num_groups != 0:
        raise ValueError(f"channels {c} not divisible by groups {num_groups}")
    stat_dt = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    xg = x.astype(stat_dt).reshape(n, num_groups, c // num_groups, h, w)
    # GroupNorm keeps the stable two-pass mean/var: unlike BN there is no
    # independent pivot (running stats) to center the one-pass sum/sumsq on,
    # and an x-derived pivot forces XLA to materialize the centered tensor
    # (measured 3x slower than two-pass on v5e — see batch_norm's note).
    mean = jnp.mean(xg, axis=(2, 3, 4), keepdims=True)
    var = jnp.var(xg, axis=(2, 3, 4), keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(n, c, h, w)
    if gamma is not None:
        y = y * gamma.astype(stat_dt).reshape(1, c, 1, 1)
    if beta is not None:
        y = y + beta.astype(stat_dt).reshape(1, c, 1, 1)
    return y.astype(x.dtype)
