"""Loss functions — value and explicit gradient, matching reference reductions.

Reference equivalent: the six Loss classes + kernels
(``include/nn/loss.hpp:59-401``, ``src/nn/loss_impl/cpu/loss_ops.cpp``,
``cuda/loss_ops.cu``). Semantics reproduced exactly:

- targets are one-hot (or dense regression targets), same as the reference's
  data loaders produce;
- classification losses reduce as mean over the batch; regression losses as
  mean over all elements (loss_ops.cpp: ``/ batch_size`` vs ``/ total_size``);
- each loss exposes ``*_grad`` with the same scaling the reference's
  ``compute_gradient`` kernels apply (e.g. softmax-CE grad =
  ``(softmax - target)/batch``) so pipeline coordinators can inject the initial
  backward tensor exactly like the reference does
  (``sync_pipeline_coordinator.cpp:144-156``).

In the single-device trainer the gradient versions are unused — autodiff
differentiates the loss value — but they are tested against autodiff.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp


def _f32(a: jax.Array) -> jax.Array:
    """Upcast to at-least-fp32 (fp64 inputs stay fp64 — the fp64 precision
    mode must not lose bits at the loss boundary)."""
    a = jnp.asarray(a)
    if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != jnp.float64:
        return a.astype(jnp.float32)
    return a


def upcast_logits(logits: jax.Array) -> jax.Array:
    """Model outputs -> loss/metric dtype: fp32 for fp32/bf16 activations,
    fp64 preserved (the fp64 mode must not quantize the loss boundary).
    The canonical cast for every trainer/eval path."""
    return _f32(logits)


def _loss_fp32(fn):
    """Loss math always runs in fp32: under the bf16 mixed-precision mode
    (core/precision.py) models emit bf16 predictions, and logsumexp/softmax
    in bf16 costs real accuracy. Every consumer (trainer, pipeline
    coordinators, user code calling get_loss) gets the fp32 boundary here,
    at the loss itself."""
    @functools.wraps(fn)
    def wrapped(pred, targets, *args, **kw):
        return fn(_f32(pred), _f32(targets), *args, **kw)
    return wrapped


def _grad_fp32(fn):
    """Gradient twins compute in fp32 but cast the result back to the
    prediction dtype, so a pipeline backward seed matches the stage's
    compute dtype (the coordinator feeds it straight into a vjp)."""
    @functools.wraps(fn)
    def wrapped(pred, targets, *args, **kw):
        out = fn(_f32(pred), _f32(targets), *args, **kw)
        return out.astype(jnp.asarray(pred).dtype)
    return wrapped


# ---------------- classification ----------------

@_loss_fp32
def cross_entropy(probs: jax.Array, targets: jax.Array, eps: float = 1e-15) -> jax.Array:
    """CE over probability inputs, clamped to [eps, 1-eps]
    (reference ``CrossEntropyLoss``, loss.hpp:59; eps 1e-15)."""
    p = jnp.clip(probs, eps, 1.0 - eps)
    per_sample = -jnp.sum(targets * jnp.log(p), axis=-1)
    return jnp.mean(per_sample)


@_grad_fp32
def cross_entropy_grad(probs: jax.Array, targets: jax.Array) -> jax.Array:
    """Reference grad kernel is ``(pred - target)/batch``
    (loss_ops.cpp compute_crossentropy_gradient). NOTE: this is the *fused*
    softmax-CE shortcut, not ∂loss/∂probs — it already folds in the softmax
    jacobian, assuming the producing layer's softmax backward is treated as
    identity (which is how the reference wires it). Kept verbatim for pipeline
    parity; single-device training autodiffs the loss value instead."""
    return (probs - targets) / probs.shape[0]


@_loss_fp32
def softmax_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Stable fused softmax+CE over logits (reference
    ``SoftmaxCrossEntropyLoss``, loss.hpp:122): loss = logsumexp(x) - x[target],
    mean over batch."""
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    per_sample = jnp.sum(targets * (lse - logits), axis=-1)
    return jnp.mean(per_sample)


@_grad_fp32
def softmax_cross_entropy_grad(logits: jax.Array, targets: jax.Array) -> jax.Array:
    return (jax.nn.softmax(logits, axis=-1) - targets) / logits.shape[0]


@_loss_fp32
def log_softmax_cross_entropy(log_probs: jax.Array, targets: jax.Array) -> jax.Array:
    """CE over log-probability inputs (reference ``LogSoftmaxCrossEntropyLoss``,
    loss.hpp:180) — the model's last layer applies log-softmax."""
    per_sample = -jnp.sum(targets * log_probs, axis=-1)
    return jnp.mean(per_sample)


@_grad_fp32
def log_softmax_cross_entropy_grad(log_probs: jax.Array, targets: jax.Array) -> jax.Array:
    """Fused like the reference kernel: ``(exp(logp) - t)/batch`` equals the
    end-to-end gradient at the *logits* feeding the log-softmax — i.e. the
    log-softmax jacobian is folded in (see ``cross_entropy_grad`` note)."""
    return (jnp.exp(log_probs) - targets) / log_probs.shape[0]


def token_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Next-token cross-entropy: ``logits [..., V]`` against integer
    ``labels [...]``, mean over every token; the logsumexp in float32."""
    logits = _f32(logits)
    picked = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32), axis=-1)
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked[..., 0])


def token_cross_entropy_grad(logits: jax.Array, labels: jax.Array) -> jax.Array:
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    g = (jax.nn.softmax(_f32(logits), axis=-1) - onehot) / labels.size
    return g.astype(jnp.asarray(logits).dtype)


# ---------------- regression ----------------

@_loss_fp32
def mse_loss(pred: jax.Array, targets: jax.Array) -> jax.Array:
    return jnp.mean(jnp.square(pred - targets))


@_grad_fp32
def mse_grad(pred: jax.Array, targets: jax.Array) -> jax.Array:
    return 2.0 * (pred - targets) / pred.size


@_loss_fp32
def mae_loss(pred: jax.Array, targets: jax.Array) -> jax.Array:
    return jnp.mean(jnp.abs(pred - targets))


@_grad_fp32
def mae_grad(pred: jax.Array, targets: jax.Array) -> jax.Array:
    return jnp.sign(pred - targets) / pred.size


@_loss_fp32
def huber_loss(pred: jax.Array, targets: jax.Array, delta: float = 1.0) -> jax.Array:
    """Huber with delta 1.0 default (reference loss.hpp:345)."""
    d = pred - targets
    a = jnp.abs(d)
    quad = 0.5 * jnp.square(d)
    lin = delta * (a - 0.5 * delta)
    return jnp.mean(jnp.where(a <= delta, quad, lin))


@_grad_fp32
def huber_grad(pred: jax.Array, targets: jax.Array, delta: float = 1.0) -> jax.Array:
    d = pred - targets
    g = jnp.where(jnp.abs(d) <= delta, d, delta * jnp.sign(d))
    return g / pred.size


# ---------------- registry (reference LossFactory, loss.hpp:403) ----------------

LossFn = Callable[[jax.Array, jax.Array], jax.Array]

LOSSES: Dict[str, Tuple[LossFn, LossFn]] = {
    "crossentropy": (cross_entropy, cross_entropy_grad),
    "softmax_crossentropy": (softmax_cross_entropy, softmax_cross_entropy_grad),
    "logsoftmax_crossentropy": (log_softmax_cross_entropy, log_softmax_cross_entropy_grad),
    "token_crossentropy": (token_cross_entropy, token_cross_entropy_grad),
    "mse": (mse_loss, mse_grad),
    "mae": (mae_loss, mae_grad),
    "huber": (huber_loss, huber_grad),
}


def get_loss(name: str) -> LossFn:
    try:
        return LOSSES[name.lower()][0]
    except KeyError:
        raise ValueError(f"unknown loss {name!r}; known: {sorted(LOSSES)}") from None


def get_loss_grad(name: str) -> LossFn:
    return LOSSES[name.lower()][1]
