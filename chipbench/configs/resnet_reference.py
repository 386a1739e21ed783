"""Plain reference for the ResNet configurations (``resnet18_tin``; basic and
bottleneck blocks alike, from the layer list): forward, loss, gradients and Adam/AdamW in straightforward
``jax.numpy`` float32, matrix products at ``highest`` precision. It follows
He et al. 2015 and the layer list of the configuration file, imports nothing
of the program and takes nothing the program made.

Semantics the configuration states (and the program is held to):

- convolution with symmetric zero padding, weights OIHW, optional bias;
- batch norm in training mode: per-channel mean and biased variance over
  (N, H, W) of the batch it is called on, ``y = (x - mean) / sqrt(var + eps)
  * gamma + beta``; running statistics ``r = (1 - m) r + m * batch`` with the
  unbiased variance (they do not enter a training step's result);
- residual block: ``relu(main(x) + shortcut(x))``;
- loss: mean over the batch of ``logsumexp(z) - z[target]``;
- Adam with bias correction; ``adamw`` decays decoupled
  (``p <- p - wd*lr*p - lr*mhat/(sqrt(vhat)+eps)``), ``adam`` with
  ``weight_decay`` adds ``wd*lr*p`` to the update.

``quantize`` puts every convolution's and the dense layer's two operands
through a lower precision on the way in (the control of ``correct``); the
accumulation and everything else stay float32.
"""

from __future__ import annotations

import math
import os
import sys
from typing import Callable, Optional

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from flops import expand_block  # noqa: E402  (the block layouts, one copy)

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- weights

def _uniform(key, shape, fan_in):
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _init_layers(layers, key, cin):
    """(params, state, channels out) of a list of plain layers."""
    params, state = [], []
    for i, layer in enumerate(layers):
        k = jax.random.fold_in(key, i)
        op = layer["op"]
        if op == "conv":
            fan_in = cin * layer["k"] ** 2
            p = {"w": _uniform(jax.random.fold_in(k, 0),
                               (layer["out"], cin, layer["k"], layer["k"]), fan_in)}
            if layer["bias"]:
                p["b"] = _uniform(jax.random.fold_in(k, 1), (layer["out"],), fan_in)
            params.append(p)
            state.append({})
            cin = layer["out"]
        elif op == "bn":
            params.append({"gamma": jnp.ones((cin,), jnp.float32),
                           "beta": jnp.zeros((cin,), jnp.float32)})
            state.append({"running_mean": jnp.zeros((cin,), jnp.float32),
                          "running_var": jnp.ones((cin,), jnp.float32)})
        elif op in ("basic", "bottleneck"):
            main, shortcut = expand_block(layer)
            pm, sm, cout = _init_layers(main, jax.random.fold_in(k, 0), cin)
            ps, ss, _ = _init_layers(shortcut, jax.random.fold_in(k, 1), cin)
            params.append({"main": pm, "shortcut": ps})
            state.append({"main": sm, "shortcut": ss})
            cin = cout
        elif op == "dense":
            p = {"w": _uniform(jax.random.fold_in(k, 0), (layer["out"], cin), cin)}
            if layer["bias"]:
                p["b"] = _uniform(jax.random.fold_in(k, 1), (layer["out"],), cin)
            params.append(p)
            state.append({})
            cin = layer["out"]
        else:  # relu, pools, flatten: no parameters
            if op == "flatten":
                cin = layer["features"]
            params.append({})
            state.append({})
    return tuple(params), tuple(state), cin


def _with_flatten_width(cfg):
    """The layer list with the flatten layer's output width filled in."""
    c, h, w = cfg["input_shape"]
    out = []
    for layer in cfg["layers"]:
        op = layer["op"]
        if op == "conv":
            c = layer["out"]
        if op in ("conv", "maxpool", "avgpool"):
            h = (h + 2 * layer["pad"] - layer["k"]) // layer["stride"] + 1
            w = (w + 2 * layer["pad"] - layer["k"]) // layer["stride"] + 1
        elif op in ("basic", "bottleneck"):
            c = layer["out"]
            h = (h - 1) // layer["stride"] + 1
            w = (w - 1) // layer["stride"] + 1
        elif op == "flatten":
            layer = dict(layer, features=c * h * w)
        out.append(layer)
    return out


def init(cfg: dict, key):
    """Weights and batch-norm state from a key: uniform in
    ``+-1/sqrt(fan_in)`` for weights and biases, gamma 1, beta 0."""
    params, state, _ = _init_layers(_with_flatten_width(cfg), key,
                                    cfg["input_shape"][0])
    return params, state


# ---------------------------------------------------------------- forward

def _conv(x, p, layer, quantize):
    w = p["w"]
    if quantize is not None:
        x, w = quantize(x), quantize(w)
    y = jax.lax.conv_general_dilated(
        x, w, (layer["stride"],) * 2, ((layer["pad"],) * 2,) * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
    if "b" in p:
        y = y + p["b"].reshape(1, -1, 1, 1)
    return y


def _bn(x, p, s, layer):
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.mean(jnp.square(x - mean.reshape(1, -1, 1, 1)), axis=(0, 2, 3))
    y = (x - mean.reshape(1, -1, 1, 1)) * jax.lax.rsqrt(
        var + layer["eps"]).reshape(1, -1, 1, 1)
    y = y * p["gamma"].reshape(1, -1, 1, 1) + p["beta"].reshape(1, -1, 1, 1)
    n = x.size // x.shape[1]
    m = layer["momentum"]
    new = {"running_mean": (1 - m) * s["running_mean"] + m * mean,
           "running_var": (1 - m) * s["running_var"] + m * var * (n / max(n - 1, 1))}
    return y, new


def _pool(x, layer, op):
    k, st, pd = layer["k"], layer["stride"], layer["pad"]
    dims, strides = (1, 1, k, k), (1, 1, st, st)
    pads = ((0, 0), (0, 0), (pd, pd), (pd, pd))
    if op == "maxpool":
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims, strides, pads)
    return jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides, pads) / (k * k)


def _apply_layers(layers, params, state, x, quantize):
    new_state = []
    for layer, p, s in zip(layers, params, state):
        op = layer["op"]
        ns = s
        if op == "conv":
            x = _conv(x, p, layer, quantize)
        elif op == "bn":
            x, ns = _bn(x, p, s, layer)
        elif op == "relu":
            x = jnp.maximum(x, 0.0)
        elif op in ("maxpool", "avgpool"):
            x = _pool(x, layer, op)
        elif op in ("basic", "bottleneck"):
            main, shortcut = expand_block(layer)

            # saved for the backward pass: a block's input only, its inside
            # is computed again (so that float32 at the cells' batches fits)
            @jax.checkpoint
            def block(p, s, x):
                h, sm = _apply_layers(main, p["main"], s["main"], x, quantize)
                r, ss = _apply_layers(shortcut, p["shortcut"], s["shortcut"], x, quantize)
                return jnp.maximum(h + r, 0.0), {"main": sm, "shortcut": ss}

            x, ns = block(p, s, x)
        elif op == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif op == "dense":
            w = p["w"]
            a = x
            if quantize is not None:
                a, w = quantize(a), quantize(w)
            x = jnp.matmul(a, w.T, precision=HIGHEST)
            if "b" in p:
                x = x + p["b"]
        else:
            raise ValueError(f"unknown layer op {op!r}")
        new_state.append(ns)
    return x, tuple(new_state)


def forward(cfg, params, state, x, quantize: Optional[Callable] = None):
    """Training-mode forward: logits and the new batch-norm state."""
    return _apply_layers(cfg["layers"], params, state,
                         x.astype(jnp.float32), quantize)


def loss_fn(logits, onehot):
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.sum(onehot * logits, axis=-1))


# ---------------------------------------------------------------- training

def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": jnp.zeros((), jnp.int32)}


def adam_update(opt: dict, params, grads, opt_state, lr):
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["epsilon"], opt["weight_decay"]
    t = opt_state["t"] + 1
    tf = t.astype(jnp.float32)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, opt_state["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, opt_state["v"], grads)

    def one(p, m, v):
        step = lr * (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf)) + eps)
        if opt["type"] == "adamw":
            return p - wd * lr * p - step
        return p - step - wd * lr * p

    return jax.tree_util.tree_map(one, params, m, v), {"m": m, "v": v, "t": t}


def loss_and_grads(cfg, params, state, x, y,
                   quantize: Optional[Callable] = None, rows=None):
    """Mean loss, its gradient and the new state over one batch. ``rows`` (a slice) keeps only those rows: the planted fault
    "half of the batch left out" of the benchmark's tests."""
    if rows is not None:
        x, y = x[rows], y[rows]

    def f(p):
        logits, ns = forward(cfg, p, state, x, quantize)
        return loss_fn(logits, y), ns

    (loss, ns), g = jax.value_and_grad(f, has_aux=True)(params)
    return loss, g, ns


def train_step(cfg, params, state, opt_state, x, y, lr,
               quantize: Optional[Callable] = None, rows=None):
    """One optimizer step on one batch; returns the new (params, state,
    opt_state), the loss and the gradient the optimizer got."""
    loss, grads, state = loss_and_grads(cfg, params, state, x, y, quantize, rows)
    params, opt_state = adam_update(cfg["optimizer"], params, grads, opt_state, lr)
    return params, state, opt_state, loss, grads


# ---------------------------------------------------------------- control

def quantizer(name: Optional[str]) -> Optional[Callable]:
    """The operand rounding of a control precision. ``fp8_e4m3``: each
    operand scaled by its largest magnitude to the format's range, rounded to
    float8 e4m3 and scaled back; the gradient passes straight through, so the
    backward products see the rounded forward operands and float32
    cotangents. ``bf16``: operands rounded to bfloat16."""
    if name in (None, "", "float32"):
        return None
    if name == "bf16":
        dt, top = jnp.bfloat16, None
    elif name == "fp8_e4m3":
        dt, top = jnp.float8_e4m3fn, 448.0
    else:
        raise ValueError(f"unknown control precision {name!r}")

    def q(a):
        if top is None:
            r = a.astype(dt).astype(a.dtype)
        else:
            s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
            r = (a / s).astype(dt).astype(a.dtype) * s
        return a + jax.lax.stop_gradient(r - a)

    return q
