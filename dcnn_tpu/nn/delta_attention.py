"""Kimi Delta Attention (KDA, arXiv:2510.26692), the training form: linear
attention whose state of ``head_dim x head_dim`` a head is written by the
delta rule and forgotten channel by channel.

For one head, ``t`` the position (``Conv`` a depthwise causal convolution
over the last ``conv_size`` positions of each channel, no bias):

    q_t = L2Norm(SiLU(Conv(W_q x)_t)) / sqrt(head_dim)
    k_t = L2Norm(SiLU(Conv(W_k x)_t)),   v_t = SiLU(Conv(W_v x)_t)
    g_t = -exp(A_log[h]) * softplus(W_f2 (W_f1 x_t) + dt_bias)    (log decay, a vector)
    beta_t = sigmoid(w_b x_t)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,   o_t = S_t^T q_t
    y_t = W_o (RMSNorm(o_t) * w_norm * sigmoid(W_g2 (W_g1 x_t) + b_g))

The recurrence runs in chunks (``ops/delta_rule.py``): one algorithm, and the
inside of a chunk by Pallas kernels on the TPU where the head is a multiple
of 128 wide and a chunk 16 to 64 positions (``takes_kernel``), by XLA's
products anywhere else. The layer computes whatever batch it is given; a
trainer that has to hold the rule's temporaries gives it a sequence at a
time (``models/latent_moe.py``). The two gates are low-rank, of rank ``head_dim`` as the
family's reference implementation has them. ``A_log`` and ``dt_bias`` set
the decay's rate and are read in float32 whatever the mode (``FLOAT32``: the
model hands them over uncast). No cache and no decode form: nothing in this
repo serves the model (ROADMAP R-M4).

Scopes, side by side: ``<name>`` (projections, convolutions, gates, the
output's norm and projection) and ``<name>.chunk`` (the chunked rule alone).
Counters, at trace time: ``nn_kda_chunked_total``, KDA layers of traced
programs; ``nn_kda_kernel_total``, those of them whose rule took the kernels.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..obs import get_registry
from ..ops.delta_rule import chunked_gated_delta_rule, takes_kernel
from . import initializers as init
from .factory import register_layer
from .layer import ParameterizedLayer
from .transformer import matmul, rms_norm

L2_EPS = 1e-6


def causal_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """``y_t = sum_j taps[j] * x_{t - (K - 1) + j}`` for each channel of
    ``x [B, S, C]``, ``taps [K, C]``; positions before the first are zeros."""
    size, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (size - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s].astype(jnp.float32) * taps[j].astype(jnp.float32)
               for j in range(size)).astype(x.dtype)


def l2_norm(x: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + L2_EPS)
            ).astype(x.dtype)


@register_layer("delta_attention")
class DeltaAttentionLayer(ParameterizedLayer):
    FLOAT32 = ("A_log", "dt_bias")

    def __init__(self, num_heads: int, head_dim: int, *, conv_size: int = 4,
                 chunk: int = 64, epsilon: float = 1e-5, init_std: float = 0.02,
                 name: Optional[str] = None):
        super().__init__(name)
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.conv_size, self.chunk = int(conv_size), int(chunk)
        self.epsilon, self.init_std = float(epsilon), float(init_std)

    def init(self, key, input_shape):
        e, h, d = input_shape[-1], self.num_heads, self.head_dim
        c, std = h * d, self.init_std
        (kq, kk, kv, kcq, kck, kcv, kf1, kf2, ka, kdt, kb, kg1, kg2, ko) = jax.random.split(key, 14)
        bound = 1.0 / math.sqrt(self.conv_size)          # a depthwise Conv1d's default

        def taps(key):
            return jax.random.uniform(key, (self.conv_size, c), jnp.float32, -bound, bound)
        # a step drawn log-uniformly in [0.001, 0.1], through the inverse of softplus
        dt = jnp.exp(jax.random.uniform(kdt, (c,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {"wq": init.normal(kq, (e, c), std), "wk": init.normal(kk, (e, c), std),
                "wv": init.normal(kv, (e, c), std),
                "conv_q": taps(kcq),
                "conv_k": taps(kck),
                "conv_v": taps(kcv),
                "f_a": init.normal(kf1, (e, d), std), "f_b": init.normal(kf2, (d, c), std),
                "A_log": jnp.log(jax.random.uniform(ka, (h,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "wb": init.normal(kb, (e, h), std),
                "g_a": init.normal(kg1, (e, d), std), "g_b": init.normal(kg2, (d, c), std),
                "g_bias": init.zeros((c,)), "o_norm": init.ones((d,)),
                "wo": init.normal(ko, (c, e), std)}, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        b, s, _ = x.shape
        h, d = self.num_heads, self.head_dim
        get_registry().counter(
            "nn_kda_chunked_total",
            "Kimi Delta Attention layers in traced programs (each takes the "
            "chunked form of the gated delta rule)").inc()
        if takes_kernel(self.chunk, d, d):
            get_registry().counter(
                "nn_kda_kernel_total",
                "Kimi Delta Attention layers in traced programs whose chunks' "
                "inside the Pallas kernels compute (on the TPU, on a geometry "
                "they take)").inc()

        def heads(a):
            return a.reshape(b, s, h, d).transpose(0, 2, 1, 3)               # (B, H, S, D)

        with jax.named_scope(self.name):
            q, k, v = (jax.nn.silu(causal_conv(matmul(x, params["w" + n]), params["conv_" + n]))
                       for n in "qkv")
            q, k, v = heads(q), heads(k), heads(v)
            q, k = l2_norm(q) * d ** -0.5, l2_norm(k)
            rate = matmul(matmul(x, params["f_a"]), params["f_b"]).astype(jnp.float32)
            g = heads(jax.nn.softplus(rate + params["dt_bias"].astype(jnp.float32)))
            g = -jnp.exp(params["A_log"].astype(jnp.float32))[:, None, None] * g
            beta = jax.nn.sigmoid(matmul(x, params["wb"]).astype(jnp.float32)).transpose(0, 2, 1)
        with jax.named_scope(self.name + ".chunk"):
            o = chunked_gated_delta_rule(q, k, v, g, beta, chunk=self.chunk)
        with jax.named_scope(self.name):
            gate = matmul(matmul(x, params["g_a"]), params["g_b"]) + params["g_bias"]
            o = rms_norm(o, params["o_norm"], self.epsilon).transpose(0, 2, 1, 3)
            o = o.reshape(b, s, h * d) * jax.nn.sigmoid(gate)
            return matmul(o, params["wo"]), state

    def param_count(self, input_shape):
        e, h, d = input_shape[-1], self.num_heads, self.head_dim
        c = h * d
        return (4 * e * c + 3 * self.conv_size * c + 2 * (e * d + d * c) + 2 * c
                + h + e * h + d)

    def get_config(self):
        return {**super().get_config(), "num_heads": self.num_heads,
                "head_dim": self.head_dim, "conv_size": self.conv_size,
                "chunk": self.chunk, "epsilon": self.epsilon, "init_std": self.init_std}
