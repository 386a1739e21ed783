"""Multi-head latent attention (MLA), the training form.

Keys and values are up-projected from one low-rank latent per token
(``kv_rank`` wide, RMS-normalised) and the positional part of the key is one
rotary head shared by all heads (``rotary=False``: no positions at all, the
same columns unrotated, as a model that leaves order to its other layers has
it); queries and keys score at ``nope + rope``
(192) while values mix at ``v_dim`` (128), so the flash kernels run with a
value head dim of their own (``ops/attention.py``). The decode form (a
latent cache, ``W_kvb`` absorbed into the query and output sides) is not
here: nothing in this repo serves the model yet (ROADMAP R-M3).

The scope ``<name>.flash`` holds the kernel alone, beside ``<name>`` and not
inside it, so that a trace tells projections from scores.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.attention import flash_attention
from . import initializers as init
from .factory import register_layer
from .layer import ParameterizedLayer
from .transformer import (apply_rotary, matmul, rms_norm, rotary_inv_freq,
                          rotary_tables, yarn_mscale)


@register_layer("latent_attention")
class LatentAttentionLayer(ParameterizedLayer):
    def __init__(self, num_heads: int, nope_dim: int, rope_dim: int,
                 kv_rank: int, v_dim: int, *, rope_theta: float = 10000.0,
                 rope_scaling: Optional[dict] = None, rotary: bool = True,
                 epsilon: float = 1e-6,
                 init_std: float = 0.02, name: Optional[str] = None):
        super().__init__(name)
        self.num_heads, self.nope_dim, self.rope_dim = int(num_heads), int(nope_dim), int(rope_dim)
        self.kv_rank, self.v_dim = int(kv_rank), int(v_dim)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.rotary = bool(rotary)
        self.epsilon = float(epsilon)
        self.init_std = float(init_std)
        # YaRN: the tables carry mscale / mscale_all_dim, the softmax scale
        # carries mscale_all_dim squared
        rs = self.rope_scaling
        all_dim = yarn_mscale(rs["factor"], rs["mscale_all_dim"]) if rs else 1.0
        self.table_scale = yarn_mscale(rs["factor"], rs["mscale"]) / all_dim if rs else 1.0
        self.softmax_scale = (self.nope_dim + self.rope_dim) ** -0.5 * all_dim ** 2

    def init(self, key, input_shape):
        e = input_shape[-1]
        h, qk = self.num_heads, self.nope_dim + self.rope_dim
        kq, ka, kb, ko = jax.random.split(key, 4)
        std = self.init_std
        return {"wq": init.normal(kq, (e, h * qk), std),
                "wkva": init.normal(ka, (e, self.kv_rank + self.rope_dim), std),
                "kv_norm": init.ones((self.kv_rank,)),
                "wkvb": init.normal(kb, (self.kv_rank, h * (self.nope_dim + self.v_dim)), std),
                "wo": init.normal(ko, (h * self.v_dim, e), std)}, {}

    def apply(self, params, state, x, *, training=False, rng=None):
        b, s, _ = x.shape
        h, nope, rope, dv = self.num_heads, self.nope_dim, self.rope_dim, self.v_dim
        with jax.named_scope(self.name):
            q = matmul(x, params["wq"]).reshape(b, s, h, nope + rope)
            kva = matmul(x, params["wkva"])
            c = rms_norm(kva[..., :self.kv_rank], params["kv_norm"], self.epsilon)
            kv = matmul(c, params["wkvb"]).reshape(b, s, h, nope + dv)
            turn = lambda a: a                                 # noqa: E731
            if self.rotary:
                cos, sin = rotary_tables(
                    s, rotary_inv_freq(rope, self.rope_theta, self.rope_scaling),
                    self.table_scale)
                turn = lambda a: apply_rotary(a, cos, sin)     # noqa: E731
            q = q.transpose(0, 2, 1, 3)                        # (B, H, S, 192)
            q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
            k_pe = turn(kva[..., self.kv_rank:])               # (B, S, 64)
            kv = kv.transpose(0, 2, 1, 3)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_pe[:, None], (b, h, s, rope))], axis=-1)
            v = kv[..., nope:]
        with jax.named_scope(self.name + ".flash"):
            # tiles: the best of five tried on the v5e at 4 x 16 heads x
            # 4096, 192 | 128 (forward and backward 23.5 ms; the kernels'
            # default 1024 x 512: 25.8; tools/bench_lm_kernels.py). Off the
            # TPU flash_attention is the blockwise form.
            o = flash_attention(q, k, v, causal=True, scale=self.softmax_scale,
                                block_q=512, block_kv=1024)
        with jax.named_scope(self.name):
            o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
            return matmul(o, params["wo"]), state

    def param_count(self, input_shape):
        e = input_shape[-1]
        h = self.num_heads
        return (e * h * (self.nope_dim + self.rope_dim)
                + e * (self.kv_rank + self.rope_dim) + self.kv_rank
                + self.kv_rank * h * (self.nope_dim + self.v_dim) + h * self.v_dim * e)

    def get_config(self):
        return {**super().get_config(), "num_heads": self.num_heads,
                "nope_dim": self.nope_dim, "rope_dim": self.rope_dim,
                "kv_rank": self.kv_rank, "v_dim": self.v_dim,
                "rope_theta": self.rope_theta, "rope_scaling": self.rope_scaling,
                "rotary": self.rotary,
                "epsilon": self.epsilon, "init_std": self.init_std}
