"""Causal decoder model for generative serving (ISSUE 20).

No reference analog (the reference is a CNN-only classifier framework,
SURVEY.md §5.7); this is the ``mha_classifier`` family grown one step: the
same ``MultiHeadAttentionLayer`` blocks with the same relu-residual wiring
(``out = relu(attn(x) + x)``), but causal, over a learned token embedding,
with a vocab-projection head — the smallest model whose serving shape is
*iterative* (one token per step, hundreds of steps per request) instead of
one-shot. That execution shape is the whole point: the continuous batcher
(``serve/decode.py``) and the paged KV cache (``serve/kvcache.py``) exist
to serve it.

Two forward paths, one parameter set:

- :meth:`MHADecoder.apply` — full-sequence causal forward ``(B, S)`` →
  ``(B, S, V)`` logits. The numerics oracle (naive materialized attention),
  used by training-shaped code and the decode-consistency tests;
- :meth:`MHADecoder.decode_step` — single-token forward against explicit
  per-layer K/V contexts (the serving hot path; the engine feeds it
  gathered KV pages). Per-row independent: a row's output depends only on
  that row's token/position/context, which is what makes continuous
  batching bit-stable per sequence (``tests/test_decode.py``).

Kept out of ``Sequential`` deliberately: integer token input and per-layer
cache state don't fit the ``(B, *input_shape)`` float pipeline contract,
and wedging them in would cost more than the factory conveniences buy.
``get_config``/``from_config`` keep it checkpoint-friendly.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.precision import get_precision
from ..nn import initializers as init
from ..nn.attention_layer import MultiHeadAttentionLayer


class MHADecoder:
    """Tiny causal transformer decoder: embed → N × (causal MHA + relu
    residual) → vocab head. Greedy decode over it is deterministic, which
    the serving tests lean on (bit-identical replay per sequence)."""

    def __init__(self, vocab_size: int = 64, embed_dim: int = 64,
                 num_heads: int = 4, num_layers: int = 2,
                 max_seq_len: int = 64, use_bias: bool = True,
                 name: str = "mha_decoder"):
        if vocab_size < 2:
            raise ValueError(f"vocab_size must be >= 2, got {vocab_size}")
        if embed_dim % num_heads:
            raise ValueError(f"embed dim {embed_dim} not divisible by "
                             f"{num_heads} heads")
        self.name = name
        self.vocab_size = int(vocab_size)
        self.embed_dim = int(embed_dim)
        self.num_heads = int(num_heads)
        self.num_layers = int(num_layers)
        self.max_seq_len = int(max_seq_len)
        self.use_bias = bool(use_bias)
        # naive impl: the materializing oracle — exact, and the decode
        # path's masking convention matches it term for term
        self.blocks: List[MultiHeadAttentionLayer] = [
            MultiHeadAttentionLayer(num_heads, embed_dim, causal=True,
                                    impl="naive", use_bias=use_bias,
                                    name=f"{name}_mha{i}")
            for i in range(num_layers)]

    # -- params --
    def init(self, key: jax.Array) -> Dict[str, Any]:
        keys = jax.random.split(key, self.num_layers + 3)
        e, v = self.embed_dim, self.vocab_size
        params: Dict[str, Any] = {
            "embed": init.kaiming_uniform(keys[0], (v, e), e),
            "head_w": init.kaiming_uniform(keys[1], (e, v), e),
            "head_b": init.zeros((v,)),
            "blocks": [],
        }
        for i, blk in enumerate(self.blocks):
            bp, _ = blk.init(keys[i + 2], (self.max_seq_len, e))
            params["blocks"].append(bp)
        return params

    # -- full-sequence oracle --
    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """Full causal forward: ``tokens (B, S)`` int32 → logits
        ``(B, S, V)``. The training-shaped path and the decode oracle."""
        x = jnp.take(params["embed"], tokens, axis=0)
        for blk, bp in zip(self.blocks, params["blocks"]):
            y, _ = blk.apply(bp, {}, x)
            x = jax.nn.relu(y + x)
        return (jnp.matmul(x, params["head_w"], precision=get_precision())
                + params["head_b"])

    # -- single-token serving path --
    def embed_tokens(self, params: Dict[str, Any],
                     tokens: jax.Array) -> jax.Array:
        """``(B,)`` int32 token ids → ``(B, E)`` embeddings."""
        return jnp.take(params["embed"], tokens, axis=0)

    def head(self, params: Dict[str, Any], x_t: jax.Array) -> jax.Array:
        """``(B, E)`` final hidden → ``(B, V)`` logits."""
        return (jnp.matmul(x_t, params["head_w"],
                           precision=get_precision()) + params["head_b"])

    def decode_dense(self, params: Dict[str, Any], x_t: jax.Array,
                     k_caches: Sequence[jax.Array],
                     v_caches: Sequence[jax.Array], positions: jax.Array,
                     ) -> Tuple[jax.Array, List[jax.Array], List[jax.Array]]:
        """Single-token decode through per-layer DENSE KV caches (each
        ``(B, T, E)``): write this token's K/V rows at ``positions``,
        attend over the prefix (current token included — the oracle's
        causal diagonal), relu-residual, head. Returns ``(logits,
        k_caches, v_caches)``. This is the un-paged reference for the
        serving engine's paged step (``serve/decode.py``), which does the
        same write → gather → attend dance against a shared page pool."""
        x = x_t
        new_k: List[jax.Array] = []
        new_v: List[jax.Array] = []
        for blk, bp, kc, vc in zip(self.blocks, params["blocks"],
                                   k_caches, v_caches):
            y, kc, vc = blk.decode(bp, {}, x, kc, vc, positions)
            x = jax.nn.relu(y + x)
            new_k.append(kc)
            new_v.append(vc)
        return self.head(params, x), new_k, new_v

    # -- config --
    def get_config(self) -> Dict[str, Any]:
        return {"type": "mha_decoder", "name": self.name,
                "vocab_size": self.vocab_size, "embed_dim": self.embed_dim,
                "num_heads": self.num_heads, "num_layers": self.num_layers,
                "max_seq_len": self.max_seq_len, "use_bias": self.use_bias}

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "MHADecoder":
        cfg = dict(cfg)
        cfg.pop("type", None)
        return cls(**cfg)

    def __repr__(self) -> str:
        return (f"MHADecoder({self.name!r}, vocab={self.vocab_size}, "
                f"embed={self.embed_dim}, heads={self.num_heads}, "
                f"layers={self.num_layers}, max_seq={self.max_seq_len})")


def create_mha_decoder(data_format: str = "NCHW") -> MHADecoder:
    """Zoo factory for the default small decoder. ``data_format`` is
    accepted for zoo-signature uniformity and ignored (token input)."""
    return MHADecoder()
