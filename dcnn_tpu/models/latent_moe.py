"""A decoder-only language model built from a published configuration:
a leading dense gated MLP and then mixture-of-experts layers with shared
experts, pre-norm residual blocks, untied embedding and head; each layer's
mixer is latent attention (MLA) or, where the configuration says so, Kimi
Delta Attention (KDA). Two families: DeepSeek-V2 (arXiv:2405.04434: MLA with
rotary positions in every layer, softmax routing) and Kimi Linear
(arXiv:2510.26692: ``linear_attn_config`` lists the KDA layers and the MLA
layers, 1-based, three to one; MLA without positions, ``mla_use_nope``;
sigmoid routing).

    h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    logits = RMSNorm(y_last) W_head

The configuration is a dict under the model's own published key names, either
family's (``n_routed_experts`` or ``num_experts``, ``num_experts_per_tok`` or
``num_experts_per_token`` ...: ``_FAMILY_KEYS``). Three keys say which part
of a deployment this process holds: the experts' count (the router's width
stays ``<that key>_published``), ``first_expert``, and ``vocab_size`` (a slice
is a smaller vocabulary). ``create_model`` names give such configurations;
``resized`` gives the same model at other sizes (tests, the benchmark's CPU
rehearsal).

Like ``MHADecoder`` this is not a ``Sequential`` (integer token input), but
it has the ``init`` / ``apply(params, state, x, training, rng)`` contract
that ``make_train_step`` and the resident epoch use, so ``Trainer`` trains it
from a ``TokenDataset`` as it trains a CNN from a ``DeviceDataset``.

**Recomputation.** In training each block is a ``jax.checkpoint``: its input
and the flash kernel's two results (output, logsumexp) are kept, everything
else of the block is computed again in the backward pass. A KDA block keeps
the mixer's result too: its mixer runs a sequence at a time, each sequence a
checkpoint of its own, and its feed-forward half is one more.

Scopes: ``embed``, ``l<k>.attn``, ``l<k>.attn.flash`` (MLA layers) or
``l<k>.kda``, ``l<k>.kda.chunk`` (KDA layers), ``l<k>.mlp`` (dense
layers) or ``l<k>.router`` / ``.dispatch`` / ``.experts`` / ``.combine`` /
``.shared``, ``head``; the norms carry their consumer's scope.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.precision import cast_to_compute
from ..nn import initializers as init
from ..nn.delta_attention import DeltaAttentionLayer
from ..nn.latent_attention import LatentAttentionLayer
from ..nn.moe import MoELayer, publish_routing
from ..nn.transformer import gated_mlp, init_gated_mlp, rms_norm
from ..ops.attention import FLASH_LSE, FLASH_OUT

# One rank of eight that share each layer of DeepSeek-V2-Lite
# (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json):
# 8 of the 64 routed experts, an eighth of the vocabulary, the leading dense
# layer and 4 of the 26 expert layers; every width as published.
DEEPSEEK_V2_LITE_EP8: Dict[str, Any] = {
    "hidden_size": 2048, "intermediate_size": 10944,
    "moe_intermediate_size": 1408, "num_attention_heads": 16,
    "kv_lora_rank": 512, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "first_k_dense_replace": 1, "n_shared_experts": 2,
    "num_experts_per_tok": 6, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "aux_loss_alpha": 0.001,
    "initializer_std": 0.006,
    "num_hidden_layers": 5, "n_routed_experts": 8,
    "n_routed_experts_published": 64, "first_expert": 0,
    "vocab_size": 12800,
}


# One rank of 32 that share each layer of Kimi-Linear-48B-A3B-Instruct
# (https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json):
# 8 of the 256 routed experts, an eighth of the vocabulary, the leading dense
# layer and the four that follow (layers 1, 2, 3, 5 KDA and 4 MLA of the
# published lists: one whole period); every width as published.
KIMI_LINEAR_48B_EP32: Dict[str, Any] = {
    "hidden_size": 2304, "intermediate_size": 9216,
    "moe_intermediate_size": 1024, "num_attention_heads": 32,
    "kv_lora_rank": 512, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "mla_use_nope": True, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "rope_scaling": None,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                       22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "kda_chunk_size": 64,
    "first_k_dense_replace": 1, "num_shared_experts": 1,
    "num_experts_per_token": 8, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "routed_scaling_factor": 2.446,
    "initializer_std": 0.006,
    "num_hidden_layers": 5, "num_experts": 8,
    "num_experts_published": 256, "first_expert": 0,
    "vocab_size": 20480,
}

# what this module calls a size -> the key either family publishes it under
_FAMILY_KEYS = {
    "experts_held": ("n_routed_experts", "num_experts"),
    "top_k": ("num_experts_per_tok", "num_experts_per_token"),
    "n_shared": ("n_shared_experts", "num_shared_experts"),
    "norm_topk": ("norm_topk_prob", "moe_renormalize"),
    "scoring": ("scoring_func", "moe_router_activation_func"),
}


def _published(config: Dict[str, Any], what: str, default=None):
    """``what`` under whichever family's key the configuration has."""
    return next((config[key] for key in _FAMILY_KEYS[what] if key in config), default)


class LatentMoEDecoder:
    input_shape = None        # token ids: no per-sample float shape

    def __init__(self, config: Dict[str, Any], name: str = "latent_moe_decoder"):
        if config.get("q_lora_rank") is not None:
            raise NotImplementedError("a low-rank query projection (q_lora_rank)")
        self.name = name
        self.config = dict(config)
        c = self.config
        self.hidden, self.vocab = int(c["hidden_size"]), int(c["vocab_size"])
        self.eps, self.std = float(c["rms_norm_eps"]), float(c["initializer_std"])
        self.num_layers = int(c["num_hidden_layers"])
        self.dense_layers = int(c["first_k_dense_replace"])
        linear = c.get("linear_attn_config") or {}
        # the published lists count layers from 1
        self.kda_layers = [k for k in range(self.num_layers)
                           if k + 1 in linear.get("kda_layers", ())]
        self.attn = [
            DeltaAttentionLayer(
                linear["num_heads"], linear["head_dim"],
                conv_size=linear["short_conv_kernel_size"],
                chunk=c.get("kda_chunk_size", 64), epsilon=self.eps,
                init_std=self.std, name=f"l{k}.kda")
            if k in self.kda_layers else
            LatentAttentionLayer(
                c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                c["kv_lora_rank"], c["v_head_dim"], rope_theta=c["rope_theta"],
                rope_scaling=c.get("rope_scaling"),
                rotary=not c.get("mla_use_nope", False), epsilon=self.eps,
                init_std=self.std, name=f"l{k}.attn")
            for k in range(self.num_layers)]
        held_key = next(key for key in _FAMILY_KEYS["experts_held"] if key in c)
        self.experts_held = int(c[held_key])
        self.n_routed = int(c.get(held_key + "_published", c[held_key]))
        self.top_k = int(_published(c, "top_k"))
        self.moe = {k: MoELayer(
            c["moe_intermediate_size"], n_routed=self.n_routed, top_k=self.top_k,
            first_expert=c.get("first_expert", 0), experts_held=self.experts_held,
            n_shared=_published(c, "n_shared"),
            aux_alpha=c.get("aux_loss_alpha", 0.0),
            routed_scale=c.get("routed_scaling_factor", 1.0),
            norm_topk=_published(c, "norm_topk", False),
            scoring=_published(c, "scoring", "softmax"), init_std=self.std,
            name=f"l{k}")
            for k in range(self.dense_layers, self.num_layers)}

    def resized(self, **overrides) -> "LatentMoEDecoder":
        """The same model with some configuration keys changed."""
        return type(self)({**self.config, **overrides}, self.name)

    # -- params --
    def init(self, key: jax.Array, input_shape=None) -> Tuple[Dict, Dict]:
        del input_shape
        e = self.hidden
        ke, kh, *kl = jax.random.split(key, 2 + self.num_layers)
        layers, states = [], []
        for k in range(self.num_layers):
            ka, kf = jax.random.split(kl[k])
            attn, _ = self.attn[k].init(ka, (0, e))
            if k in self.moe:
                ffn, state = self.moe[k].init(kf, (0, e))
            else:
                ffn, state = init_gated_mlp(
                    kf, e, self.config["intermediate_size"], self.std), {}
            layers.append({"attn_norm": {"w": init.ones((e,))}, "attn": attn,
                           "ffn_norm": {"w": init.ones((e,))}, "ffn": ffn})
            states.append(state)
        params = {"embed": init.normal(ke, (self.vocab, e), self.std),
                  "layers": layers, "final_norm": {"w": init.ones((e,))},
                  "head": init.normal(kh, (e, self.vocab), self.std)}
        return params, {"layers": states}

    # -- forward --
    def _block(self, k: int, training: bool):
        mixer = self.attn[k]

        def mix(p, uncast, x):
            with jax.named_scope(mixer.name):
                h = rms_norm(x, p["attn_norm"]["w"], self.eps)
            a, _ = mixer.apply({**p["attn"], **uncast}, {}, h, training=training)
            return x + a

        def feed_forward(p, state, x):
            if k in self.moe:
                with jax.named_scope(f"l{k}.router"):
                    h = rms_norm(x, p["ffn_norm"]["w"], self.eps)
                f, state = self.moe[k].apply(p["ffn"], state, h, training=training)
            else:
                with jax.named_scope(f"l{k}.mlp"):
                    f = gated_mlp(p["ffn"], rms_norm(x, p["ffn_norm"]["w"], self.eps))
            return x + f, state

        def uncast(p):
            return {n: p["attn"][n] for n in getattr(mixer, "FLOAT32", ())}

        def block(p, state, x):
            keep = uncast(p)
            p = cast_to_compute(p)
            return feed_forward(p, state, mix(p, keep, x))

        def kda_block(p, state, x):
            """The mixer a sequence at a time, each a checkpoint of its own,
            then the feed-forward half as one: the block keeps its input and
            the mixer's result, and the backward pass holds one sequence's
            temporaries of the mixer (the chunked rule's are 2.75 GB for 4 x
            4096 tokens compiled for a v5e) or the feed-forward half's."""
            half = lambda *names: {n: p[n] for n in names}  # noqa: E731
            one = jax.checkpoint(lambda p, keep, x: mix(cast_to_compute(p), keep, x[None])[0])
            x = jax.lax.map(lambda x: one(half("attn_norm", "attn"), uncast(p), x), x)
            return jax.checkpoint(lambda p, s, x: feed_forward(cast_to_compute(p), s, x))(
                half("ffn_norm", "ffn"), state, x)

        if not training:
            return block
        if k in self.kda_layers:
            return kda_block
        return jax.checkpoint(block, policy=jax.checkpoint_policies
                              .save_only_these_names(FLASH_OUT, FLASH_LSE))

    def apply(self, params, state, x, *, training: bool = False,
              rng: Optional[jax.Array] = None):
        """``x (B, S)`` int32 ids -> float32 logits ``(B, S, V)``."""
        del rng
        with jax.named_scope("embed"):
            h = jnp.take(cast_to_compute(params["embed"]), x, axis=0)
        states = []
        for k in range(self.num_layers):
            h, s = self._block(k, training)(params["layers"][k],
                                            state["layers"][k], h)
            states.append(s)
        with jax.named_scope("head"):
            h = rms_norm(h, cast_to_compute(params["final_norm"]["w"]), self.eps)
            logits = jnp.matmul(h, cast_to_compute(params["head"]),
                                preferred_element_type=jnp.float32)
        return logits, {"layers": states}

    def publish_state(self, state):
        """The expert layers' routing counts to the registry's ``moe_*``
        counters, and the state with them back at zero (``Trainer`` asks
        after each epoch's fence)."""
        return publish_routing(state)

    # -- metadata --
    def param_count(self, input_shape=None) -> int:
        del input_shape
        e, c = self.hidden, self.config
        per = 2 * e
        total = 2 * self.vocab * e + e
        for k in range(self.num_layers):
            total += per + self.attn[k].param_count((0, e))
            total += (self.moe[k].param_count((0, e)) if k in self.moe
                      else 3 * e * c["intermediate_size"])
        return total

    def get_config(self) -> Dict[str, Any]:
        return {"type": "latent_moe_decoder", "name": self.name,
                "config": self.config}

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "LatentMoEDecoder":
        return cls(cfg["config"], cfg.get("name", "latent_moe_decoder"))

    def summary(self, input_shape=None) -> str:
        mixers = (f", {len(self.kda_layers)} of them KDA and "
                  f"{self.num_layers - len(self.kda_layers)} MLA" if self.kda_layers else "")
        return (f"{self.name}: {self.num_layers} layers ({self.dense_layers} dense, "
                f"{len(self.moe)} with {self.experts_held} of {self.n_routed} experts, "
                f"top {self.top_k}{mixers}), hidden {self.hidden}, "
                f"vocabulary {self.vocab}, {self.param_count():,} parameters")

    def __repr__(self) -> str:
        return f"LatentMoEDecoder({self.summary()})"


def create_deepseek_v2_lite_ep8(data_format: str = "NCHW") -> LatentMoEDecoder:
    """Zoo factory; ``data_format`` is accepted for the zoo's signature and
    ignored (token input)."""
    del data_format
    return LatentMoEDecoder(DEEPSEEK_V2_LITE_EP8, name="deepseek_v2_lite_ep8")


def create_kimi_linear_48b_ep32(data_format: str = "NCHW") -> LatentMoEDecoder:
    """Zoo factory of the second family; ``data_format`` as above."""
    del data_format
    return LatentMoEDecoder(KIMI_LINEAR_48B_EP32, name="kimi_linear_48b_ep32")
