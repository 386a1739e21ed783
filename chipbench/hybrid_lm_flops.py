"""Operations, bytes and parameters of a hybrid decoder's share as a cell
runs it (Kimi Delta Attention layers beside latent attention, a leading dense
MLP, then expert layers), from the configuration's published keys
(``linear_attn_config``, ``num_experts`` ...); ``lm_flops.py`` counts the
family whose every layer is latent attention.

Counted as the model needs them: a multiply-add is two operations, causal
scores are counted exactly (position t attends t + 1 keys), a routed expert
counts ``num_experts_per_token * held / published`` times a token (an even
spread), recomputation is not counted. Training is three times the forward.

**The chunked gated delta rule, as counted here** (``dcnn_tpu/ops/delta_rule.py``
computes this algorithm): a head's sequence in chunks of ``C`` positions, keys
and values ``D`` wide. A chunk takes, in multiply-adds: the two decayed
products ``A`` and ``B``, their lower triangles, ``C^2 / 2 * D`` each; the
inverse of the unit lower triangular system, ``C^3 / 6``; that inverse (lower
triangular) times ``[K | V]``, ``C^2 / 2 * 2 D``; the state read by ``W`` and
by ``Q``, ``2 C D^2``; ``B U``, ``C^2 / 2 * D``; the state's update,
``C D^2``. The backward pass counts twice the forward. The bytes are the
operands and the result once each, and their cotangents once each: q, k, v
and o in the compute dtype, the log decay and beta in float32.
"""

from __future__ import annotations

from typing import Dict, Tuple

from lm_flops import scoped_seconds  # noqa: F401  (the readers take it from here)


def kda_layers(cfg: dict) -> int:
    """KDA layers among the layers held (the published list counts from 1)."""
    return sum(1 for k in cfg["linear_attn_config"]["kda_layers"]
               if k <= cfg["num_hidden_layers"])


def _kda_matrices(cfg: dict) -> int:
    e, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    c = h * d
    return 4 * e * c + 2 * (e * d + d * c) + e * h


def _kda_params(cfg: dict) -> int:
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    c = h * d
    return _kda_matrices(cfg) + 3 * lin["short_conv_kernel_size"] * c + 2 * c + h + d


def _mla_matrices(cfg: dict) -> int:
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    return e * h * (nope + rope) + e * (rank + rope) + rank * h * (nope + dv) + h * dv * e


def _mlp_params(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def param_count(cfg: dict) -> int:
    """Parameters held here: the held experts, the vocabulary slice."""
    e, dense, layers = cfg["hidden_size"], cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    kda = kda_layers(cfg)
    mixers = kda * _kda_params(cfg) + (layers - kda) * (_mla_matrices(cfg) + cfg["kv_lora_rank"])
    experts = (e * cfg["num_experts_published"]
               + _mlp_params(cfg, cfg["moe_intermediate_size"])
               * (cfg["num_experts"] + cfg["num_shared_experts"]))
    return (mixers + layers * 2 * e + dense * _mlp_params(cfg, cfg["intermediate_size"])
            + (layers - dense) * experts + 2 * cfg["vocab_size"] * e + e)


def kda_chunk_flops_per_token(cfg: dict) -> float:
    """Forward operations of the chunked rule alone, one token, all heads of
    one layer (module docstring)."""
    lin = cfg["linear_attn_config"]
    c, d = cfg.get("kda_chunk_size", 64), lin["head_dim"]
    a_chunk = (2 * c * c / 2 * d            # A and B
               + c ** 3 / 6                 # the triangular inverse
               + c * c / 2 * 2 * d          # the inverse times [K | V]
               + 2 * c * d * d              # W S and Q S
               + c * c / 2 * d              # B U
               + c * d * d)                 # the state's update
    return 2.0 * a_chunk / c * lin["num_heads"]


def forward_flops_per_token(cfg: dict) -> Dict[str, float]:
    """By part, for one token of a ``seq_len`` causal sequence (the mean over
    its positions): ``kda_proj`` and ``kda_chunk`` (a KDA layer's products and
    its chunked rule), ``mla_proj`` and ``scores`` (an MLA layer's), ``dense_mlp``,
    ``shared``, ``routed``, ``router`` (an expert layer's), ``head``, and
    ``total`` over the cut model."""
    e, h, s = cfg["hidden_size"], cfg["num_attention_heads"], cfg["seq_len"]
    qk, dv = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    width = cfg["moe_intermediate_size"]
    parts = {
        "kda_proj": 2.0 * _kda_matrices(cfg),
        "kda_chunk": kda_chunk_flops_per_token(cfg),
        "mla_proj": 2.0 * _mla_matrices(cfg),
        "scores": 2.0 * h * (qk + dv) * (s + 1) / 2,
        "dense_mlp": 2.0 * _mlp_params(cfg, cfg["intermediate_size"]),
        "shared": 2.0 * _mlp_params(cfg, cfg["num_shared_experts"] * width),
        "routed": (cfg["num_experts_per_token"] * cfg["num_experts"]
                   / cfg["num_experts_published"]) * 2.0 * _mlp_params(cfg, width),
        "router": 2.0 * e * cfg["num_experts_published"],
        "head": 2.0 * e * cfg["vocab_size"],
    }
    layers, dense, kda = (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
                          kda_layers(cfg))
    parts["total"] = (kda * (parts["kda_proj"] + parts["kda_chunk"])
                      + (layers - kda) * (parts["mla_proj"] + parts["scores"])
                      + dense * parts["dense_mlp"]
                      + (layers - dense) * (parts["shared"] + parts["routed"] + parts["router"])
                      + parts["head"])
    return parts


def train_flops_per_sequence(cfg: dict) -> float:
    return 3.0 * forward_flops_per_token(cfg)["total"] * cfg["seq_len"]


def kda_chunk_min_seconds(cfg: dict, batch: int, peaks: Dict[str, float]) -> Tuple[float, str]:
    """The least time of one KDA layer's chunked rule in one training step,
    forward and backward: the larger of its operations (three times the
    forward's) over the bf16 peak and its bytes over the memory's rate: q, k,
    v, o (2 bytes an element), the log decay (4) and beta (4 a head) read or
    written once forward; read again, with o's cotangent, and their five
    cotangents written, backward."""
    lin = cfg["linear_attn_config"]
    tokens, h, d = batch * cfg["seq_len"], lin["num_heads"], lin["head_dim"]
    flops = 3.0 * kda_chunk_flops_per_token(cfg) * tokens
    inputs = 3 * d * 2 + d * 4 + 4                 # q, k, v, g, beta of a token and head
    moved = float(tokens * h * ((inputs + d * 2) + (inputs + d * 2) + inputs))
    t_f, t_b = flops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"]
    return max(t_f, t_b), "flops" if t_f >= t_b else "bytes"
