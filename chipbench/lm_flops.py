"""Operations, bytes and parameters of a decoder language model's share as a
cell runs it, from the configuration's published keys; and the part of a
reduced trace that lies under the program's scopes ``l<k>.<what>``.

Counted as the model needs them: a multiply-add is two operations, causal
scores are counted exactly (position t attends t + 1 keys), a routed expert
counts ``num_experts_per_tok * held / published`` times a token (an even
spread over the experts; the traced steps' own pairs where a reader has
them), recomputation is not counted. Training is three times the forward.
"""

from __future__ import annotations

import re
from typing import Dict, Tuple


def _attention_params(cfg: dict) -> int:
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    return (e * h * (nope + rope) + e * (rank + rope) + rank
            + rank * h * (nope + dv) + h * dv * e)


def _mlp_params(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def param_count(cfg: dict) -> int:
    """Parameters held here: the held experts, the vocabulary slice."""
    e, dense = cfg["hidden_size"], cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    width = cfg["moe_intermediate_size"]
    layer = _attention_params(cfg) + 2 * e
    expert_layer = (layer + e * cfg["n_routed_experts_published"]
                    + _mlp_params(cfg, width) * (cfg["n_routed_experts"]
                                                 + cfg["n_shared_experts"]))
    return (dense * (layer + _mlp_params(cfg, cfg["intermediate_size"]))
            + moe * expert_layer + 2 * cfg["vocab_size"] * e + e)


def forward_flops_per_token(cfg: dict) -> Dict[str, float]:
    """By part, for one token of a ``seq_len`` causal sequence (the mean
    over its positions): ``dense_layer``, ``expert_layer`` (of which
    ``scores`` and ``routed``), ``head``, and ``total`` over the cut model."""
    e, h, s = cfg["hidden_size"], cfg["num_attention_heads"], cfg["seq_len"]
    qk, dv = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    width = cfg["moe_intermediate_size"]
    proj = 2.0 * (_attention_params(cfg) - cfg["kv_lora_rank"])
    scores = 2.0 * h * (qk + dv) * (s + 1) / 2
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
              / cfg["n_routed_experts_published"]) * 2.0 * _mlp_params(cfg, width)
    shared = 2.0 * _mlp_params(cfg, cfg["n_shared_experts"] * width)
    router = 2.0 * e * cfg["n_routed_experts_published"]
    dense_layer = proj + scores + 2.0 * _mlp_params(cfg, cfg["intermediate_size"])
    expert_layer = proj + scores + router + shared + routed
    head = 2.0 * e * cfg["vocab_size"]
    dense = cfg["first_k_dense_replace"]
    return {"dense_layer": dense_layer, "expert_layer": expert_layer,
            "scores": scores, "routed": routed, "head": head,
            "total": dense * dense_layer
            + (cfg["num_hidden_layers"] - dense) * expert_layer + head}


def train_flops_per_sequence(cfg: dict) -> float:
    return 3.0 * forward_flops_per_token(cfg)["total"] * cfg["seq_len"]


def flash_min_seconds(cfg: dict, batch: int, peaks: Dict[str, float]) -> Tuple[float, str]:
    """The least time of one layer's flash kernels in one training step: the
    forward's two products (q k^T at nope + rope, p v at the value dim) and the
    backward's five (the scores again, dO v^T, dV, dQ, dK), over the causal
    half; q, k, v, o, dO read and o, dq, dk, dv written once each."""
    h, s = cfg["num_attention_heads"], cfg["seq_len"]
    qk, dv = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    half = batch * h * s * (s + 1) / 2
    flops = 2.0 * half * ((qk + dv) + (3 * qk + 2 * dv))
    rows = batch * h * s
    moved = 2.0 * rows * ((2 * qk + 2 * dv) + (2 * qk + 3 * dv) + (2 * qk + dv))
    t_f, t_b = flops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"]
    return max(t_f, t_b), "flops" if t_f >= t_b else "bytes"


def expert_min_seconds(cfg: dict, pairs: float, layer_steps: float,
                       peaks: Dict[str, float]) -> Tuple[float, str]:
    """The least time of the held experts' products for ``pairs`` (token,
    expert) pairs over ``layer_steps`` expert layers x training steps: three
    products a pair, three times over for training; each step reads a
    layer's expert weights once a pass and moves each pair's rows once."""
    e, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 3.0 * 3 * 2.0 * pairs * e * width
    weights = 3 * cfg["n_routed_experts"] * e * width * 2.0
    moved = 3.0 * (layer_steps * weights + pairs * 2.0 * (2 * e + 3 * width))
    t_f, t_b = flops / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"]
    return max(t_f, t_b), "flops" if t_f >= t_b else "bytes"


# ------------------------------------------------------------------ traces

def scoped_seconds(reduced: dict, what: str) -> Tuple[float, float]:
    """(seconds under the scopes ``l<k>.<what>`` for ``what`` a regular
    expression, busy seconds), each the mean over the trace's devices; the
    names are ``trace_reduce.stable_name``'s."""
    devices = (reduced or {}).get("devices") or {}
    if not devices:
        return 0.0, 0.0
    pattern = re.compile(r"^l\d+\.(?:" + what + r")/")
    mine = sum(s for d in devices.values() for name, s in d["ops"].items()
               if pattern.match(name))
    busy = sum(d["busy_s"] for d in devices.values())
    return mine / len(devices), busy / len(devices)
