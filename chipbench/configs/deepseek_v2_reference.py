"""Plain reference of one expert-parallel rank's share of a DeepSeek-V2
decoder (arXiv:2405.04434; ``modeling_deepseek.py`` of the published model):
float32 ``jax.numpy``, no import from the program, attention scores
materialised (a head at a time), experts as a plain loop with masks, the loss
and its gradients, AdamW. The caller sets
``jax.default_matmul_precision("highest")``.

``cfg`` is the configuration file's dict, under the published key names.

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h));  logits = RMSNorm(y) W_head

- Latent attention (``q_lora_rank`` null): ``q = x W_q`` -> heads x (nope | rope);
  ``[c | k_pe] = x W_kva``; ``c = RMSNorm(c)``; ``[k_nope | v] = c W_kvb`` per
  head; ``k_pe`` one head shared by all. Rotary on ``q_pe``, ``k_pe`` over
  the pairs (2i, 2i+1) with YaRN frequencies; scores
  ``softmax(causal(q k^T (nope + rope)^-1/2 mscale_all_dim^2))``.
- Expert layer: ``s = softmax(x W_g)`` over all ``n_routed_experts_published``;
  the ``num_experts_per_tok`` largest, weights unnormalised times
  ``routed_scaling_factor``; ``y = sum_{e in top and held} s_e FFN_e(x) +
  FFN_shared(x)`` for the held experts ``first_expert .. first_expert +
  n_routed_experts``. What the absent experts would add is left out.
- Sequence-wise balance loss: ``f_e = E / (K S) #{t: e in top(t)}``,
  ``P_e = mean_t s_te``, ``L_aux = alpha mean_seq sum_e f_e P_e``; its gradient
  is added, its value is not part of the reported loss.
- Loss: mean cross-entropy of the next token over the vocabulary slice.

Departures from the published training recipe (the configuration file lists
them too): no gradient clipping; a constant learning rate.

The step is computed a sequence at a time with each block recomputed in the
backward pass, so that it fits one chip beside its optimizer state.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp


# ------------------------------------------------------------------ weights

def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def _mlp_init(key, hidden, width, std):
    kg, ku, kd = jax.random.split(key, 3)
    return {"gate": _normal(kg, (hidden, width), std),
            "up": _normal(ku, (hidden, width), std),
            "down": _normal(kd, (width, hidden), std)}


def init(cfg: dict, key):
    """(params, state): every matrix ``N(0, initializer_std^2)``, every norm
    weight 1. The state is empty: the program's holds routing counts."""
    e, std, heads = cfg["hidden_size"], cfg["initializer_std"], cfg["num_attention_heads"]
    nope, rope, dv, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    width, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    ke, kh, *kl = jax.random.split(key, 2 + cfg["num_hidden_layers"])
    layers = []
    for k in range(cfg["num_hidden_layers"]):
        kq, ka, kb, ko, kf, kr, ks = jax.random.split(kl[k], 7)
        attn = {"wq": _normal(kq, (e, heads * (nope + rope)), std),
                "wkva": _normal(ka, (e, rank + rope), std),
                "kv_norm": jnp.ones((rank,), jnp.float32),
                "wkvb": _normal(kb, (rank, heads * (nope + dv)), std),
                "wo": _normal(ko, (heads * dv, e), std)}
        if k < cfg["first_k_dense_replace"]:
            ffn = _mlp_init(kf, e, cfg["intermediate_size"], std)
        else:
            kg, ku, kd = jax.random.split(kf, 3)
            ffn = {"router": _normal(kr, (e, cfg["n_routed_experts_published"]), std),
                   "experts": {"gate": _normal(kg, (held, e, width), std),
                               "up": _normal(ku, (held, e, width), std),
                               "down": _normal(kd, (held, width, e), std)},
                   "shared": _mlp_init(ks, e, cfg["n_shared_experts"] * width, std)}
        layers.append({"attn_norm": {"w": jnp.ones((e,), jnp.float32)}, "attn": attn,
                       "ffn_norm": {"w": jnp.ones((e,), jnp.float32)}, "ffn": ffn})
    params = {"embed": _normal(ke, (cfg["vocab_size"], e), std), "layers": layers,
              "final_norm": {"w": jnp.ones((e,), jnp.float32)},
              "head": _normal(kh, (e, cfg["vocab_size"]), std)}
    return params, {}


# ------------------------------------------------------------------ forward

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict):
    dim, theta, rs = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def pair(turns):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (turns * 2 * math.pi)) / (2 * math.log(theta)))
    low = max(math.floor(pair(rs["beta_fast"])), 0)
    high = min(math.ceil(pair(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0, 1)
    m = 1.0 - ramp
    return extra / rs["factor"] * (1 - m) + extra * m


def _rotate(x, cos, sin):
    """x [..., S, dim]: the pairs (2i, 2i+1) turned by the position's angle."""
    p = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = p[..., 0], p[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _attention(cfg, p, x, q8):
    """x [S, E] -> [S, E]."""
    s = x.shape[0]
    heads, nope, rope, dv, rank = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                                   cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                                   cfg["kv_lora_rank"])
    rs = cfg["rope_scaling"]
    all_dim = _mscale(rs["factor"], rs["mscale_all_dim"])
    table = _mscale(rs["factor"], rs["mscale"]) / all_dim
    scale = (nope + rope) ** -0.5 * all_dim ** 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)[None, :]
    cos, sin = jnp.cos(ang) * table, jnp.sin(ang) * table

    q = (q8(x) @ q8(p["wq"])).reshape(s, heads, nope + rope).transpose(1, 0, 2)
    kva = q8(x) @ q8(p["wkva"])
    c = _rms(kva[:, :rank], p["kv_norm"], cfg["rms_norm_eps"])
    kv = (q8(c) @ q8(p["wkvb"])).reshape(s, heads, nope + dv).transpose(1, 0, 2)
    k_pe = _rotate(kva[:, rank:], cos, sin)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], cos, sin)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[None], (heads, s, rope))],
                        axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = jnp.where(causal, (q8(qh) @ q8(kh).T) * scale, -jnp.inf)
        return q8(jax.nn.softmax(scores, axis=-1)) @ q8(vh)

    o = jax.lax.map(one_head, (q, k, v))                       # [heads, S, dv]
    return q8(o.transpose(1, 0, 2).reshape(s, heads * dv)) @ q8(p["wo"])


def _mlp(p, x, q8):
    return q8(jax.nn.silu(q8(x) @ q8(p["gate"])) * (q8(x) @ q8(p["up"]))) @ q8(p["down"])


def _experts(cfg, p, x, q8, first: Optional[int] = None, held: Optional[int] = None):
    """x [S, E] -> (the held experts' part + the shared experts, L_aux of
    this sequence without alpha). ``first``/``held`` default to the
    configuration's share; the weights of the held experts are ``p``'s."""
    n, k = cfg["n_routed_experts_published"], cfg["num_experts_per_tok"]
    first = cfg["first_expert"] if first is None else first
    held = cfg["n_routed_experts"] if held is None else held
    s = jax.nn.softmax(q8(x) @ q8(p["router"]), axis=-1)
    top_w, top_e = jax.lax.top_k(s, k)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg["routed_scaling_factor"]
    def add_expert(y, expert):
        """One held expert over every token, weighted by the router where
        the token chose it and by nought elsewhere (``lax.scan``: the loop's
        body is compiled once)."""
        j, one = expert
        w_j = jnp.sum(jnp.where(top_e == first + j, top_w, 0.0), axis=-1)
        return y + w_j[:, None] * _mlp(one, x, q8), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(held), p["experts"]))
    chosen = jnp.sum(jax.nn.one_hot(top_e, n, dtype=jnp.float32), axis=(0, 1))
    f = chosen * (n / (k * x.shape[0]))
    aux = jnp.sum(jax.lax.stop_gradient(f) * jnp.mean(s, axis=0))
    return y + _mlp(p["shared"], x, q8), aux


def _block(cfg, k, p, x, q8):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, p["attn"], _rms(x, p["attn_norm"]["w"], eps), q8)
    h = _rms(x, p["ffn_norm"]["w"], eps)
    if k < cfg["first_k_dense_replace"]:
        return x + _mlp(p["ffn"], h, q8), jnp.zeros((), jnp.float32)
    f, aux = _experts(cfg, p["ffn"], h, q8)
    return x + f, aux


def forward(cfg, params, tokens, quantize: Optional[Callable] = None):
    """One sequence ``tokens [S]`` -> (logits [S, V], sum of the layers'
    L_aux without alpha)."""
    q8 = quantize or (lambda a: a)
    x = params["embed"][tokens]
    aux = jnp.zeros((), jnp.float32)
    for k in range(cfg["num_hidden_layers"]):
        block = jax.checkpoint(lambda p, x, k=k: _block(cfg, k, p, x, q8))
        x, a = block(params["layers"][k], x)
        aux = aux + a
    x = _rms(x, params["final_norm"]["w"], cfg["rms_norm_eps"])
    return q8(x) @ q8(params["head"]), aux


def sequence_loss(cfg, params, tokens, labels, quantize=None):
    """(mean cross-entropy of one sequence, its L_aux with alpha)."""
    logits, aux = forward(cfg, params, tokens, quantize)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked),
            cfg["aux_loss_alpha"] * aux)


def loss_and_grads(cfg, params, state, x, y, quantize=None, rows=None):
    """x, y ``[B, S]`` ids and next ids. The reported loss is the mean
    cross-entropy; the gradient is of that plus the balance loss. ``rows`` (a
    slice) keeps only those sequences: a planted fault of the benchmark."""
    if rows is not None:
        x, y = x[rows], y[rows]

    def objective(p):
        def one(carry, xy):
            ce, aux = sequence_loss(cfg, p, xy[0], xy[1], quantize)
            return (carry[0] + ce, carry[1] + aux), None
        (ce, aux), _ = jax.lax.scan(one, (jnp.zeros(()), jnp.zeros(())), (x, y))
        return (ce + aux) / x.shape[0], ce / x.shape[0]

    (_, loss), grads = jax.value_and_grad(objective, has_aux=True)(params)
    return loss, grads, state


# ------------------------------------------------------------------ optimizer

def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree_util.tree_map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adam_update(opt: dict, params, grads, opt_state, lr):
    """AdamW: the decay is applied to the weights, not added to the update."""
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["epsilon"], opt["weight_decay"]
    t = opt_state["t"] + 1
    tf = t.astype(jnp.float32)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, opt_state["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, opt_state["v"], grads)

    def one(p, m, v):
        step = lr * (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf)) + eps)
        return p - wd * lr * p - step

    return jax.tree_util.tree_map(one, params, m, v), {"m": m, "v": v, "t": t}


def train_step(cfg, params, state, opt_state, x, y, lr, quantize=None, rows=None):
    loss, grads, state = loss_and_grads(cfg, params, state, x, y, quantize, rows)
    params, opt_state = adam_update(cfg["optimizer"], params, grads, opt_state, lr)
    return params, state, opt_state, loss, grads


# ------------------------------------------------------------------ control

def quantizer(name: Optional[str]) -> Optional[Callable]:
    """The operand rounding of a control precision, put on both operands of
    every matrix product. ``fp8_e4m3``: scaled by the operand's largest
    magnitude to the format's range, rounded to float8 e4m3 and scaled back;
    the gradient passes straight through. ``bf16``: rounded to bfloat16."""
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
