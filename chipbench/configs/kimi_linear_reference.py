"""Plain reference of one expert-parallel rank's share of a Kimi Linear
decoder (arXiv:2510.26692, "Kimi Linear: An Expressive, Efficient Attention
Architecture"; ``model_type`` ``kimi_linear``): float32 ``jax.numpy``, no
import from the program, Kimi Delta Attention as the recurrence it is defined
by (position by position), attention scores materialised (a head at a time),
experts as a plain loop with masks, the loss and its gradients, AdamW. The
caller sets ``jax.default_matmul_precision("highest")``.

``cfg`` is the configuration file's dict, under the published key names.

    h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h));  logits = RMSNorm(y) W_head

- The mixer of 1-based layer ``l`` is KDA where ``l`` is in
  ``linear_attn_config.kda_layers`` and latent attention otherwise.
- KDA, one head (``Conv`` a depthwise causal convolution over the last
  ``short_conv_kernel_size`` positions of each channel, no bias; ``L2Norm``
  over the head's channels, ``x / sqrt(sum x^2 + 1e-6)``):
  ``q_t = L2Norm(SiLU(Conv(W_q x)_t)) / sqrt(head_dim)``,
  ``k_t = L2Norm(SiLU(Conv(W_k x)_t))``, ``v_t = SiLU(Conv(W_v x)_t)``;
  ``g_t = -exp(A_log[h]) softplus(W_f2 (W_f1 x_t) + dt_bias)``, ``alpha_t = exp(g_t)``;
  ``beta_t = sigmoid(w_b x_t)``;
  ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T`` from
  ``S_0 = 0``; ``o_t = S_t^T q_t``;
  ``y_t = W_o (RMSNorm(o_t) w_norm sigmoid(W_g2 (W_g1 x_t) + b_g))``.
- Latent attention without positions (``mla_use_nope``, ``q_lora_rank`` null):
  ``q = x W_q`` -> heads x (nope + rope); ``[c | k_pe] = x W_kva``;
  ``c = RMSNorm(c)``; ``[k_nope | v] = c W_kvb`` per head; ``k_pe`` one head
  shared by all, nothing rotated; scores
  ``softmax(causal(q k^T (nope + rope)^-1/2))``.
- Expert layer: ``s = sigmoid(x W_r)`` over all ``num_experts_published``; the
  ``num_experts_per_token`` largest of ``s + b`` (``b`` the selection bias,
  zero here, outside the gradient); weights ``s_i / (sum of the chosen s +
  1e-20) * routed_scaling_factor``; ``y = sum_{e chosen and held} w_e FFN_e(x)
  + FFN_shared(x)`` for the held experts ``first_expert .. first_expert +
  num_experts``. What the absent experts would add is left out. No balance
  loss.
- Loss: mean cross-entropy of the next token over the vocabulary slice.

Departures from the published training recipe (the configuration file lists
them too): AdamW where the paper trains with Muon; no gradient clipping; a
constant learning rate; the selection bias stays at zero.

The step is computed a sequence at a time with each block recomputed in the
backward pass and KDA's recurrence recomputed by stretches of ``STRETCH``
positions (the 4096 states of a sequence are 8.6 GB otherwise), so that it
fits one chip beside its optimizer state.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

STRETCH = 64          # positions of the recurrence between two kept states
L2_EPS = 1e-6


# ------------------------------------------------------------------ weights

def _normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def _mlp_init(key, hidden, width, std):
    kg, ku, kd = jax.random.split(key, 3)
    return {"gate": _normal(kg, (hidden, width), std),
            "up": _normal(ku, (hidden, width), std),
            "down": _normal(kd, (width, hidden), std)}


def is_kda(cfg: dict, k: int) -> bool:
    """Whether 0-based layer ``k`` mixes by KDA (the published lists count from 1)."""
    return k + 1 in cfg["linear_attn_config"]["kda_layers"]


def _kda_init(cfg, key):
    e, std, lin = cfg["hidden_size"], cfg["initializer_std"], cfg["linear_attn_config"]
    h, d, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    c = h * d
    keys = jax.random.split(key, 14)
    bound = 1.0 / math.sqrt(taps)
    dt = jnp.exp(jax.random.uniform(keys[9], (c,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    conv = lambda k: jax.random.uniform(k, (taps, c), jnp.float32, -bound, bound)  # noqa: E731
    return {"wq": _normal(keys[0], (e, c), std), "wk": _normal(keys[1], (e, c), std),
            "wv": _normal(keys[2], (e, c), std),
            "conv_q": conv(keys[3]), "conv_k": conv(keys[4]), "conv_v": conv(keys[5]),
            "f_a": _normal(keys[6], (e, d), std), "f_b": _normal(keys[7], (d, c), std),
            "A_log": jnp.log(jax.random.uniform(keys[8], (h,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "wb": _normal(keys[10], (e, h), std),
            "g_a": _normal(keys[11], (e, d), std), "g_b": _normal(keys[12], (d, c), std),
            "g_bias": jnp.zeros((c,), jnp.float32), "o_norm": jnp.ones((d,), jnp.float32),
            "wo": _normal(keys[13], (c, e), std)}


def _mla_init(cfg, key):
    e, std, heads = cfg["hidden_size"], cfg["initializer_std"], cfg["num_attention_heads"]
    nope, rope, dv, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {"wq": _normal(kq, (e, heads * (nope + rope)), std),
            "wkva": _normal(ka, (e, rank + rope), std),
            "kv_norm": jnp.ones((rank,), jnp.float32),
            "wkvb": _normal(kb, (rank, heads * (nope + dv)), std),
            "wo": _normal(ko, (heads * dv, e), std)}


def init(cfg: dict, key):
    """(params, state): every matrix ``N(0, initializer_std^2)``, every norm
    weight 1, the convolutions' taps ``U(-1/sqrt(K), 1/sqrt(K))``,
    ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a step drawn
    log-uniformly in [0.001, 0.1], ``b_g`` zero. The state is empty: the
    program's holds routing counts and the selection bias (zero)."""
    e, std = cfg["hidden_size"], cfg["initializer_std"]
    width, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    ke, kh, *kl = jax.random.split(key, 2 + cfg["num_hidden_layers"])
    layers = []
    for k in range(cfg["num_hidden_layers"]):
        km, kf, kr, ks = jax.random.split(kl[k], 4)
        mixer = _kda_init(cfg, km) if is_kda(cfg, k) else _mla_init(cfg, km)
        if k < cfg["first_k_dense_replace"]:
            ffn = _mlp_init(kf, e, cfg["intermediate_size"], std)
        else:
            kg, ku, kd = jax.random.split(kf, 3)
            ffn = {"router": _normal(kr, (e, cfg["num_experts_published"]), std),
                   "experts": {"gate": _normal(kg, (held, e, width), std),
                               "up": _normal(ku, (held, e, width), std),
                               "down": _normal(kd, (held, width, e), std)},
                   "shared": _mlp_init(ks, e, cfg["num_shared_experts"] * width, std)}
        layers.append({"attn_norm": {"w": jnp.ones((e,), jnp.float32)}, "attn": mixer,
                       "ffn_norm": {"w": jnp.ones((e,), jnp.float32)}, "ffn": ffn})
    params = {"embed": _normal(ke, (cfg["vocab_size"], e), std), "layers": layers,
              "final_norm": {"w": jnp.ones((e,), jnp.float32)},
              "head": _normal(kh, (e, cfg["vocab_size"]), std)}
    return params, {}


# ------------------------------------------------------------------ forward

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _conv(x, taps):
    """x [S, C], taps [K, C]: ``y_t = sum_j taps[j] x_{t - (K - 1) + j}``."""
    size = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((size - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + x.shape[0]] * taps[j] for j in range(size))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta, q8=lambda a: a, stretch: int = STRETCH):
    """The recurrence over positions for all heads of one sequence: ``q, k, g``
    ``[S, H, Dk]``, ``v [S, H, Dv]``, ``beta [S, H]`` -> ``o [S, H, Dv]``."""
    s, h, dk = q.shape
    dv = v.shape[-1]

    def position(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[:, :, None] * state                       # Diag(alpha) S
        seen = jnp.einsum("hk,hkv->hv", q8(k_t), q8(state))            # what k reads back
        write = b_t[:, None] * (v_t - seen)
        state = state + jnp.einsum("hk,hv->hkv", q8(k_t), q8(write))
        return state, jnp.einsum("hk,hkv->hv", q8(q_t), q8(state))

    @jax.checkpoint
    def some(state, at):
        return jax.lax.scan(position, state, at)

    steps = -(-s // stretch)
    pad = steps * stretch - s            # positions that leave the state as it is

    def stretches(a):
        a = jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)])
        return a.reshape(steps, stretch, *a.shape[1:])
    first = jnp.zeros((h, dk, dv), jnp.float32)
    _, o = jax.lax.scan(some, first, tuple(stretches(a) for a in (q, k, v, g, beta)))
    return o.reshape(steps * stretch, h, dv)[:s]


def _kda(cfg, p, x, q8):
    """x [S, E] -> [S, E]."""
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    s = x.shape[0]
    heads = lambda a: a.reshape(s, h, d)  # noqa: E731
    q, k, v = (heads(jax.nn.silu(_conv(q8(x) @ q8(p["w" + n]), p["conv_" + n]))) for n in "qkv")
    q, k = _l2(q) / math.sqrt(d), _l2(k)
    rate = q8(q8(x) @ q8(p["f_a"])) @ q8(p["f_b"]) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[None, :, None] * heads(jax.nn.softplus(rate))
    beta = jax.nn.sigmoid(q8(x) @ q8(p["wb"]))
    o = delta_rule(q, k, v, g, beta, q8)
    gate = jax.nn.sigmoid(q8(q8(x) @ q8(p["g_a"])) @ q8(p["g_b"]) + p["g_bias"])
    o = _rms(o, p["o_norm"], cfg["rms_norm_eps"]).reshape(s, h * d) * gate
    return q8(o) @ q8(p["wo"])


def _attention(cfg, p, x, q8):
    """Latent attention without positions: x [S, E] -> [S, E]."""
    s = x.shape[0]
    heads, nope, rope, dv, rank = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                                   cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                                   cfg["kv_lora_rank"])
    scale = (nope + rope) ** -0.5
    q = (q8(x) @ q8(p["wq"])).reshape(s, heads, nope + rope).transpose(1, 0, 2)
    kva = q8(x) @ q8(p["wkva"])
    c = _rms(kva[:, :rank], p["kv_norm"], cfg["rms_norm_eps"])
    kv = (q8(c) @ q8(p["wkvb"])).reshape(s, heads, nope + dv).transpose(1, 0, 2)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(kva[None, :, rank:], (heads, s, rope))], axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint          # 32 heads' scores of 4096 x 4096 are 2 GB if kept
    def one_head(qkv):
        qh, kh, vh = qkv
        scores = jnp.where(causal, (q8(qh) @ q8(kh).T) * scale, -jnp.inf)
        return q8(jax.nn.softmax(scores, axis=-1)) @ q8(vh)

    o = jax.lax.map(one_head, (q, k, v))                       # [heads, S, dv]
    return q8(o.transpose(1, 0, 2).reshape(s, heads * dv)) @ q8(p["wo"])


def _mlp(p, x, q8):
    return q8(jax.nn.silu(q8(x) @ q8(p["gate"])) * (q8(x) @ q8(p["up"]))) @ q8(p["down"])


def route(cfg, router, x, q8, bias=None):
    """``(weights [S, K], experts [S, K])``: sigmoid scores, the largest of
    score + bias chosen, the chosen scores renormalised and scaled."""
    s = jax.nn.sigmoid(q8(x) @ q8(router))
    bias = jnp.zeros(s.shape[-1]) if bias is None else jax.lax.stop_gradient(bias)
    _, top_e = jax.lax.top_k(s + bias, cfg["num_experts_per_token"])
    top_w = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg["moe_renormalize"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_w * cfg["routed_scaling_factor"], top_e


def _experts(cfg, p, x, q8, first: Optional[int] = None, held: Optional[int] = None,
             bias=None):
    """x [S, E] -> the held experts' part + the shared expert. ``first`` /
    ``held`` default to the configuration's share; the weights of the held
    experts are ``p``'s."""
    first = cfg["first_expert"] if first is None else first
    held = cfg["num_experts"] if held is None else held
    top_w, top_e = route(cfg, p["router"], x, q8, bias)

    def add_expert(y, expert):
        """One held expert over every token, weighted by the router where
        the token chose it and by nought elsewhere (``lax.scan``: the loop's
        body is compiled once)."""
        j, one = expert
        w_j = jnp.sum(jnp.where(top_e == first + j, top_w, 0.0), axis=-1)
        return y + w_j[:, None] * _mlp(one, x, q8), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (jnp.arange(held), p["experts"]))
    return y + _mlp(p["shared"], x, q8)


def _block(cfg, k, p, x, q8):
    eps = cfg["rms_norm_eps"]
    mixer = _kda if is_kda(cfg, k) else _attention
    x = x + mixer(cfg, p["attn"], _rms(x, p["attn_norm"]["w"], eps), q8)
    h = _rms(x, p["ffn_norm"]["w"], eps)
    if k < cfg["first_k_dense_replace"]:
        return x + _mlp(p["ffn"], h, q8)
    return x + _experts(cfg, p["ffn"], h, q8)


def forward(cfg, params, tokens, quantize: Optional[Callable] = None):
    """One sequence ``tokens [S]`` -> logits [S, V]."""
    q8 = quantize or (lambda a: a)
    x = params["embed"][tokens]
    for k in range(cfg["num_hidden_layers"]):
        block = jax.checkpoint(lambda p, x, k=k: _block(cfg, k, p, x, q8))
        x = block(params["layers"][k], x)
    x = _rms(x, params["final_norm"]["w"], cfg["rms_norm_eps"])
    return q8(x) @ q8(params["head"])


def sequence_loss(cfg, params, tokens, labels, quantize=None):
    """Mean cross-entropy of one sequence."""
    logits = forward(cfg, params, tokens, quantize)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss_and_grads(cfg, params, state, x, y, quantize=None, rows=None):
    """x, y ``[B, S]`` ids and next ids: the mean cross-entropy and its
    gradient. ``rows`` (a slice) keeps only those sequences: a planted fault
    of the benchmark."""
    if rows is not None:
        x, y = x[rows], y[rows]

    def objective(p):
        def one(total, xy):
            return total + sequence_loss(cfg, p, xy[0], xy[1], quantize), None
        return jax.lax.scan(one, jnp.zeros(()), (x, y))[0] / x.shape[0]

    loss, grads = jax.value_and_grad(objective)(params)
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
    every matrix product (the recurrence's reads and writes of the state
    among them). ``fp8_e4m3``: scaled by the operand's largest magnitude to
    the format's range, rounded to float8 e4m3 and scaled back; the gradient
    passes straight through. ``bf16``: rounded to bfloat16."""
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
