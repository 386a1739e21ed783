"""The language-model path at tiny widths on the CPU: the layers against the
plain reference (``chipbench/configs/deepseek_v2_reference.py``, imported by
path: it imports nothing of the program), the flash kernels with a value
head dim of their own, the expert layer's share of a deployment, the token
job on the trainer's resident path, and the benchmark's counts.

Hidden 64, 4 heads of 16 | 8 | 16, latent 32, 16 experts top-3 with 4 held,
vocabulary 128, sequences of 32.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcnn_tpu.core.precision import get_precision_mode, set_precision
from dcnn_tpu.data import TokenDataset
from dcnn_tpu.models import create_model
from dcnn_tpu.nn.latent_attention import LatentAttentionLayer
from dcnn_tpu.nn.moe import MoELayer, publish_routing
from dcnn_tpu.nn.transformer import (apply_rotary, rms_norm, rotary_inv_freq,
                                     rotary_tables, yarn_correction_range,
                                     yarn_mscale)
from dcnn_tpu.obs import get_registry
from dcnn_tpu.ops.attention import attention, flash_attention
from dcnn_tpu.ops.grouped import grouped_matmul
from dcnn_tpu.ops.losses import get_loss, get_loss_grad, token_cross_entropy
from dcnn_tpu.optim import AdamW
from dcnn_tpu.train.trainer import (Trainer, TrainState, create_train_state,
                                    evaluate_classification)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")


def _by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(*parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _by_path("dsv2_reference", BENCH, "configs", "deepseek_v2_reference.py")
lm_flops = _by_path("lm_flops", BENCH, "lm_flops.py")

TINY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=24,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=3,
            n_routed_experts=4, n_routed_experts_published=16,
            num_experts_per_tok=3, vocab_size=128, initializer_std=0.1,
            aux_loss_alpha=0.01)
OPT = {"type": "adamw", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
       "epsilon": 1e-8, "weight_decay": 0.1}


@pytest.fixture(autouse=True)
def parity():
    before = get_precision_mode()
    set_precision("parity")
    yield
    set_precision(before)


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "deepseek_v2_lite_ep8.json")) as f:
        return json.load(f)


def tiny_model(**more):
    return create_model("deepseek_v2_lite_ep8").resized(**{**TINY, **more})


def tiny_cfg(model):
    return dict(model.config, optimizer=OPT, seq_len=32)


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) <= tol * max(np.linalg.norm(b), 1e-30)


# ------------------------------------------------------------------ parts

def test_rms_norm_is_the_plain_formula(rng):
    x = jnp.asarray(rng.normal(size=(3, 5, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=64), jnp.float32)
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6) * w
    assert close(rms_norm(x, w, 1e-6), want, 1e-6)


def test_yarn_frequencies_at_the_published_keys(published):
    rs = published["rope_scaling"]
    # the pair that turns 32 times over 4096 positions: 64 ln(4096 / 64 pi) /
    # (2 ln 10000) = 10.47; once: 22.51
    assert yarn_correction_range(32, 1, 64, 10000, 4096) == (10, 23)
    f = np.asarray(rotary_inv_freq(64, published["rope_theta"], rs))
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert np.allclose(f[:11], extra[:11], rtol=1e-6)           # fast pairs: as they are
    assert np.allclose(f[23:], extra[23:] / 40, rtol=1e-6)      # slow pairs: stretched by 40
    keep = 1 - (16 - 10) / 13                                   # pair 16, inside the ramp
    assert np.isclose(f[16], extra[16] / 40 * (1 - keep) + extra[16] * keep, rtol=1e-6)
    assert np.allclose(f, np.asarray(ref.yarn_inv_freq(
        dict(published, qk_rope_head_dim=64))), rtol=1e-6)
    assert np.isclose(yarn_mscale(40, 0.707), 1.26080, atol=1e-5)
    layer = create_model("deepseek_v2_lite_ep8").attn[0]
    assert np.isclose(layer.softmax_scale, 192 ** -0.5 * 1.58962, rtol=1e-5)
    assert layer.table_scale == 1.0


def test_rotary_turns_pairs_and_keeps_relative_positions(rng):
    cos, sin = rotary_tables(12, rotary_inv_freq(8, 10000.0))
    q = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    rq, rk = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
    assert close(jnp.linalg.norm(rq, axis=-1), jnp.linalg.norm(q, axis=-1), 1e-5)
    # the same content at positions (5, 2) and (9, 6) scores the same
    same_q, same_k = jnp.tile(q[:1], (12, 1)), jnp.tile(k[:1], (12, 1))
    sq, sk = apply_rotary(same_q, cos, sin), apply_rotary(same_k, cos, sin)
    assert np.isclose(float(sq[5] @ sk[2]), float(sq[9] @ sk[6]), rtol=1e-4)


@pytest.mark.parametrize("sizes", [[5, 0, 9, 2], [0, 0, 0, 16], [3, 3, 3, 3]])
def test_grouped_matmul_is_a_loop_over_groups(rng, sizes):
    x = jnp.asarray(rng.normal(size=(20, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6, 5)), jnp.float32)
    got = np.asarray(grouped_matmul(x, w, jnp.asarray(sizes)))
    at = 0
    for g, n in enumerate(sizes):
        assert close(got[at:at + n], np.asarray(x[at:at + n]) @ np.asarray(w[g]), 1e-5) or n == 0
        at += n
    assert not got[at:].any()            # rows past the last group: zeros


# ------------------------------------------------------------------ attention

@pytest.mark.parametrize("shape", [(2, 3, 64, 24, 16), (1, 2, 96, 16, 32), (1, 2, 80, 16, 8)])
def test_flash_kernels_with_a_value_dim_of_their_own(rng, shape):
    """Interpret mode, forward and the two backward kernels, against the
    materialising oracle; blocks smaller than the sequence, one shape ragged."""
    b, h, s, d, dv = shape
    q, k = (jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(b, h, s, dv)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(b, h, s, dv)), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=0.3, block_q=32,
                               block_kv=32, interpret=True)

    def oracle(q, k, v):
        return attention(q, k, v, causal=True, scale=0.3)

    out, pull = jax.vjp(flash, q, k, v)
    want, pull_want = jax.vjp(oracle, q, k, v)
    assert out.shape == (b, h, s, dv) and close(out, want, 1e-5)
    for got, exp in zip(pull(g), pull_want(g)):
        assert got.shape == exp.shape and close(got, exp, 1e-4)


def test_blockwise_fallback_takes_a_value_dim_of_its_own(rng):
    q, k = (jnp.asarray(rng.normal(size=(1, 2, 40, 24)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 2, 40, 8)), jnp.float32)
    assert close(flash_attention(q, k, v, causal=True),        # CPU: blockwise
                 attention(q, k, v, causal=True), 1e-5)


def _attention_pair(rng):
    model = tiny_model()
    cfg = tiny_cfg(model)
    p = ref.init(cfg, jax.random.PRNGKey(3))[0]["layers"][0]["attn"]
    layer = LatentAttentionLayer(4, 16, 8, 32, 16, rope_scaling=cfg["rope_scaling"],
                                 name="l0.attn")
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    return cfg, p, layer, x


def test_latent_attention_against_the_reference(rng):
    cfg, p, layer, x = _attention_pair(rng)
    assert jax.tree_util.tree_map(jnp.shape, layer.init(jax.random.PRNGKey(0), (32, 64))[0]) \
        == jax.tree_util.tree_map(jnp.shape, p)
    g = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

    def program(p, x):
        return layer.apply(p, {}, x)[0]

    def reference(p, x):
        return jax.vmap(lambda one: ref._attention(cfg, p, one, lambda a: a))(x)

    both = lambda f: jax.jit(lambda p, x: (f(p, x), jax.vjp(f, p, x)[1](g)))  # noqa: E731
    (out, (gp, gx)), (want, (wp, wx)) = both(program)(p, x), both(reference)(p, x)
    assert close(out, want)
    assert close(gx, wx)
    for name in p:
        assert close(gp[name], wp[name]), name


# ------------------------------------------------------------------ experts

def _moe(first=0, held=4, alpha=0.01):
    return MoELayer(24, n_routed=16, top_k=3, first_expert=first, experts_held=held,
                    n_shared=2, aux_alpha=alpha, init_std=0.1, name="l1")


def _uncut_weights(rng):
    """All 16 experts, the router and the shared experts of one layer."""
    n = lambda *s: jnp.asarray(0.1 * rng.normal(size=s), jnp.float32)  # noqa: E731
    return {"router": n(64, 16),
            "experts": {"gate": n(16, 64, 24), "up": n(16, 64, 24), "down": n(16, 24, 64)},
            "shared": {"gate": n(64, 48), "up": n(64, 48), "down": n(48, 64)}}


def _share(w, first, held):
    return {**w, "experts": {m: a[first:first + held] for m, a in w["experts"].items()}}


def test_the_shares_add_up_to_the_whole_layer(rng):
    """The four shares' routed parts, with the shared experts counted once,
    are what the uncut reference gives for the whole layer."""
    cfg = tiny_cfg(tiny_model())
    w = _uncut_weights(rng)
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    ident = lambda a: a  # noqa: E731
    whole = jax.vmap(lambda one: ref._experts(cfg, w, one, ident, 0, 16)[0])(x)
    shared = jax.vmap(lambda one: ref._mlp(w["shared"], one, ident))(x)
    total = shared
    for first in (0, 4, 8, 12):
        layer = _moe(first)
        y, state = layer.apply(_share(w, first, 4), layer.init(jax.random.PRNGKey(0), (32, 64))[1],
                               x, training=True)
        part = jax.vmap(lambda one, first=first: ref._experts(
            cfg, _share(w, first, 4), one, ident, first, 4)[0])(x)
        assert close(y, part)                      # a share is the reference's share
        total = total + (y - shared)
        assert int(state["pairs_routed"]) == 2 * 32 * 3
    assert close(total, whole)


def test_dropless_when_every_token_picks_one_held_expert(rng):
    """A router that sends every token to expert 5 first: the share that
    holds it computes all 64 pairs, whatever the imbalance."""
    w = _uncut_weights(rng)
    w["router"] = w["router"].at[:, 5].set(0.0) * 0.01
    x = jnp.abs(jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32))
    w["router"] = w["router"].at[:, 5].set(1.0)
    layer = _moe(4)
    _, state0 = layer.init(jax.random.PRNGKey(0), (32, 64))
    y, state = layer.apply(_share(w, 4, 4), state0, x, training=True)
    assert int(state["load_max"]) == 64 and int(state["pairs_held"]) >= 64
    cfg = tiny_cfg(tiny_model())
    want = jax.vmap(lambda one: ref._experts(cfg, _share(w, 4, 4), one,
                                             lambda a: a, 4, 4)[0])(x)
    assert close(y, want)
    top_w, top_e, _ = layer.route(w["router"], x)
    assert bool(jnp.all(top_e[..., 0] == 5))


def test_balance_loss_moves_the_router_and_not_the_loss(rng):
    w = _share(_uncut_weights(rng), 0, 4)
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    g = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

    def run(alpha):
        layer = _moe(0, alpha=alpha)
        state = layer.init(jax.random.PRNGKey(0), (32, 64))[1]
        return jax.vjp(lambda w: layer.apply(w, state, x, training=True)[0], w)

    (y0, pull0), (y1, pull1) = run(0.0), run(0.01)
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    (g0,), (g1,) = pull0(g), pull1(g)
    assert not close(g1["router"], g0["router"], 1e-6)
    for name in ("gate", "up", "down"):
        assert close(g1["experts"][name], g0["experts"][name], 1e-6)
    # and it is the reference's balance loss that was added
    cfg = dict(tiny_cfg(tiny_model()), aux_loss_alpha=0.01)
    aux = lambda r: 0.01 * jnp.mean(jax.vmap(  # noqa: E731
        lambda one: ref._experts(cfg, {**w, "router": r}, one, lambda a: a, 0, 4)[1])(x))
    (alone,) = pull1(jnp.zeros_like(g))     # no cotangent at all: the balance loss's own
    assert close(alone["router"], jax.grad(aux)(w["router"]), 1e-4)
    assert not np.asarray(pull0(jnp.zeros_like(g))[0]["router"]).any()


def test_routing_counts_are_published_and_cleared():
    reg = get_registry()
    held0 = reg.snapshot().get("moe_pairs_held_total", 0)
    state = ({"running_mean": jnp.ones(3)},
             {"layers": [{}, {"pairs_routed": jnp.asarray(96), "pairs_held": jnp.asarray(20),
                              "load_max": jnp.asarray(9), "pair_rows": jnp.asarray(48),
                              "fallbacks": jnp.asarray(1)}]})
    rows0 = reg.snapshot().get("moe_pair_rows_total", 0)
    fell0 = reg.snapshot().get("moe_capacity_fallbacks_total", 0)
    cleared = publish_routing(state)
    snap = reg.snapshot()
    assert snap["moe_pairs_held_total"] - held0 == 20 and snap["moe_expert_load_max"] == 9
    assert snap["moe_pair_rows_total"] - rows0 == 48
    assert snap["moe_capacity_fallbacks_total"] - fell0 == 1
    assert int(cleared[1]["layers"][1]["pairs_held"]) == 0
    assert cleared[0]["running_mean"] is state[0]["running_mean"]
    bn_only = ({"running_mean": jnp.ones(3)},)
    assert publish_routing(bn_only) is bn_only


def _crafted_layer(first, held, load, rng, n_routed=16, tokens=128, k=3):
    """A layer whose router's *choice* is planted (``load`` pairs on the held
    experts, the rest on absent ones) while the weights stay the router's own
    softmax scores, so that gradients still reach the router."""
    layer = MoELayer(24, n_routed=n_routed, top_k=k, first_expert=first, experts_held=held,
                     init_std=0.1, name="l1")
    mine = rng.integers(first, first + held, size=tokens * k)
    others = np.setdiff1d(np.arange(n_routed), np.arange(first, first + held))
    away = others[rng.integers(0, len(others), size=tokens * k)] if len(others) else mine
    planted = np.where(rng.permutation(tokens * k) < load, mine, away).reshape(1, tokens, k)
    top_e = jnp.asarray(planted, jnp.int32)

    def route(router_w, x):
        s = jax.nn.softmax(jnp.matmul(x, router_w), axis=-1)
        return jnp.take_along_axis(s, top_e, axis=-1), top_e, jnp.zeros((), jnp.float32)
    layer.route = route
    return layer


def _layer_and_gradients(layer, w, x, g):
    state = layer.init(jax.random.PRNGKey(0), (x.shape[1], 64))[1]
    y, pull, after = jax.vjp(lambda w, x: layer.apply(w, state, x, training=True),
                             w, x, has_aux=True)
    return y, pull(g), after


# 128 tokens x top 3 = 384 pairs; 3 of 16 experts held: an even share of 72
# pairs, so rounds of 144 rows, three of them at the most (432 >= 384)
ROUND_CASES = {
    "none_held": (0, 3, 0, 0), "one_pair": (0, 3, 1, 144), "exactly_a_round": (0, 3, 144, 144),
    "a_round_and_1": (0, 3, 145, 288), "two_rounds_full": (0, 3, 288, 288),
    "past_the_second": (0, 3, 289, 432), "every_pair": (0, 3, 384, 432),
    "first_expert_5": (5, 3, 100, 144), "first_expert_13_two_rounds": (13, 3, 200, 288),
    "all_experts_held": (0, 16, 384, 384)}


@pytest.mark.parametrize("case", list(ROUND_CASES))
def test_any_number_of_rounds_is_the_one_round_of_every_pair(rng, monkeypatch, case):
    """Output and gradients (x, the three expert weights, the router) of the
    rounds a load takes against one round of ``T * k`` rows on the same
    inputs, and the counts the step leaves."""
    import dcnn_tpu.nn.moe as moe

    first, held, load, rows = ROUND_CASES[case]
    assert moe.round_rows(128, 3, held, 16) == (144 if held == 3 else 384)
    layer = _crafted_layer(first, held, load, rng)
    w = _share({k: v for k, v in _uncut_weights(rng).items() if k != "shared"}, first, held)
    x = jnp.asarray(rng.normal(size=(1, 128, 64)), jnp.float32)
    g = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    y, (gw, gx), after = _layer_and_gradients(layer, w, x, g)
    assert (int(after["pairs_held"]), int(after["pair_rows"])) == (load, rows)
    assert int(after["fallbacks"]) == (rows == 432)      # every round taken: the worst case
    monkeypatch.setattr(moe, "round_rows", lambda t, k, *_: t * k)
    want, (ww, wx), whole = _layer_and_gradients(layer, w, x, g)
    assert int(whole["pair_rows"]) == (384 if load else 0) and int(whole["fallbacks"]) == 0
    assert close(y, want, 1e-5) or (load == 0 and not np.asarray(y).any())
    assert close(gx, wx, 1e-5)
    assert close(gw["router"], ww["router"], 1e-5)
    for name in ("gate", "up", "down"):
        assert close(gw["experts"][name], ww["experts"][name], 1e-5), name
    if load:
        assert np.asarray(gw["router"]).any() and np.asarray(gw["experts"]["down"]).any()


def test_two_rounds_through_the_pallas_interpreter(rng, monkeypatch):
    """Two rounds with the TPU's grouped product (interpret mode), whose
    rows past the last group come out as it finds them."""
    import dcnn_tpu.nn.moe as moe

    layer = _crafted_layer(5, 3, 200, rng)
    w = _share({k: v for k, v in _uncut_weights(rng).items() if k != "shared"}, 5, 3)
    x = jnp.asarray(rng.normal(size=(1, 128, 64)), jnp.float32)
    g = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    want = _layer_and_gradients(layer, w, x, g)
    monkeypatch.setattr(moe, "grouped_matmul", lambda *a: grouped_matmul(*a, interpret=True))
    got = _layer_and_gradients(layer, w, x, g)
    assert int(got[2]["pair_rows"]) == 288
    for a, b in zip(jax.tree_util.tree_leaves(got[:2]), jax.tree_util.tree_leaves(want[:2])):
        assert np.isfinite(np.asarray(a)).all() and close(a, b, 1e-5)


def test_a_rounds_rows_come_from_the_shapes_alone():
    from dcnn_tpu.nn.moe import round_rows
    from dcnn_tpu.ops.grouped import padded_rows

    assert round_rows(16384, 6, 8, 64) == 24576          # the cell's layer: 4 rounds at the most
    assert round_rows(64, 3, 4, 16) == 96                # its rehearsal
    assert round_rows(16384, 6, 64, 64) == 98304         # every expert held: one round
    assert round_rows(16384, 6, 32, 64) == 98304         # twice the share is all the pairs
    assert round_rows(1000, 6, 8, 64) == 1536            # 1500 in tiles of 64
    for rows in (1, 8, 96, 1500, 12300, 24600, 98304):
        padded = padded_rows(rows)
        assert padded >= rows and padded % 8 == 0 and padded - rows <= max(8, rows // 16)


# ------------------------------------------------------------------ the model

def test_model_against_the_reference_loss_and_gradients():
    model = tiny_model()
    cfg = tiny_cfg(model)
    params, _ = ref.init(cfg, jax.random.PRNGKey(0))
    want_p, state = jax.eval_shape(model.init, jax.random.PRNGKey(1))
    assert jax.tree_util.tree_map(lambda a: a.shape, want_p) \
        == jax.tree_util.tree_map(lambda a: a.shape, params)
    state = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), state)
    rows = jax.random.randint(jax.random.PRNGKey(2), (4, 33), 0, 128)
    x, y = rows[:, :-1], rows[:, 1:]
    loss_r, grads_r, _ = jax.jit(
        lambda p: ref.loss_and_grads(cfg, p, {}, x, y))(params)

    def f(p):
        return token_cross_entropy(model.apply(p, state, x, training=True)[0], y)

    loss_p, grads_p = jax.jit(jax.value_and_grad(f))(params)
    assert np.isclose(float(loss_p), float(loss_r), rtol=1e-5)
    flat_r, _ = jax.tree_util.tree_flatten_with_path(grads_r)
    for (path, a), b in zip(flat_r, jax.tree_util.tree_leaves(grads_p)):
        assert close(b, a), jax.tree_util.keystr(path)
    # evaluation: same logits, no routing counts, no balance gradient
    logits, same = jax.jit(model.apply)(params, state, x)
    assert close(logits, jax.jit(lambda p: model.apply(p, state, x, training=True)[0])(params),
                 1e-6)
    assert int(same["layers"][1]["pairs_held"]) == 0


def test_zoo_model_is_the_published_cut(published):
    model = create_model("deepseek_v2_lite_ep8")
    assert model.param_count() == 535_060_992
    for key, value in model.config.items():
        assert published[key] == value, key
    assert published["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert published["experts_held"] == published["n_routed_experts"] == 8
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))[0]
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == 535_060_992


def test_lm_flops_is_the_hand_arithmetic(published):
    assert lm_flops.param_count(published) == 535_060_992
    f = lm_flops.forward_flops_per_token(published)
    assert round(f["total"] / 1e6) == 621
    assert round(f["dense_layer"] / 1e6) == 183 and round(f["expert_layer"] / 1e6, 1) == 96.3
    assert round(f["scores"] / 1e6) == 21 and round(f["routed"] / 1e6) == 13
    assert round(f["head"] / 1e6, 1) == 52.4
    assert round(4 * lm_flops.train_flops_per_sequence(published) / 1e12, 1) == 30.5
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = lm_flops.flash_min_seconds(published, 4, peaks)
    # 7 products of the causal half: 2 x 4 x 16 x 4096 x 4097 / 2 x (320 + 832)
    assert bound == "flops" and np.isclose(t, 2 * 4 * 16 * 4096 * 4097 / 2 * 1152 / 197e12)
    t, bound = lm_flops.expert_min_seconds(published, 4 * 12288, 4, peaks)
    assert bound == "flops" and np.isclose(t, 18 * 4 * 12288 * 2048 * 1408 / 197e12)


def test_scoped_seconds_reads_stable_names():
    reduced = {"devices": {"/device:TPU:0": {"busy_s": 2.0, "ops": {
        "l1.experts/ragged_dot_general": 0.2, "l1.experts/mul_bwd": 0.1,
        "l12.router/dot": 0.05, "l1.shared/dot": 0.4, "l1.attn.flash/pallas_call": 0.3,
        "l1.attn/dot": 0.25, "optim/add": 0.1}}}}
    assert np.isclose(lm_flops.scoped_seconds(reduced, "experts")[0], 0.3)
    assert np.isclose(lm_flops.scoped_seconds(reduced, r"attn\.flash")[0], 0.3)
    mine, busy = lm_flops.scoped_seconds(reduced, "router|dispatch|experts|combine")
    assert np.isclose(mine, 0.35) and busy == 2.0
    assert lm_flops.scoped_seconds({}, "experts") == (0.0, 0.0)


# ------------------------------------------------------------------ the token job

def test_token_cross_entropy_is_the_one_hot_loss(rng):
    logits = jnp.asarray(rng.normal(size=(3, 7, 11)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 11, size=(3, 7)))
    want = get_loss("softmax_crossentropy")(logits.reshape(21, 11),
                                            jax.nn.one_hot(labels.reshape(21), 11))
    assert np.isclose(float(get_loss("token_crossentropy")(logits, labels)), float(want),
                      rtol=1e-6)
    assert close(get_loss_grad("token_crossentropy")(logits, labels),
                 jax.grad(token_cross_entropy)(logits, labels), 1e-6)


@pytest.mark.parametrize("bad, why", [
    (np.zeros((4, 1), np.int32), "S \\+ 1"), (np.zeros((4, 9), np.float32), "integers"),
    (np.zeros(9, np.int32), "S \\+ 1")])
def test_token_dataset_refuses(bad, why):
    with pytest.raises(ValueError, match=why):
        TokenDataset(bad, 16, batch_size=2)


def test_token_dataset_is_staged_like_an_image_split():
    reg = get_registry()
    before = reg.snapshot().get("data_stage_bytes_total", 0)
    ds = TokenDataset(np.arange(8 * 33).reshape(8, 33) % 128, 128, batch_size=2)
    assert reg.snapshot()["data_stage_bytes_total"] - before == 8 * 33 * 4
    assert ds.x_staged.dtype == jnp.int32 and ds.y is None
    assert (ds.steps_per_epoch, ds.seq_len, ds.hbm_bytes) == (4, 32, 8 * 33 * 4)
    with pytest.raises(ValueError, match="batch_size"):
        TokenDataset(np.zeros((2, 9), np.int32), 16, batch_size=4)


def _lm_trainer():
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    return _by_path("lm_trainer_example", ROOT, "examples", "lm_trainer.py")


def test_zipf_tokens_follow_the_law_and_the_seed():
    lm = _lm_trainer()
    a = lm.zipf_tokens(3_000_000_007, 64, 4097, 128)
    assert a.shape == (64, 4097) and a.dtype == np.int32 and 0 <= a.min() and a.max() < 128
    assert np.array_equal(a, lm.zipf_tokens(3_000_000_007, 64, 4097, 128))
    assert not np.array_equal(a, lm.zipf_tokens(3_000_000_008, 64, 4097, 128))
    # the counts fall off as 1 / (id + 1), in every sequence alike
    counts = np.bincount(a.ravel(), minlength=128).astype(float)
    assert 1.8 < counts[0] / counts[1] < 2.2 and 7 < counts[0] / counts[7] < 9
    assert all(np.bincount(row).argmax() == 0 for row in a)


def test_train_epoch_on_a_token_dataset_equals_the_reference():
    """Three steps through ``Trainer.train_epoch`` -> ``_train_epoch_resident``
    against the reference fed the same batches by the feed's stated recipe."""
    from dcnn_tpu.core.config import TrainingConfig

    model = tiny_model()
    cfg = tiny_cfg(model)
    tokens = _lm_trainer().zipf_tokens(11, 6, 33, 128)
    ds = TokenDataset(tokens, 128, batch_size=2)
    opt = AdamW(OPT["learning_rate"], beta2=0.95, weight_decay=0.1)
    trainer = Trainer(model, opt, "token_crossentropy",
                      TrainingConfig(batch_size=2, learning_rate=OPT["learning_rate"]))
    params0, _ = ref.init(cfg, jax.random.PRNGKey(5))
    ts = create_train_state(model, opt, jax.random.PRNGKey(0))
    ts = TrainState(jax.tree_util.tree_map(jnp.array, params0), ts.state,
                    opt.init(params0), ts.step)
    snap0 = get_registry().snapshot()
    held0, rows0 = (snap0.get(k, 0) for k in ("moe_pairs_held_total", "moe_pair_rows_total"))
    rng, epoch = jax.random.PRNGKey(7), 1
    ts, loss, _ = trainer.train_epoch(ts, ds, rng, epoch)
    assert int(ts.step) == 3
    snap = get_registry().snapshot()
    assert snap["moe_pairs_held_total"] > held0
    # 3 steps x 2 expert layers, each in rounds of 96 rows, two at the most
    assert snap["moe_pair_rows_total"] - rows0 >= snap["moe_pairs_held_total"] - held0
    assert (snap["moe_pair_rows_total"] - rows0) % 96 == 0
    assert snap["moe_pair_rows_total"] - rows0 <= 6 * 192
    assert int(ts.state["layers"][1]["pairs_held"]) == 0          # published, cleared

    kperm, _ = jax.random.split(jax.random.fold_in(rng, epoch))
    idx = np.asarray(jax.random.permutation(jax.random.fold_in(kperm, 0), 6)).reshape(3, 2)
    p, o, losses = params0, ref.adam_init(params0), []
    step = jax.jit(lambda p, o, x, y: ref.train_step(cfg, p, {}, o, x, y,
                                                     OPT["learning_rate"])[:4])
    for i in range(3):
        rows = jnp.asarray(tokens[idx[i]])
        p, _, o, l = step(p, o, rows[:, :-1], rows[:, 1:])
        losses.append(float(l))
    assert np.isclose(loss, np.mean(losses), rtol=1e-5)
    leaves = jax.tree_util.tree_leaves
    for got, want in zip(leaves(ts.opt_state["m"]), leaves(o["m"])):
        assert close(got, want, 1e-3)
    for got, want, start in zip(leaves(ts.params), leaves(p), leaves(params0)):
        assert close(got - start, want - start, 2e-3)


def test_no_validation_on_tokens_yet():
    model = tiny_model()
    params, state = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="TokenDataset"):
        evaluate_classification(model, params, state, get_loss("token_crossentropy"),
                                TokenDataset(np.zeros((4, 33), np.int32), 128, batch_size=2))


def test_every_epoch_path_asks_the_model_for_its_counts(rng):
    """``Trainer.train_epoch`` hands the state to the model's
    ``publish_state`` after the step loop and the chunked path too, not only
    after a resident epoch; a model without one is left alone."""
    from dcnn_tpu.core.config import TrainingConfig
    from dcnn_tpu.nn import SequentialBuilder

    model = (SequentialBuilder("m").input((2, 4, 4)).flatten("f").dense(3, True, "d").build())
    x = rng.normal(size=(8, 2, 4, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
    asked = []
    model.publish_state = lambda state: (asked.append(state), state)[1]
    opt = AdamW(1e-2)
    for k, batches in ((1, [(x[:4], y[:4]), (x[4:], y[4:])]),
                       (2, [(x.reshape(2, 4, 2, 4, 4), y.reshape(2, 4, 3))])):
        trainer = Trainer(model, opt, "softmax_crossentropy",
                          TrainingConfig(batch_size=4, steps_per_dispatch=k))
        ts = create_train_state(model, opt, jax.random.PRNGKey(0))
        ts, loss, _ = trainer.train_epoch(ts, batches, jax.random.PRNGKey(1), 1)
        assert int(ts.step) == 2 and np.isfinite(loss) and len(asked) == k


def test_an_image_split_still_trains_through_the_same_epoch(rng):
    """A DeviceDataset (labels beside the pixels) takes the decode and
    one-hot branch of the scan body; a model with no ``publish_state`` keeps
    its batch-norm state as the epoch left it."""
    from dcnn_tpu.core.config import TrainingConfig
    from dcnn_tpu.data import DeviceDataset
    from dcnn_tpu.nn import SequentialBuilder

    model = (SequentialBuilder("m").input((2, 4, 4)).conv2d(3, 3, 1, 1, True, "c")
             .batchnorm(name="bn").flatten("f").dense(3, True, "d").build())
    x = rng.integers(0, 255, size=(8, 2, 4, 4)).astype(np.uint8)
    ds = DeviceDataset(x, rng.integers(0, 3, 8), 3, batch_size=4)
    opt = AdamW(1e-2)
    trainer = Trainer(model, opt, "softmax_crossentropy", TrainingConfig(batch_size=4))
    ts = create_train_state(model, opt, jax.random.PRNGKey(0))
    ts, loss, _ = trainer.train_epoch(ts, ds, jax.random.PRNGKey(1), 1)
    assert int(ts.step) == 2 and np.isfinite(loss)
    assert isinstance(ts.state, tuple) and "running_mean" in ts.state[1]


# ------------------------------------------------------------------ the benchmark

def test_the_cells_cpu_rehearsal_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "dsv2lite_train_resident", "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["metrics"] == {} and result["failed"] == 0


def test_benchmark_lists_the_cell_and_its_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = [w for w in spec["workloads"] if w["name"] == "dsv2lite_train_resident"]
    assert len(cell) == 1 and cell[0]["chips"] == 1 and len(cell[0]["why"]) <= 200
    assert (cell[0]["config"], cell[0]["traffic"]) == ("deepseek_v2_lite_ep8", "resident_tokens")
    listed = {m["name"] for m in spec["per_layer"]
              if "dsv2lite_train_resident" in m.get("workloads", [])}
    assert {"lm_train_mfu", "mla_flash_roofline", "expert_gmm_roofline", "moe_device_share",
            "device_idle_share", "peak_hbm_gb", "compile_s"} <= listed
    assert not {"train_mfu", "conv_roofline", "conv_phase_roofline"} & listed
    for name in listed:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_expert_layer_through_the_pallas_interpreter(rng, monkeypatch):
    """The TPU's grouped product (interpret mode) leaves rows past the last
    group as it finds them; the layer's output and gradients must not see
    them, and must equal the fallback's."""
    import dcnn_tpu.nn.moe as moe

    w = _share(_uncut_weights(rng), 4, 4)
    x = jnp.asarray(rng.normal(size=(1, 32, 64)), jnp.float32)
    g = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    layer = _moe(4)
    state = layer.init(jax.random.PRNGKey(0), (32, 64))[1]
    run = lambda: jax.jit(lambda w, x: jax.vjp(  # noqa: E731
        lambda w, x: layer.apply(w, state, x, training=True)[0], w, x)[1](g))(w, x)
    want = run()
    monkeypatch.setattr(moe, "grouped_matmul",
                        lambda *a: grouped_matmul(*a, interpret=True))
    got = run()
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all() and close(a, b)


# ------------------------------------------------------------------ the program's text

@pytest.fixture(scope="module")
def training_step():
    """Loss and gradients of the rehearsal model over 4 x 32 tokens: 384
    pairs a layer, 4 of 16 experts held, rounds of 192 rows."""
    model = tiny_model()
    params, state = model.init(jax.random.PRNGKey(0))
    rows = jax.random.randint(jax.random.PRNGKey(2), (4, 33), 0, 128)

    def loss(p):
        logits, after = model.apply(p, state, rows[:, :-1], training=True)
        return token_cross_entropy(logits, rows[:, 1:]), after
    return jax.value_and_grad(loss, has_aux=True), params


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _shapes(jaxpr):
    return {tuple(v.aval.shape) for eqn in _equations(jaxpr)
            for v in (*eqn.invars, *eqn.outvars) if hasattr(v.aval, "shape")}


def test_no_round_holds_an_array_with_a_row_for_every_pair(training_step):
    """The invariant: in the training step, forward and backward, a round
    holds vectors of ``T * k`` scalars and arrays of ``R`` or ``T`` rows,
    never ``T * k`` rows by a width."""
    from dcnn_tpu.nn.moe import round_rows

    step, params = training_step
    pairs = 128 * 3
    assert round_rows(128, 3, 4, 16) == 192
    rows_for_every_pair = lambda shape: (  # noqa: E731
        len(shape) >= 2 and shape[-1] > 1 and int(np.prod(shape[:-1])) == pairs)
    whole = _shapes(jax.make_jaxpr(step)(params).jaxpr)
    assert any(map(rows_for_every_pair, whole))          # the one-hot that counts the groups
    # the rounds of the routed part are the loops that hold R rows by the width
    bodies = [_shapes(eqn.params["body_jaxpr"].jaxpr)
              for eqn in _equations(jax.make_jaxpr(step)(params).jaxpr)
              if eqn.primitive.name == "while"]
    rounds = [shapes for shapes in bodies if (192, 64) in shapes]
    assert len(rounds) >= 4                              # two expert layers, forward and backward
    for shapes in rounds:
        assert not [s for s in shapes if rows_for_every_pair(s)]


def test_a_trace_still_names_the_routed_parts_scopes(training_step):
    """``chipbench/trace_reduce.stable_name`` over the compiled step's
    operation names: each of the four scopes, forward and backward, and
    nothing named after a loop or a branch."""
    import re

    from jax.experimental.compilation_cache import compilation_cache

    trace_reduce = _by_path("trace_reduce", BENCH, "trace_reduce.py")
    step, params = training_step
    # scope names are not in the persistent cache's key: an entry from before
    # a scope moved would answer with the old names
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(step).lower(params).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    names = {trace_reduce.stable_name(m) for m in re.findall(r'op_name="([^"]+)"', text)}
    for k in (1, 2):
        for scope in ("router", "dispatch", "experts", "combine"):
            mine = {n for n in names if n.startswith(f"l{k}.{scope}/")}
            assert any(n.endswith("_bwd") for n in mine), (k, scope)
            assert any(not n.endswith("_bwd") for n in mine), (k, scope)
    assert not [n for n in names if "branch_" in n]
    assert lm_flops.scoped_seconds(
        {"devices": {"d": {"busy_s": 1.0, "ops": {n: 1.0 for n in names}}}}, "experts")[0] > 0


# ------------------------------------------------------------------ the readers

class _Window:
    traced_images, traced_s, images = 32, 4.0, 256


def _reader(name):
    sys.path.insert(0, BENCH)          # the readers import lm_flops by name
    return _by_path("reader_" + name, BENCH, "layer_metrics", name + ".py")


def _ctx(published, ops, **more):
    reduced = {"devices": {"/device:TPU:0": {"busy_s": 4.0, "ops": ops}}} if ops else {}
    return {"reduced": reduced, "window": _Window(), "cfg": published, "chips": 1,
            "traffic": {}, "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "counters": {}, "log": lambda *a: None, **more}


OPS = {"l1.attn.flash/pallas_call": 0.2, "l1.attn.flash/pallas_call_bwd": 0.5,
       "l2.experts/pallas_call_bwd": 0.3, "l2.experts/mul": 0.1, "l2.router/dot": 0.1,
       "l2.dispatch/gather": 0.2, "l2.combine/gather_bwd": 0.1, "l2.shared/dot": 0.4,
       "l0.mlp/dot_bwd": 0.5, "optim/sub": 0.1}


def test_readers_read_the_programs_scopes(published):
    ctx = _ctx(published, OPS, counters={"moe_pairs_held_by_epoch": [400_000] * 8})
    mfu = _reader("lm_train_mfu").read(ctx)
    assert np.isclose(mfu, 100 * 32 * lm_flops.train_flops_per_sequence(published)
                      / (4.0 * 197e12)) and 0 < mfu < 100
    flash = _reader("mla_flash_roofline").read(ctx)
    one = lm_flops.flash_min_seconds(published, 4, ctx["peaks"])[0]
    assert np.isclose(flash, 100 * 5 * 8 * one / 0.7)
    gmm = _reader("expert_gmm_roofline").read(ctx)
    least = lm_flops.expert_min_seconds(published, 400_000, 4 * 8, ctx["peaks"])[0]
    assert np.isclose(gmm, 100 * least / 0.4)
    assert np.isclose(_reader("moe_device_share").read(ctx), 100 * 0.8 / 4.0)


@pytest.mark.parametrize("name", ["lm_train_mfu", "mla_flash_roofline",
                                  "expert_gmm_roofline", "moe_device_share"])
def test_readers_find_nothing_on_another_program(published, name):
    """A program without the scopes or the counter, a convolutional
    configuration, a CPU rehearsal: nothing to read, and no exception."""
    read = _reader(name).read
    conv = {"layer1_block1/conv": 1.0, "optim/sub": 0.1}
    resnet = {"batch_size": 2048, "layers": []}
    assert read(dict(_ctx(published, OPS), peaks=None)) is None
    assert read(_ctx(resnet, conv)) is None
    if name != "lm_train_mfu":
        assert read(_ctx(published, conv)) is None
        assert read(_ctx(published, {})) is None
    if name == "expert_gmm_roofline":
        assert read(_ctx(published, OPS)) is None        # the scope, but no counter
