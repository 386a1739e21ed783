"""The second language model's path at tiny widths on the CPU, each part
against the plain reference (``chipbench/configs/kimi_linear_reference.py``,
imported by path: it imports nothing of the program): the chunked gated delta
rule against the recurrence position by position, the KDA layer, latent
attention without positions, the sigmoid route, the expert layer's share of a
32-way deployment, the model, the token job on the trainer's resident path,
and the benchmark's counts and readers.

Hidden 64, KDA 2 heads of 16 in chunks of 8, MLA 4 heads of 16 | 8 | 16,
latent 32, 16 experts top-3 with 4 held, vocabulary 128, sequences of 32; one
dense layer and three that follow, the third of the four MLA.
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
from dcnn_tpu.models import MODEL_ZOO, LatentMoEDecoder, create_model
from dcnn_tpu.nn.delta_attention import DeltaAttentionLayer
from dcnn_tpu.nn.latent_attention import LatentAttentionLayer
from dcnn_tpu.nn.moe import MoELayer
from dcnn_tpu.obs import get_registry
from dcnn_tpu.ops.delta_rule import (chunked_gated_delta_rule, decayed_products,
                                     gated_delta_rule_by_token, unit_lower_inverse)
from dcnn_tpu.ops.losses import token_cross_entropy
from dcnn_tpu.optim import AdamW
from dcnn_tpu.train.trainer import Trainer, TrainState, create_train_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
CELL = "kimilin_train_resident"


def _by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(*parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)              # hybrid_lm_flops and the readers import by name
ref = _by_path("kimi_reference", BENCH, "configs", "kimi_linear_reference.py")
flops = _by_path("hybrid_lm_flops", BENCH, "hybrid_lm_flops.py")

TINY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=24,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=4,
            linear_attn_config={"full_attn_layers": [3], "head_dim": 16,
                                "kda_layers": [1, 2, 4], "num_heads": 2,
                                "short_conv_kernel_size": 4},
            kda_chunk_size=8, num_experts=4, num_experts_published=16,
            num_experts_per_token=3, vocab_size=128, initializer_std=0.1)
OPT = {"type": "adamw", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
       "epsilon": 1e-8, "weight_decay": 0.1}
IDENT = lambda a: a  # noqa: E731


@pytest.fixture(autouse=True)
def parity():
    before = get_precision_mode()
    set_precision("parity")
    yield
    set_precision(before)


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "kimi_linear_48b_ep32.json")) as f:
        return json.load(f)


def tiny_model(**more):
    return create_model("kimi_linear_48b_ep32").resized(**{**TINY, **more})


def tiny_cfg(model):
    return dict(model.config, optimizer=OPT, seq_len=32)


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) <= tol * max(np.linalg.norm(b), 1e-30)


# ------------------------------------------------------------------ the rule

def _rule_inputs(rng, s, strength, d=16, dv=20, lead=(2, 3)):
    """``strength`` scales the log decay of a position: 1e-3 keeps nearly
    everything (alpha near 1), 30 forgets nearly everything (alpha near 0,
    where ``exp(-G)`` is far out of float32's range within a chunk)."""
    q, k = (jnp.asarray(rng.normal(size=(*lead, s, d)), jnp.float32) for _ in range(2))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jnp.asarray(rng.normal(size=(*lead, s, dv)), jnp.float32)
    g = -strength * jnp.asarray(rng.uniform(0, 1, size=(*lead, s, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, size=(*lead, s)), jnp.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("strength", [1e-3, 1.0, 30.0])
@pytest.mark.parametrize("chunk", [4, 8, 16, 64])
def test_chunked_rule_is_the_recurrence(rng, strength, chunk):
    """Forward and all five gradients, on 50 positions (several chunks, the
    last one ragged), against the recurrence in the program's ops and against
    the reference's own (which lays a sequence out as [S, H, D])."""
    args = _rule_inputs(rng, 50, strength)
    pull = jnp.asarray(rng.normal(size=args[2].shape), jnp.float32)

    def chunked(*a):
        return chunked_gated_delta_rule(*a, chunk=chunk)

    both = lambda f: jax.jit(lambda *a: (f(*a), jax.vjp(f, *a)[1](pull)))(*args)  # noqa: E731
    (out, grads), (want, grads_want) = both(chunked), both(gated_delta_rule_by_token)
    assert np.isfinite(np.asarray(out)).all()
    assert close(out, want, 2e-5)
    for got, exp in zip(grads, grads_want):
        assert close(got, exp, 1e-4)
    one = tuple(jnp.moveaxis(a[0], 0, 1) for a in args)             # [S, H, ...]
    assert close(jnp.moveaxis(out[0], 0, 1),
                 jax.jit(lambda *a: ref.delta_rule(*a, stretch=16))(*one), 2e-5)


def test_chunked_rule_where_every_key_is_nearly_the_same(rng):
    """Keys within a hundredth of one direction, beta 0.98, hardly any decay:
    the triangular system at its worst (its inverse's intermediate powers at
    their largest)."""
    q, k, v, g, beta = _rule_inputs(rng, 64, 1e-3)
    k = jnp.broadcast_to(k[..., :1, :], k.shape) + 0.01 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jnp.full_like(beta, 0.98)
    got = jax.jit(lambda *a: chunked_gated_delta_rule(*a, chunk=64))(q, k, v, g, beta)
    assert close(got, jax.jit(gated_delta_rule_by_token)(q, k, v, g, beta), 2e-5)


def test_chunked_rule_keeps_the_values_dtype_and_a_float32_state(rng):
    q, k, v, g, beta = _rule_inputs(rng, 32, 1.0)
    half = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
    out = chunked_gated_delta_rule(half(q), half(k), half(v), g, beta, chunk=8)
    assert out.dtype == jnp.bfloat16
    want = gated_delta_rule_by_token(half(q), half(k), half(v), g, beta)
    assert close(out.astype(jnp.float32), want, 2e-2)
    text = str(jax.make_jaxpr(lambda *a: chunked_gated_delta_rule(*a, chunk=8))(
        half(q), half(k), half(v), g, beta))
    assert "f32[2,3,16,20]" in text              # the carried state: Dk x Dv in float32
    with pytest.raises(ValueError, match="power of two"):
        chunked_gated_delta_rule(q, k, v, g, beta, chunk=24)


def test_decayed_products_and_the_triangular_inverse(rng):
    """The two pieces of a chunk on their own: no exponent is positive
    (finite where ``exp(-G)`` is not), no array is narrower than the chunk,
    and the inverse is the inverse."""
    _, k, _, g, _ = _rule_inputs(rng, 16, 40.0, lead=(3,))
    cum = jnp.cumsum(g, axis=-2)
    assert not np.isfinite(np.asarray(jnp.exp(-cum))).all()
    got = decayed_products(k[..., None, :, :], k, cum, jnp.float32)[..., 0, :, :]
    want = jnp.einsum("bid,bjd,bijd->bij", k, k,
                      jnp.exp(jnp.minimum(cum[:, :, None] - cum[:, None, :], 0.0)))
    assert np.isfinite(np.asarray(got)).all()
    assert close(got, jnp.tril(want), 1e-5)
    jaxpr = jax.make_jaxpr(lambda k, c: decayed_products(k[..., None, :, :], k, c, jnp.float32))(
        k, cum)
    widths = {v.aval.shape[-1] for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars
              if int(np.prod(v.aval.shape)) >= k.size}          # the arrays of any size
    assert min(widths) >= 16, widths
    lower = jnp.tril(jnp.asarray(rng.normal(size=(3, 16, 16)), jnp.float32), -1)
    for size in (4, 16):                                        # inside one block of 8; two joined
        inverse = unit_lower_inverse(lower[:, :size, :size])
        assert close(inverse @ (jnp.eye(size) + lower[:, :size, :size]),
                     jnp.broadcast_to(jnp.eye(size), (3, size, size)), 1e-5)


# ------------------------------------------------------------------ mixers

def _both(program, reference, p, x, pull):
    run = lambda f: jax.jit(lambda p, x: (f(p, x), jax.vjp(f, p, x)[1](pull)))  # noqa: E731
    return run(program)(p, x), run(reference)(p, x)


def test_kda_layer_against_the_reference(rng):
    model = tiny_model()
    cfg = tiny_cfg(model)
    p = ref.init(cfg, jax.random.PRNGKey(3))[0]["layers"][0]["attn"]
    layer = model.attn[0]
    assert isinstance(layer, DeltaAttentionLayer) and layer.chunk == 8
    mine = layer.init(jax.random.PRNGKey(0), (32, 64))[0]
    assert jax.tree_util.tree_map(jnp.shape, mine) == jax.tree_util.tree_map(jnp.shape, p)
    assert sum(int(np.prod(a.shape)) for a in mine.values()) == layer.param_count((32, 64))
    # the decay's rate as the family starts it: alpha in (0, 1), a step in [0.001, 0.1]
    step = np.log1p(np.exp(np.asarray(mine["dt_bias"], np.float64)))
    assert 0.000999 < step.min() and step.max() < 0.1001
    assert 0 <= float(mine["A_log"].min()) and float(mine["A_log"].max()) <= np.log(16.0)
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    pull = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    (out, (gp, gx)), (want, (wp, wx)) = _both(
        lambda p, x: layer.apply(p, {}, x)[0],
        lambda p, x: jax.vmap(lambda one: ref._kda(cfg, p, one, IDENT))(x), p, x, pull)
    assert close(out, want) and close(gx, wx)
    for name in p:
        assert close(gp[name], wp[name]), name


def test_latent_attention_without_positions_against_the_reference(rng):
    model = tiny_model()
    cfg = tiny_cfg(model)
    p = ref.init(cfg, jax.random.PRNGKey(3))[0]["layers"][2]["attn"]
    layer = model.attn[2]
    assert isinstance(layer, LatentAttentionLayer) and not layer.rotary
    assert layer.softmax_scale == 24 ** -0.5
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    pull = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    (out, (gp, gx)), (want, (wp, wx)) = _both(
        lambda p, x: layer.apply(p, {}, x)[0],
        lambda p, x: jax.vmap(lambda one: ref._attention(cfg, p, one, IDENT))(x), p, x, pull)
    assert close(out, want) and close(gx, wx)
    for name in p:
        assert close(gp[name], wp[name]), name
    # no position anywhere: the same tokens in another order give the same
    # rows for a last token that sees them all
    turned = x[:, ::-1]
    rotated = LatentAttentionLayer(4, 16, 8, 32, 16, name="r")
    last = lambda layer, x: layer.apply(p, {}, x)[0][:, -1]  # noqa: E731
    swap = x.at[:, :31].set(turned[:, 1:])
    assert close(last(layer, swap), last(layer, x), 1e-5)
    assert not close(last(rotated, swap), last(rotated, x), 1e-2)


# ------------------------------------------------------------------ experts

def _moe(first=0, held=4):
    return MoELayer(24, n_routed=16, top_k=3, first_expert=first, experts_held=held,
                    n_shared=1, routed_scale=2.446, norm_topk=True, scoring="sigmoid",
                    init_std=0.1, name="l1")


def _uncut_weights(rng):
    n = lambda *s: jnp.asarray(0.1 * rng.normal(size=s), jnp.float32)  # noqa: E731
    return {"router": n(64, 16),
            "experts": {"gate": n(16, 64, 24), "up": n(16, 64, 24), "down": n(16, 24, 64)},
            "shared": {"gate": n(64, 24), "up": n(64, 24), "down": n(24, 64)}}


def _share(w, first, held):
    return {**w, "experts": {m: a[first:first + held] for m, a in w["experts"].items()}}


def test_sigmoid_route_weights_and_the_selection_bias(rng):
    cfg = tiny_cfg(tiny_model())
    layer = _moe()
    router = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    none = jnp.zeros(16)
    top_w, top_e, _ = layer.route(router, x, none)
    s = np.asarray(jax.nn.sigmoid(x @ router))
    want_e = np.argsort(-s, axis=-1)[..., :3]
    assert np.array_equal(np.sort(np.asarray(top_e), -1), np.sort(want_e, -1))
    chosen = np.take_along_axis(s, np.asarray(top_e), -1)
    assert close(top_w, chosen / chosen.sum(-1, keepdims=True) * 2.446, 1e-6)
    assert np.allclose(np.asarray(top_w).sum(-1), 2.446, rtol=1e-5)
    ref_w, ref_e = jax.vmap(lambda one: ref.route(cfg, router, one, IDENT))(x)
    assert np.array_equal(np.asarray(ref_e), np.asarray(top_e)) and close(ref_w, top_w, 1e-6)
    # a bias on expert 5 moves the choice to it and leaves its weight its score's
    bias = none.at[5].set(10.0)
    w5, e5, _ = layer.route(router, x, bias)
    assert (np.asarray(e5) == 5).any(-1).all() and not (np.asarray(top_e) == 5).any(-1).all()
    chosen5 = np.take_along_axis(s, np.asarray(e5), -1)
    assert close(w5, chosen5 / chosen5.sum(-1, keepdims=True) * 2.446, 1e-6)
    ref_w5, ref_e5 = jax.vmap(lambda one: ref.route(cfg, router, one, IDENT, bias))(x)
    assert np.array_equal(np.asarray(ref_e5), np.asarray(e5)) and close(ref_w5, w5, 1e-6)
    # no gradient reaches the bias; the layer reads it from its state
    grad = jax.grad(lambda b: jnp.sum(layer.route(router, x, b)[0] ** 2))(bias)
    assert not np.asarray(grad).any()
    state = layer.init(jax.random.PRNGKey(0), (32, 64))[1]
    assert state["select_bias"].shape == (16,) and not np.asarray(state["select_bias"]).any()
    w = _share(_uncut_weights(rng), 4, 4)
    plain, after = _moe(4).apply(w, state, x, training=True)
    moved, _ = _moe(4).apply(w, {**state, "select_bias": bias}, x, training=True)
    assert "select_bias" in after and not close(moved, plain, 1e-3)
    with pytest.raises(ValueError, match="scoring"):
        MoELayer(24, n_routed=16, top_k=3, scoring="tanh", name="bad")


def test_the_32_shares_add_up_to_the_whole_layer(rng):
    """``(first, held)`` over a layer of 16 experts in four shares of four, as
    32 chips hold 8 of 256 each: the shares' routed parts, with the shared
    expert counted once, are what the uncut reference gives."""
    cfg = tiny_cfg(tiny_model())
    w = _uncut_weights(rng)
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    whole = jax.vmap(lambda one: ref._experts(cfg, w, one, IDENT, 0, 16))(x)
    shared = jax.vmap(lambda one: ref._mlp(w["shared"], one, IDENT))(x)
    total = shared
    for first in (0, 4, 8, 12):
        layer = _moe(first)
        y, state = layer.apply(_share(w, first, 4), layer.init(jax.random.PRNGKey(0), (32, 64))[1],
                               x, training=True)
        part = jax.vmap(lambda one, first=first: ref._experts(
            cfg, _share(w, first, 4), one, IDENT, first, 4))(x)
        assert close(y, part)                      # a share is the reference's share
        total = total + (y - shared)
        assert int(state["pairs_routed"]) == 2 * 32 * 3
    assert close(total, whole)


# ------------------------------------------------------------------ the model

def test_one_decoder_serves_both_zoo_entries(published):
    both = [create_model(n) for n in ("deepseek_v2_lite_ep8", "kimi_linear_48b_ep32")]
    assert all(type(m) is LatentMoEDecoder for m in both)
    assert {"deepseek_v2_lite_ep8", "kimi_linear_48b_ep32"} <= set(MODEL_ZOO)
    old, new = both
    assert old.kda_layers == [] and all(isinstance(a, LatentAttentionLayer) and a.rotary
                                        for a in old.attn)
    assert all(layer.scoring == "softmax" for layer in old.moe.values())
    assert new.kda_layers == [0, 1, 2, 4] and isinstance(new.attn[3], LatentAttentionLayer)
    assert all(layer.scoring == "sigmoid" and layer.norm_topk and layer.n_shared == 1
               and layer.routed_scale == 2.446 and (layer.n_routed, layer.top_k) == (256, 8)
               for layer in new.moe.values())
    assert "4 of them KDA and 1 MLA" in new.summary()
    assert LatentMoEDecoder.from_config(new.get_config()).param_count() == new.param_count()


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
    # evaluation mixes all sequences at once, training one at a time: rounding apart
    logits, same = jax.jit(model.apply)(params, state, x)
    assert close(logits, jax.jit(lambda p: model.apply(p, state, x, training=True)[0])(params),
                 1e-5)
    assert int(same["layers"][1]["pairs_held"]) == 0


def test_the_decays_rate_stays_float32_in_the_bf16_mode():
    """``A_log`` and ``dt_bias`` reach the layer uncast: the decay of a
    position is read as the float32 master has it."""
    model = tiny_model(num_hidden_layers=1)
    params, state = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, 32), jnp.int32)
    set_precision("bf16")
    try:
        text = str(jax.make_jaxpr(lambda p: model.apply(p, state, x, training=True)[0])(params))
    finally:
        set_precision("parity")
    import re
    assert params["layers"][0]["attn"]["A_log"].shape == (2,)
    # the block's cast of A_log is there and nothing reads it
    assert set(re.findall(r"(\w+):bf16\[2\] = convert_element_type", text)) == {"_"}
    assert re.search(r"[a-z]\w*:bf16\[64,32\] = convert_element_type", text)   # the projections


def test_zoo_model_is_the_published_cut(published):
    model = create_model("kimi_linear_48b_ep32")
    assert model.param_count() == 602_449_792 == published["parameters"]
    assert flops.param_count(published) == 602_449_792
    for key, value in model.config.items():
        assert published[key] == value, key
    assert published["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert published["experts_held"] == published["num_experts"] == 8
    assert (published["num_experts_published"], published["num_hidden_layers_published"],
            published["vocab_size_published"]) == (256, 27, 163840)
    lin = published["linear_attn_config"]
    assert [k for k in lin["kda_layers"] if k <= 5] == published["kda_layers_held"] == [1, 2, 3, 5]
    assert [k for k in lin["full_attn_layers"] if k <= 5] == published["full_attn_layers_held"]
    # every published width, unchanged
    assert (published["hidden_size"], published["intermediate_size"],
            published["moe_intermediate_size"], published["kv_lora_rank"]) == (2304, 9216, 1024, 512)
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert (published["num_attention_heads"], published["qk_nope_head_dim"],
            published["qk_rope_head_dim"], published["v_head_dim"]) == (32, 128, 64, 128)
    assert (published["num_experts_per_token"], published["routed_scaling_factor"]) == (8, 2.446)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))[0]
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == 602_449_792
    kda = shapes["layers"][0]["attn"]
    assert sum(int(np.prod(a.shape)) for a in kda.values()) == 39_518_368
    mla = shapes["layers"][3]["attn"]
    assert sum(int(np.prod(a.shape)) for a in mla.values()) == 29_114_880


def test_hybrid_lm_flops_is_the_hand_arithmetic(published):
    f = flops.forward_flops_per_token(published)
    mega = lambda name: round(f[name] / 1e6, 1)  # noqa: E731
    assert (mega("kda_proj"), mega("dense_mlp"), mega("mla_proj"), mega("scores")) \
        == (78.9, 127.4, 58.2, 42.0)
    assert (mega("shared"), mega("routed"), mega("head"), mega("router")) == (14.2, 3.5, 94.4, 1.2)
    # a chunk of 64 x 128: A, B 2 x 262,144; inverse 43,691; times [K|V] 524,288; the state
    # read twice and written 3 x 1,048,576; B U 262,144 multiply-adds; / 64 tokens x 32 heads
    assert np.isclose(f["kda_chunk"], 2 * (4_456_448 + 64 ** 3 / 6) / 64 * 32)
    assert flops.kda_layers(published) == 4
    assert np.isclose(f["total"], 4 * (f["kda_proj"] + f["kda_chunk"]) + f["mla_proj"] + f["scores"]
                      + f["dense_mlp"] + 4 * (f["shared"] + f["routed"] + f["router"]) + f["head"])
    assert round(f["total"] / 1e6) == 731
    assert round(4 * flops.train_flops_per_sequence(published) / 1e12, 1) == 35.9
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.kda_chunk_min_seconds(published, 4, peaks)
    # q, k, v, o at 2 bytes, g at 4, beta 4 a head: 1,540 forward, 2,824 backward
    assert bound == "bytes" and np.isclose(t, 16384 * 32 * (1540 + 2824) / 819e9)
    assert 3 * f["kda_chunk"] * 16384 / 197e12 < t


# ------------------------------------------------------------------ the token job

def _lm_trainer():
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    return _by_path("lm_trainer_example", ROOT, "examples", "lm_trainer.py")


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
    chunked0 = get_registry().snapshot().get("nn_kda_chunked_total", 0)
    rng, epoch = jax.random.PRNGKey(7), 1
    ts, loss, _ = trainer.train_epoch(ts, ds, rng, epoch)
    assert int(ts.step) == 3
    assert get_registry().snapshot()["nn_kda_chunked_total"] > chunked0
    assert int(ts.state["layers"][1]["pairs_held"]) == 0          # published, cleared
    assert not np.asarray(ts.state["layers"][1]["select_bias"]).any()   # and left at zero

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
    # the first gradient agrees to 1.2e-5 on every leaf; Adam's normalised
    # updates carry rounding into the second and third steps (6e-4 to 9e-4 on
    # m, every leaf alike), and an element whose first gradient is at rounding
    # level moves by the learning rate in its sign's direction: one such
    # element of l0's wo (2,048 elements) puts that leaf's change 1.9e-2 off,
    # every other leaf under 2.3e-3
    for got, want in zip(leaves(ts.opt_state["m"]), leaves(o["m"])):
        assert close(got, want, 2e-3)
    off = [np.linalg.norm(got - want) / np.linalg.norm(want - start) for got, want, start
           in zip(*(jax.device_get(leaves(t)) for t in (ts.params, p, params0)))]
    assert max(off) < 3e-2 and np.median(off) < 2e-3


def test_the_cells_cpu_rehearsal_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400,
        # a minute of compiling on every core: below the tests beside it in
        # the run, some of which time heartbeats of 50 ms
        preexec_fn=lambda: os.nice(10))
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["metrics"] == {} and result["failed"] == 0


def test_the_traffic_files_rehearsal_is_in_this_familys_keys():
    with open(os.path.join(BENCH, "traffic", "resident_tokens_kda.json")) as f:
        mine = json.load(f)
    with open(os.path.join(BENCH, "traffic", "resident_tokens.json")) as f:
        beside = json.load(f)
    for key in ("driver", "env", "reports", "counts", "trace_seconds"):
        assert mine[key] == beside[key], key
    small = mine["rehearsal"]["config"]
    model = create_model("kimi_linear_48b_ep32")
    model = model.resized(**{k: small[k] for k in model.config if k in small})
    assert model.kda_layers == [0, 1, 3] and model.dense_layers == 1 and model.num_layers == 4
    assert small["seq_len"] > 2 * model.attn[0].chunk          # a sequence crosses chunks
    assert (model.experts_held, model.n_routed, model.vocab) == (4, 16, 128)


def test_benchmark_lists_the_cell_and_its_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = [w for w in spec["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1 and len(cell[0]["why"]) <= 200
    assert (cell[0]["config"], cell[0]["traffic"]) == ("kimi_linear_48b_ep32",
                                                       "resident_tokens_kda")
    assert spec["workloads"][-1]["name"] == CELL and spec["configs"][-1]["name"] == cell[0]["config"]
    assert spec["configs"][-1]["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    listed = {m["name"] for m in spec["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {"hybrid_lm_train_mfu", "kda_chunk_roofline", "kda_device_share",
                      "moe_device_share", "device_idle_share", "peak_hbm_gb", "stage_h2d_gbps",
                      "compile_s", "data_device_share", "optim_device_share",
                      # PR 37: the trainer loop's and set-up's, in every resident cell
                      "epoch_turn_share", "first_dispatch_s", "trace_lower_s", "build_s"}
    own = [m for m in spec["per_layer"] if m["name"] in (
        "hybrid_lm_train_mfu", "kda_chunk_roofline", "kda_device_share")]
    assert len(own) == 3
    for m in own:
        assert m["workloads"] == [CELL] and m["moves"] == "train_img_per_s" and m["unit"] == "%"
    assert CELL in next(m for m in spec["end_to_end"]
                        if m["name"] == "train_img_per_s")["workloads"]
    for name in listed:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == set(limits["rehearsal_limits"]) >= {
        "moment_direction_gap", "change_direction_gap", "change_gap", "change_gap_median"}


# ------------------------------------------------------------------ the readers

def test_a_trace_names_the_kda_scopes_side_by_side():
    """``chipbench/trace_reduce.stable_name`` over the compiled training
    step's operation names: both scopes of every KDA layer, forward and
    backward, the MLA layer's own, and the routed part's four."""
    import re

    from jax.experimental.compilation_cache import compilation_cache

    trace_reduce = _by_path("trace_reduce", BENCH, "trace_reduce.py")
    model = tiny_model()
    params, state = model.init(jax.random.PRNGKey(0))
    rows = jax.random.randint(jax.random.PRNGKey(2), (4, 33), 0, 128)

    def loss(p):
        logits, _ = model.apply(p, state, rows[:, :-1], training=True)
        return token_cross_entropy(logits, rows[:, 1:])
    # scope names are not in the persistent cache's key
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.value_and_grad(loss)).lower(params).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    names = {trace_reduce.stable_name(m) for m in re.findall(r'op_name="([^"]+)"', text)}
    for k in (0, 1, 3):
        for scope in ("kda", "kda.chunk"):
            mine = {n for n in names if n.startswith(f"l{k}.{scope}/")}
            assert any(n.endswith("_bwd") for n in mine), (k, scope)
            assert any(not n.endswith("_bwd") for n in mine), (k, scope)
    assert any(n.startswith("l2.attn/") for n in names)
    assert any(n.startswith("l2.attn.flash/") for n in names)
    assert not [n for n in names if n.startswith("l2.kda") or n.startswith("l0.attn")]
    for scope in ("router", "dispatch", "experts", "combine", "shared"):
        assert any(n.startswith(f"l1.{scope}/") for n in names), scope
    reduced = {"devices": {"d": {"busy_s": 1.0, "ops": {n: 1.0 for n in names}}}}
    assert flops.scoped_seconds(reduced, r"kda\.chunk")[0] > 0
    assert flops.scoped_seconds(reduced, r"kda|kda\.chunk")[0] \
        > flops.scoped_seconds(reduced, r"kda\.chunk")[0]


class _Window:
    traced_images, traced_s, images = 32, 8.0, 128


def _reader(name):
    return _by_path("reader_" + name, BENCH, "layer_metrics", name + ".py")


def _ctx(cfg, ops, **more):
    reduced = {"devices": {"/device:TPU:0": {"busy_s": 8.0, "ops": ops}}} if ops else {}
    return {"reduced": reduced, "window": _Window(), "cfg": cfg, "chips": 1,
            "traffic": {}, "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "counters": {}, "log": lambda *a: None, **more}


OPS = {"l0.kda/dot": 0.6, "l0.kda/dot_bwd": 1.2, "l0.kda.chunk/dot": 0.3,
       "l0.kda.chunk/while_bwd": 0.5, "l2.kda.chunk/exp": 0.2, "l3.attn.flash/pallas_call": 0.4,
       "l3.attn/dot": 0.2, "l1.experts/pallas_call": 0.3, "l1.router/dot": 0.1,
       "l1.dispatch/gather": 0.2, "l1.combine/gather_bwd": 0.2, "l1.shared/dot": 0.4,
       "l0.mlp/dot_bwd": 0.5, "optim/sub": 0.1}
READERS = ["hybrid_lm_train_mfu", "kda_chunk_roofline", "kda_device_share"]


def test_readers_read_the_programs_scopes(published):
    ctx = _ctx(published, OPS)
    mfu = _reader("hybrid_lm_train_mfu").read(ctx)
    assert np.isclose(mfu, 100 * 32 * flops.train_flops_per_sequence(published)
                      / (8.0 * 197e12)) and 0 < mfu < 100
    one = flops.kda_chunk_min_seconds(published, 4, ctx["peaks"])[0]
    assert np.isclose(_reader("kda_chunk_roofline").read(ctx), 100 * 4 * 8 * one / 1.0)
    assert 0 < _reader("kda_chunk_roofline").read(ctx) < 100
    assert np.isclose(_reader("kda_device_share").read(ctx), 100 * 2.8 / 8.0)
    # the reader by scope alone that the cell shares with the other language model
    assert np.isclose(_reader("moe_device_share").read(ctx), 100 * 0.8 / 8.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_on_another_program(published, name):
    """A program without the scopes (the parent's), the other language
    model's configuration, a convolutional one, a CPU rehearsal: nothing to
    read, and no exception."""
    read = _reader(name).read
    conv = {"layer1_block1/conv": 1.0, "optim/sub": 0.1}
    with open(os.path.join(BENCH, "configs", "deepseek_v2_lite_ep8.json")) as f:
        other_lm = json.load(f)
    other_ops = {"l1.attn.flash/pallas_call": 0.2, "l1.attn/dot": 0.3, "l2.experts/mul": 0.1}
    assert read(dict(_ctx(published, OPS), peaks=None)) is None
    assert read(_ctx({"batch_size": 2048, "layers": []}, conv)) is None
    assert read(_ctx(other_lm, other_ops)) is None
    if name != "hybrid_lm_train_mfu":
        assert read(_ctx(published, other_ops)) is None
        assert read(_ctx(published, {})) is None
