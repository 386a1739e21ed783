"""The pair-of-columns form of a narrow stride-1 convolution's weight gradient
(``ops/conv.py``) and the channel-last run of a narrow ``ResidualBlock`` that
hands it its cotangent (``nn/residual.py``), against the plain products.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dcnn_tpu.core import precision
from dcnn_tpu.models import create_model
from dcnn_tpu.nn import (ActivationLayer, BatchNormLayer, Conv2DLayer,
                         MaxPool2DLayer, ResidualBlock)
from dcnn_tpu.obs.registry import get_registry
from dcnn_tpu.ops import activations as act_ops
from dcnn_tpu.ops import conv as conv_ops
from dcnn_tpu.ops import softmax_cross_entropy
from dcnn_tpu.optim import SGD
from dcnn_tpu.train import make_train_step
from dcnn_tpu.train.trainer import create_train_state


def pair_products():
    return get_registry().snapshot().get("nn_conv_pair_products_total", 0)


def plain(x, w, b, stride, padding, fmt, prec=lax.Precision.HIGHEST):
    """The parent's ``conv2d``: one ``conv_general_dilated`` and the bias."""
    (sh, sw), (ph, pw) = stride, padding
    out = lax.conv_general_dilated(
        x, w, window_strides=(sh, sw), padding=((ph, ph), (pw, pw)),
        dimension_numbers=conv_ops._dims(fmt), precision=prec)
    if b is None:
        return out
    return out + (b.reshape(1, -1, 1, 1) if fmt == "NCHW" else b.reshape(1, 1, 1, -1))


def pair(x, w, b, ph):
    """The product whose weight gradient takes the pair form, whatever the
    rule says of its width (the rule is tested apart)."""
    pw = (w.shape[3] - 1) // 2
    return conv_ops._add_bias(
        conv_ops._product_pair_grad(x, w, (ph, pw)), b, "NHWC")


def operands(cin, cout, k, bias, dtype=jnp.float32, n=3, hw=(6, 8)):
    ks = jax.random.split(jax.random.PRNGKey(cin + 7 * cout + k), 4)
    x = jax.random.normal(ks[0], (n, *hw, cin), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (cout, cin, k, k), jnp.float32) / (k * cin ** 0.5)).astype(dtype)
    b = jax.random.normal(ks[2], (cout,), jnp.float32).astype(dtype) if bias else None
    g = jax.random.normal(ks[3], (n, *hw, cout), jnp.float32).astype(dtype)
    return x, w, b, g


@pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("cin,cout", [(32, 64), (64, 64), (16, 16)])
def test_pair_form_is_the_plain_product(cin, cout, k, bias):
    """Forward, input gradient, weight gradient (and the bias's) against
    ``lax.conv_general_dilated`` and its autodiff, float32 ``highest``."""
    x, w, b, g = operands(cin, cout, k, bias)
    p = (k - 1) // 2
    args = (x, w) if b is None else (x, w, b)

    def of(fn):
        def loss(x, w, b=None):
            return jnp.sum(fn(x, w, b) * g)
        return jax.value_and_grad(loss, argnums=tuple(range(len(args))))(*args)

    before = pair_products()
    got, got_grads = of(lambda x, w, b: pair(x, w, b, p))
    assert pair_products() - before == 1
    want, want_grads = of(lambda x, w, b: plain(x, w, b, (1, 1), (p, p), "NHWC"))
    np.testing.assert_array_equal(pair(x, w, b, p), plain(x, w, b, (1, 1), (p, p), "NHWC"))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_array_equal(got_grads[0], want_grads[0])         # the plain product's own
    scale = float(jnp.abs(want_grads[1]).max())
    np.testing.assert_allclose(got_grads[1], want_grads[1], atol=1e-6 * scale, rtol=0)
    if bias:
        np.testing.assert_array_equal(got_grads[2], want_grads[2])


@pytest.mark.parametrize("ph", [0, 2])
def test_pair_weight_grad_keeps_the_layers_own_row_padding(ph):
    """Only the columns pair up: the window's height and the padding in H are
    the layer's own (3x3 with no or two rows of padding, a 1x3 window)."""
    for kh in (3, 1):
        x, _, _, _ = operands(16, 16, 3, False)
        w = jax.random.normal(jax.random.PRNGKey(5), (16, 16, kh, 3), jnp.float32)
        y = plain(x, w, None, (1, 1), (ph, 1), "NHWC")
        g = jax.random.normal(jax.random.PRNGKey(6), y.shape, jnp.float32)
        want = jax.grad(lambda w: jnp.sum(plain(x, w, None, (1, 1), (ph, 1), "NHWC") * g))(w)
        got = conv_ops._pair_weight_grad(x, g, w, ph)
        np.testing.assert_allclose(got, want, atol=1e-6 * float(jnp.abs(want).max()), rtol=0)


@pytest.mark.parametrize("cin,cout", [(32, 64), (64, 64)])
def test_bfloat16_weight_gradient_is_rounded_once(cin, cout):
    """bfloat16 operands, float32 accumulation, one rounding of each element:
    the pair form's result lies within one bfloat16 rounding of the exact
    float32 product of the same operands, as the plain product's does (the
    fold adds two float32 partial sums before the cast)."""
    x, w, _, g = operands(cin, cout, 3, False, jnp.bfloat16, n=4, hw=(8, 8))
    exact = jax.grad(lambda w: jnp.sum(plain(x.astype(jnp.float32), w, None, (1, 1), (1, 1), "NHWC")
                                       * g.astype(jnp.float32)))(w.astype(jnp.float32))
    got = conv_ops._pair_weight_grad(x, g, w, 1)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - exact)
    # half a unit in the last place of bfloat16 (8 bits of mantissa) plus the
    # float32 sum's own round-off
    assert float(jnp.max(err - (2.0 ** -8) * jnp.abs(exact))) <= 1e-5 * float(jnp.abs(exact).max())
    same = jax.grad(lambda w: jnp.sum((plain(x, w, None, (1, 1), (1, 1), "NHWC", None)
                                       * g).astype(jnp.float32)))(w)
    assert float(jnp.mean(got == same)) > 0.98     # and nearly always the plain product's own bits


def lowered_text(fn, *args):
    def product(*a):
        return fn(*a)
    return jax.jit(product).lower(*args).as_text()


REFUSED = {
    "nchw":      dict(cin=64, cout=64, k=3, stride=1, pad=1, fmt="NCHW", hw=(8, 8)),
    "stride2":   dict(cin=64, cout=64, k=3, stride=2, pad=1, fmt="NHWC", hw=(8, 8)),
    "1x1":       dict(cin=64, cout=64, k=1, stride=1, pad=0, fmt="NHWC", hw=(8, 8)),
    "oddwidth":  dict(cin=64, cout=64, k=3, stride=1, pad=1, fmt="NHWC", hw=(8, 7)),
    "128out":    dict(cin=64, cout=128, k=3, stride=1, pad=1, fmt="NHWC", hw=(8, 8)),
    "cin3":      dict(cin=3, cout=64, k=3, stride=1, pad=1, fmt="NHWC", hw=(8, 8)),
    "narrowing": dict(cin=64, cout=32, k=3, stride=1, pad=1, fmt="NHWC", hw=(8, 8)),
    "valid":     dict(cin=64, cout=64, k=3, stride=1, pad=0, fmt="NHWC", hw=(8, 8)),
    "evenwin":   dict(cin=64, cout=64, k=2, stride=1, pad=1, fmt="NHWC", hw=(8, 8)),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_the_rule_refuses_lowers_to_the_parents_text(case):
    """A product the rule does not match is the parent's program, forward and
    backward, to the byte; and no pair product is counted."""
    c = REFUSED[case]
    shape = (2, c["cin"], *c["hw"]) if c["fmt"] == "NCHW" else (2, *c["hw"], c["cin"])
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    w = jax.ShapeDtypeStruct((c["cout"], c["cin"], c["k"], c["k"]), jnp.float32)
    b = jax.ShapeDtypeStruct((c["cout"],), jnp.float32)
    geom = ((c["stride"],) * 2, (c["pad"],) * 2, c["fmt"])

    def mine(x, w, b):
        return conv_ops.conv2d(x, w, b, stride=geom[0], padding=geom[1], data_format=geom[2])

    def parents(x, w, b):
        return plain(x, w, b, *geom)

    def grads(fn):
        return lambda x, w, b: jax.grad(lambda *a: jnp.sum(fn(*a) ** 2), argnums=(0, 1, 2))(x, w, b)

    before = pair_products()
    assert lowered_text(mine, x, w, b) == lowered_text(parents, x, w, b)
    assert lowered_text(grads(mine), x, w, b) == lowered_text(grads(parents), x, w, b)
    assert pair_products() == before


def test_a_matched_product_forward_only_is_the_parents_text():
    """Under the ``custom_vjp`` wiring a program without a backward holds the
    plain product alone."""
    x = jax.ShapeDtypeStruct((2, 8, 8, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64, 3, 3), jnp.float32)
    assert conv_ops.takes_pair_form(64, 64, 3, (1, 1), 1, 8)
    before = pair_products()
    assert (lowered_text(lambda x, w: conv_ops.conv2d(x, w, padding=1, data_format="NHWC"), x, w)
            == lowered_text(lambda x, w: plain(x, w, None, (1, 1), (1, 1), "NHWC"), x, w))
    assert pair_products() == before


# ---------------------------------------------------------------- the block

def basic_block(cin, cout, k=3, bias=False, fmt="NCHW"):
    p = (k - 1) // 2
    main = [Conv2DLayer(cout, k, 1, p, use_bias=bias, data_format=fmt, name="conv0"),
            BatchNormLayer(data_format=fmt, name="bn0"), ActivationLayer("relu", name="relu0"),
            Conv2DLayer(cout, k, 1, p, use_bias=bias, data_format=fmt, name="conv1"),
            BatchNormLayer(data_format=fmt, name="bn1")]
    shortcut = [] if cin == cout else [
        Conv2DLayer(cout, 1, 1, 0, use_bias=False, data_format=fmt, name="proj"),
        BatchNormLayer(data_format=fmt, name="proj_bn")]
    return ResidualBlock(main, shortcut, "relu", name="block")


def as_written(block, params, state, x, *, training):
    """The parent's ``ResidualBlock.apply``: every layer in turn, in the
    layers' own data format."""
    h, new_main = x, []
    for layer, p, s in zip(block.layers, params["main"], state["main"]):
        h, s = layer.apply(p, s, h, training=training)
        new_main.append(s)
    s_out, new_short = x, []
    for layer, p, s in zip(block.shortcut, params["shortcut"], state["shortcut"]):
        s_out, s = layer.apply(p, s, s_out, training=training)
        new_short.append(s)
    return act_ops.relu(h + s_out), {"main": tuple(new_main), "shortcut": tuple(new_short)}


@pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("cin,cout", [(32, 64), (64, 64)])
def test_narrow_nchw_block_trains_channel_last_and_agrees(cin, cout, k, bias):
    """An NCHW block with a narrow 3x3 runs channel-last in training (that is
    where its weight gradients take the pair form: two per block) and agrees
    with the block as written: output, layer states, every gradient."""
    block = basic_block(cin, cout, k, bias)
    params, state = block.init(jax.random.PRNGKey(0), (cin, 6, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, cin, 6, 8), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(2), (4, cout, 6, 8), jnp.float32)

    def of(apply):
        def loss(params, x):
            y, new_state = apply(block, params, state, x, training=True)
            return jnp.sum(y * g), (y, new_state)
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x)

    before = pair_products()
    (_, (y, new_state)), grads = of(lambda b, *a, **kw: b.apply(*a, **kw))
    assert pair_products() - before == 2
    (_, (y0, new_state0)), grads0 = of(as_written)
    np.testing.assert_allclose(y, y0, atol=2e-5, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(new_state), jax.tree.leaves(new_state0)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)
    # a bias that feeds a batch norm has a true gradient of nought: round-off
    # on both sides, held to the scale of the other leaves
    scale = max(float(jnp.abs(l).max()) for l in jax.tree.leaves(grads0))
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, rtol=2e-4)


def block_text(apply, block, params, state, x, training):
    def run(params, state, x):
        return apply(block, params, state, x, training=training)
    return jax.jit(run).lower(params, state, x).as_text()


def of_block(b, *a, **kw):
    return b.apply(*a, **kw)


def test_evaluation_keeps_the_block_as_written():
    """Nothing is gained without a backward: ``training=False`` lowers to the
    parent's text, and counts nothing."""
    block = basic_block(64, 64)
    params, state = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), (64, 8, 8)))
    x = jax.ShapeDtypeStruct((2, 64, 8, 8), jnp.float32)
    before = pair_products()
    assert (block_text(of_block, block, params, state, x, False)
            == block_text(as_written, block, params, state, x, False))
    assert pair_products() == before


def wide_block():
    return basic_block(128, 128)


def strided_block():
    b = basic_block(64, 128)
    b.layers[0] = Conv2DLayer(128, 3, 2, 1, use_bias=False, name="conv0")
    b.shortcut[0] = Conv2DLayer(128, 1, 2, 0, use_bias=False, name="proj")
    return b


def pooled_block():
    b = basic_block(64, 64)
    b.layers.append(MaxPool2DLayer(1, 1, 0, name="pool"))
    return b


def softmax_block():
    b = basic_block(64, 64)
    b.layers[2] = ActivationLayer("softmax", name="soft")
    return b


def nhwc_block():
    return basic_block(64, 64, fmt="NHWC")


@pytest.mark.parametrize("make,cin", [(wide_block, 128), (strided_block, 64), (pooled_block, 64),
                                      (softmax_block, 64)],
                         ids=["128channels", "stride2", "otherlayer", "softmax"])
def test_blocks_the_rule_refuses_train_as_written(make, cin):
    """No narrow stride-1 3x3, a layer that is not conv / batch norm /
    elementwise activation, or an activation that knows an axis: the parent's
    text in training too."""
    block = make()
    params, state = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), (cin, 8, 8)))
    x = jax.ShapeDtypeStruct((2, cin, 8, 8), jnp.float32)
    before = pair_products()
    assert (block_text(of_block, block, params, state, x, True)
            == block_text(as_written, block, params, state, x, True))
    assert pair_products() == before


def test_a_channel_last_block_needs_no_rewrite():
    """An NHWC block is left as written; its narrow convolutions take the
    pair form by themselves."""
    block = nhwc_block()
    assert block._channel_last((2, 8, 8, 64)) is None
    params, state = block.init(jax.random.PRNGKey(0), (8, 8, 64))
    x = jnp.ones((2, 8, 8, 64), jnp.float32)
    before = pair_products()
    jax.grad(lambda p: jnp.sum(block.apply(p, state, x, training=True)[0]))(params)
    assert pair_products() - before == 2


# ------------------------------------------------------------- whole models

@pytest.mark.parametrize("name,counted", [
    ("resnet18_tiny_imagenet", 4),      # layer1_block1 and layer1_block2, two convolutions each
    ("resnet34_tiny_imagenet", 6),      # three blocks of layer1
    ("resnet50_tiny_imagenet", 3),      # the 64-wide 3x3 of the first stage's three bottleneck blocks
    ("mnist_cnn", 0),
])
def test_counter_after_tracing_the_training_step(name, counted):
    model = create_model(name)
    opt = SGD(0.1)
    ts = jax.eval_shape(lambda: create_train_state(model, opt, jax.random.PRNGKey(0)))
    step = make_train_step(model, softmax_cross_entropy, opt, donate=False)
    x = jax.ShapeDtypeStruct((2, *model.input_shape), jnp.float32)
    classes = model.output_shape()[-1]
    y = jax.ShapeDtypeStruct((2, classes), jnp.float32)
    before = pair_products()
    step.lower(ts, x, y, jax.random.PRNGKey(0), jnp.float32(0.1))
    assert pair_products() - before == counted


def test_resnet18_evaluation_program_is_the_parents():
    """The evaluation program of the benchmark's model holds no pair product
    and no channel-last block: its text is that of the layers applied in turn,
    each block as written."""
    model = create_model("resnet18_tiny_imagenet")
    params, state = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), model.input_shape))
    x = jax.ShapeDtypeStruct((2, *model.input_shape), jnp.float32)

    def parents(params, state, x):
        h = precision.cast_to_compute(x)
        for layer, p, s in zip(model.layers, params, state):
            p = precision.cast_to_compute(p)
            with jax.named_scope(layer.name):
                if isinstance(layer, ResidualBlock):
                    h, _ = as_written(layer, p, s, h, training=False)
                else:
                    h, _ = layer.apply(p, s, h, training=False)
        return h

    def mine(params, state, x):
        return model.apply(params, state, x, training=False)[0]

    before = pair_products()
    assert (jax.jit(mine).lower(params, state, x).as_text().replace("jit_mine", "jit_f")
            == jax.jit(parents).lower(params, state, x).as_text().replace("jit_parents", "jit_f"))
    assert pair_products() == before
