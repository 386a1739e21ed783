"""The head conv -> [bn] -> activation -> 2x2 max-pool computed by window
position (``nn/sequential.py _apply_pool_phase``) against the layer-by-layer path.

The stem alone is compared, not a whole ResNet: at initialisation the whole
network turns float32 round-off into 0.6-0.8% of the gradient, which no tight
tolerance survives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcnn_tpu.core import precision
from dcnn_tpu.models import create_model
from dcnn_tpu.nn import (ActivationLayer, BatchNormLayer, Conv2DLayer,
                         MaxPool2DLayer, Sequential)
from dcnn_tpu.obs.registry import get_registry
from dcnn_tpu.ops import max_pool2d, softmax_cross_entropy
from dcnn_tpu.ops.conv import conv2d, conv2d_pool_phases
from dcnn_tpu.ops.pool import max_pool2d_phases
from dcnn_tpu.optim import SGD
from dcnn_tpu.train import make_train_step
from dcnn_tpu.train.trainer import create_train_state


def rewrites():
    return get_registry().snapshot().get("nn_pool_phase_rewrites_total", 0)


def layerwise(model, params, state, x, *, training):
    """Today's path: every layer applied in turn, as ``Sequential.apply``
    does for a model without the pattern."""
    h, new_state = precision.cast_to_compute(x), []
    for layer, p, s in zip(model.layers, params, state):
        h, s = layer.apply(precision.cast_to_compute(p), s, h, training=training)
        new_state.append(s)
    return h, tuple(new_state)


def first_layers(model, k, params, state, x):
    sub = Sequential(model.layers[:k])
    return layerwise(sub, params[:k], state[:k], x, training=True)[0]


def stem(fmt, *, bn=True, bias=False, kernel=3, padding=1, stride=1, pool=(2, 2, 0),
         activation="relu", lead=()):
    layers = list(lead) + [Conv2DLayer(8, kernel, stride, padding, use_bias=bias,
                                       data_format=fmt, name="conv1")]
    if bn:
        layers.append(BatchNormLayer(epsilon=1e-3, data_format=fmt, name="bn1"))
    layers += [ActivationLayer(activation, name="relu1"),
               MaxPool2DLayer(*pool, data_format=fmt, name="maxpool")]
    return Sequential(layers)


def image(fmt, n=4, c=3, hw=(12, 16), seed=1):
    shape = (n, c, *hw) if fmt == "NCHW" else (n, *hw, c)
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def init(model, x, seed=0):
    params, state = model.init(jax.random.PRNGKey(seed), x.shape[1:])
    # statistics and an affine part that are not the identity, so that the
    # evaluation path and gamma/beta's gradients are exercised
    k = jax.random.split(jax.random.PRNGKey(seed + 7), 4)
    params = tuple({n: (v if n in ("w", "b") else v + 0.3 * jax.random.normal(k[0], v.shape))
                    for n, v in p.items()} for p in params)
    state = tuple({"running_mean": 0.2 * jax.random.normal(k[1], s["running_mean"].shape),
                   "running_var": 1.0 + 0.5 * jax.random.uniform(k[2], s["running_var"].shape)}
                  if s else s for s in state)
    return params, state


def outputs_and_grads(fn, model, params, state, x, training):
    """(y, new_state, gradients to params and x) under a fixed cotangent."""
    def f(params, x):
        y, ns = fn(model, params, state, x, training=training)
        return y, ns
    (y, ns), vjp = jax.vjp(f, params, x)
    ct = jax.random.normal(jax.random.PRNGKey(5), y.shape, y.dtype)
    gp, gx = vjp((ct, jax.tree.map(jnp.zeros_like, ns)))
    return y, ns, gp, gx


def assert_close(got, want):
    """To 1e-5 of each leaf's scale: a weight gradient through the batch
    norm is a sum of cancelling terms, whose float32 round-off goes with the
    largest of them."""
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(1.0, float(jnp.max(jnp.abs(w))))
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * scale)


def drop_bias_under_bn(out, model, training):
    """A bias that feeds a training-mode batch norm has a true gradient of
    nought; what either path returns for it is round-off alone (checked to be
    small) and is not compared."""
    y, ns, gp, gx = out
    if training and type(model.layers[1]) is BatchNormLayer and "b" in gp[0]:
        assert float(jnp.max(jnp.abs(gp[0]["b"]))) < 1e-3
        gp = ({"w": gp[0]["w"]},) + tuple(gp[1:])
    return y, ns, gp, gx


def seq_apply(model, params, state, x, *, training):
    return model.apply(params, state, x, training=training)


def lowered(fn, model, params, state, x, training=True):
    def apply(params, state, x):
        return fn(model, params, state, x, training=training)
    return jax.jit(apply).lower(params, state, x).as_text()


@pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("training", [True, False], ids=["train", "eval"])
@pytest.mark.parametrize("bn", [True, False], ids=["bn", "nobn"])
@pytest.mark.parametrize("fmt", ["NCHW", "NHWC"])
def test_phase_path_matches_layerwise(fmt, bn, training, bias):
    """Training mode takes the phase path and agrees with the layer-by-layer
    one; evaluation mode (no backward to gain from) is the layer-by-layer
    path itself, to the letter of its HLO."""
    model, x = stem(fmt, bn=bn, bias=bias), image(fmt)
    params, state = init(model, x)
    before = rewrites()
    got = outputs_and_grads(seq_apply, model, params, state, x, training)
    assert rewrites() == before + training
    want = outputs_and_grads(layerwise, model, params, state, x, training)
    got, want = (drop_bias_under_bn(o, model, training) for o in (got, want))
    assert got[0].shape == want[0].shape
    assert_close(got, want)
    assert jax.tree.structure(got[1]) == jax.tree.structure(want[1])
    same = (lowered(seq_apply, model, params, state, x, training)
            == lowered(layerwise, model, params, state, x, training))
    assert same == (not training)


@pytest.mark.parametrize("kernel,padding,activation", [
    (5, 2, "leaky_relu"), (3, 0, "tanh"), (1, 0, "elu"), (5, 0, "sigmoid"), ((3, 5), (1, 2), "relu")])
def test_phase_path_other_geometries(kernel, padding, activation):
    model = stem("NCHW", kernel=kernel, padding=padding, activation=activation)
    x = image("NCHW", hw=(12, 16))
    params, state = init(model, x)
    before = rewrites()
    got = outputs_and_grads(seq_apply, model, params, state, x, True)
    assert rewrites() == before + 1
    want = outputs_and_grads(layerwise, model, params, state, x, True)
    assert_close(got, want)


@pytest.mark.parametrize("padding", [0, 1, 2, (1, 0)])
@pytest.mark.parametrize("fmt", ["NCHW", "NHWC"])
def test_conv_phases_are_the_strided_views(fmt, padding):
    x = image(fmt, hw=(10, 12))
    w = jax.random.normal(jax.random.PRNGKey(2), (5, 3, 3, 3))
    b = jax.random.normal(jax.random.PRNGKey(3), (5,))
    full = conv2d(x, w, b, padding=padding, data_format=fmt)
    phases = conv2d_pool_phases(x, w, b, padding=padding, data_format=fmt)
    for di in (0, 1):
        for dj in (0, 1):
            view = (full[:, :, di::2, dj::2] if fmt == "NCHW" else full[:, di::2, dj::2, :])
            np.testing.assert_allclose(phases[2 * di + dj], view, rtol=1e-5, atol=1e-5)


def ties(fmt, dtype, seed):
    """Integer-valued, few distinct values: most windows hold a tie."""
    shape = (3, 4, 8, 6) if fmt == "NCHW" else (3, 8, 6, 4)
    return jax.random.randint(jax.random.PRNGKey(seed), shape, -2, 2).astype(dtype)


def split(x, fmt):
    return [x[:, :, di::2, dj::2] if fmt == "NCHW" else x[:, di::2, dj::2, :]
            for di in (0, 1) for dj in (0, 1)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("fmt", ["NCHW", "NHWC"])
def test_pool_of_phases_gives_ties_to_the_first_maximum(fmt, dtype):
    x = ties(fmt, dtype, 11)
    y, vjp = jax.vjp(lambda x: max_pool2d(x, 2, data_format=fmt), x)
    ct = ties(fmt, dtype, 12)[:y.shape[0], :y.shape[1], :y.shape[2], :y.shape[3]] + 3
    (want,) = vjp(ct)
    y2, vjp2 = jax.vjp(lambda x: max_pool2d_phases(*split(x, fmt)), x)
    (got,) = vjp2(ct)
    assert np.array_equal(np.asarray(y2, np.float32), np.asarray(y, np.float32))
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    # and it is not jnp.maximum's rule, which halves a tie
    _, vjp3 = jax.vjp(lambda x: jnp.max(jnp.stack(split(x, fmt)), axis=0), x)
    assert not np.array_equal(np.asarray(vjp3(ct)[0], np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("fmt", ["NCHW", "NHWC"])
def test_bf16_stem_with_ties_is_bit_equal(fmt):
    """conv -> relu -> pool on integer-valued bfloat16 small enough that every
    product and sum is exact: output and all gradients equal today's path bit
    for bit, ties included."""
    model = stem(fmt, bn=False)
    shape = (2, 3, 8, 8) if fmt == "NCHW" else (2, 8, 8, 3)
    x = jax.random.randint(jax.random.PRNGKey(1), shape, -1, 2).astype(jnp.bfloat16)
    params = ({"w": jax.random.randint(jax.random.PRNGKey(2), (8, 3, 3, 3), -1, 2)
               .astype(jnp.bfloat16)}, {}, {})
    state = ({}, {}, {})

    def run(fn):
        (y, _), vjp = jax.vjp(lambda p, x: fn(model, p, state, x, training=True), params, x)
        ct = jax.random.randint(jax.random.PRNGKey(3), y.shape, -1, 2).astype(y.dtype)
        return (y,) + tuple(jax.tree.leaves(vjp((ct, state))))

    got, want = run(seq_apply), run(layerwise)
    a = split(first_layers(model, 2, params, state, x), fmt)
    tied = sum((p == want[0]).astype(jnp.int32) for p in a) > 1
    assert float(jnp.mean(tied)) > 0.15
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))


FALL_BACKS = {
    "odd_size": dict(hw=(11, 16)),
    "odd_after_padding": dict(hw=(12, 16), kernel=2, padding=0),
    "pool_3_2_1": dict(pool=(3, 2, 1)),
    "pool_2_1_0": dict(pool=(2, 1, 0)),
    "pool_2_2_1": dict(pool=(2, 2, 1)),
    "conv_stride_2": dict(stride=2, hw=(24, 32)),
    "softmax": dict(activation="softmax"),
    "not_at_the_head": dict(lead=[Conv2DLayer(3, 1, name="conv0"),
                                  ActivationLayer("relu", name="relu0")]),
}


@pytest.mark.parametrize("case", sorted(FALL_BACKS))
def test_fall_backs_take_todays_path(case):
    kw = dict(FALL_BACKS[case])
    x = image("NCHW", hw=kw.pop("hw", (12, 16)))
    model = stem("NCHW", **kw)
    params, state = init(model, x)
    before = rewrites()
    assert (lowered(seq_apply, model, params, state, x)
            == lowered(layerwise, model, params, state, x))
    assert rewrites() == before


def test_the_pattern_lowers_differently():
    """The fall-back test's comparison can tell the two paths apart."""
    x = image("NCHW")
    model = stem("NCHW")
    params, state = init(model, x)
    assert (lowered(seq_apply, model, params, state, x)
            != lowered(layerwise, model, params, state, x))


def test_stage_cut_inside_the_head_takes_todays_path():
    model = stem("NCHW")
    x = image("NCHW")
    params, state = init(model, x)
    first, second = model.split([(0, 2), (2, 4)])
    before = rewrites()
    h, s1 = first.apply(params[:2], state[:2], x, training=True)
    y, s2 = second.apply(params[2:], state[2:], h, training=True)
    assert rewrites() == before
    want, ws = layerwise(model, params, state, x, training=True)
    np.testing.assert_array_equal(y, want)
    for g, w in zip(jax.tree.leaves(s1 + s2), jax.tree.leaves(ws)):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("name,scatters,counted", [
    ("resnet18_tiny_imagenet", 0, 1),
    ("resnet34_tiny_imagenet", 0, 1),
    ("resnet50_tiny_imagenet", 1, 0),
])
def test_train_step_lowering(name, scatters, counted):
    """ResNet-18's train step holds no select-and-scatter (the 2x2 pool's
    backward is elementwise); ResNet-50's 3/2/1 pool keeps its one."""
    model = create_model(name)
    opt = SGD(0.1)
    ts = jax.eval_shape(lambda: create_train_state(model, opt, jax.random.PRNGKey(0)))
    step = make_train_step(model, softmax_cross_entropy, opt, donate=False)
    x = jax.ShapeDtypeStruct((2, 3, 64, 64), jnp.float32)
    y = jax.ShapeDtypeStruct((2, 200), jnp.float32)
    before = rewrites()
    text = step.lower(ts, x, y, jax.random.PRNGKey(0), jnp.float32(0.1)).as_text()
    assert text.count("select_and_scatter") == scatters
    assert rewrites() - before == counted


@pytest.mark.parametrize("name", ["resnet18_tiny_imagenet", "resnet34_tiny_imagenet"])
def test_evaluation_and_serving_take_todays_path(name):
    """Nothing is gained without a backward (the forward alone reads x four
    times), so ``training=False`` is the layer-by-layer path, unfolded and
    after ``fold`` (conv -> relu -> pool also has the head's shape)."""
    from dcnn_tpu.nn.fold import fold_batchnorm
    model = create_model(name)
    params, state = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), model.input_shape))
    x = jax.ShapeDtypeStruct((2, *model.input_shape), jnp.float32)
    before = rewrites()
    assert lowered(seq_apply, model, params, state, x, training=False) \
        == lowered(layerwise, model, params, state, x, training=False)
    params, state = model.init(jax.random.PRNGKey(0), model.input_shape)
    folded, fp, fs = fold_batchnorm(model, params, state)
    assert type(folded.layers[1]) is ActivationLayer
    assert lowered(seq_apply, folded, fp, fs, x, training=False) \
        == lowered(layerwise, folded, fp, fs, x, training=False)
    assert rewrites() == before
