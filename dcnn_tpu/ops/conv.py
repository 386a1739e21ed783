"""2-D convolution ops.

Reference equivalent: the im2col→GEMM→layout-fix forward and the three
backward kernels (weight-grad GEMM, input-grad GEMM→col2im, bias reduce) in
``include/nn/layers_impl/conv2d_layer.tpp:140-241`` +
``src/nn/layers_impl/{cpu,cuda}/conv2d_ops.*``, and the cuDNN fast path
(``cudnn_conv2d_ops.cu``).

On TPU there is no im2col: ``lax.conv_general_dilated`` lowers directly onto
the MXU and XLA picks the tiling, so the whole reference kernel family
collapses to one primitive per direction. Explicit ``conv2d_weight_grad`` /
``conv2d_input_grad`` are still exported so kernel-level tests can check each
direction against autodiff (the reference tests each CUDA kernel against a
naive CPU reference the same way, SURVEY.md §4.2).

Weights are stored OIHW (reference layout) regardless of activation layout;
activations may be NCHW (API default, reference parity) or NHWC (TPU-preferred
tiling, the fast path).
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..core.precision import get_precision
from ..obs.registry import get_registry

IntOrPair = Union[int, Tuple[int, int], Sequence[int]]


def _pair(v: IntOrPair) -> Tuple[int, int]:
    if isinstance(v, int):
        return (v, v)
    a, b = v
    return (int(a), int(b))


def _dims(data_format: str) -> lax.ConvDimensionNumbers:
    if data_format == "NCHW":
        return lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1), ("NCHW", "OIHW", "NCHW"))
    if data_format == "NHWC":
        return lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1), ("NHWC", "OIHW", "NHWC"))
    raise ValueError(f"unsupported data_format {data_format!r}")


def conv2d(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    stride: IntOrPair = 1,
    padding: IntOrPair = 0,
    data_format: str = "NCHW",
) -> jax.Array:
    """Forward conv. ``w`` is OIHW; ``padding`` is symmetric int(s) like the
    reference (conv2d_layer.hpp pad_h/pad_w), not a string."""
    stride, padding = _pair(stride), _pair(padding)
    if data_format == "NHWC" and takes_pair_form(
            w.shape[1], w.shape[0], w.shape[3], stride, padding[1], x.shape[2]):
        out = _product_pair_grad(x, w, padding)
    else:
        out = _product(x, w, stride, padding, data_format)
    return _add_bias(out, b, data_format)


def _product(x: jax.Array, w: jax.Array, stride: Tuple[int, int],
             padding: Tuple[int, int], data_format: str) -> jax.Array:
    (sh, sw), (ph, pw) = stride, padding
    return lax.conv_general_dilated(
        x, w,
        window_strides=(sh, sw),
        padding=((ph, ph), (pw, pw)),
        dimension_numbers=_dims(data_format),
        precision=get_precision(),
    )


def _add_bias(out: jax.Array, b: jax.Array | None, data_format: str) -> jax.Array:
    if b is None:
        return out
    if data_format == "NCHW":
        return out + b.reshape(1, -1, 1, 1)
    return out + b.reshape(1, 1, 1, -1)


# -- the pair-of-columns form of a narrow stride-1 convolution ---------------
#
# Two adjacent output columns side by side as channels,
# ``y2[n, h, j, r*O + co] = y[n, h, 2j + r, co]``, are one product of 2O
# output channels: a window one tap wider, ``t = 0..k``, moved by two columns,
# ``y2[.., j, (r,co)] = sum w4[(r,co), ci, kh, t] * xpad[.., h + kh, 2j + t, ci]``
# with ``w4[(r,co), ci, kh, t] = w[co, ci, kh, t - r]`` (zero where that tap
# does not exist). The same dot products plus exact zeros, (k+1)/k of the
# FLOPs, and 128 channels on the product's wide side where the layer has 64,
# which is what a 128-wide systolic array wants. The input is read as it lies
# (the window's stride pairs its columns, the product's own padding pads it),
# so whatever XLA fuses into that operand stays fused; the paired side is one
# reshape of a channel-last array, and one order of bytes in XLA:TPU's
# batch-minor layout of a narrow activation (H, W, C, N from major to minor).
#
# Measured on the v5e (PERF.md, PR 31). In ResNet-18's train step (batch 2048,
# 32x32, bfloat16), ms a step, plain -> pair:
#
#   weight gradient 64->64   1.653, 1.653, 1.618 -> 1.209, 1.207, 1.187
#   weight gradient 32->64   0.814               -> 0.617
#   input gradient  64->64   1.461 with the batch-norm backward's sums and the
#                            relu mask in its output -> 1.142 bare, and those
#                            thrown out into passes of 0.7-1.2 ms
#   forward         64->64   1.107 / 1.398 with the batch-norm sums in its
#                            output -> 1.160 / 1.421 without, 0.357 for the sums
#
# So the pair form is the weight gradient's alone (its other side is a few
# kilobytes): the rule of a ``custom_vjp`` whose forward and input gradient
# are the plain product. And it is taken channel-last only: pairing the
# cotangent of an NCHW product needs a transpose, which XLA answers by writing
# the cotangent out in both orders (72.5 -> 78.8 ms a step); a narrow
# ``ResidualBlock`` therefore runs channel-last in training (nn/residual.py).
#
# The weight gradient alone (same operands, 3x3 where not said), plain -> pair:
#
#   cin->cout  64->64 1.650 -> 1.206   32->64 0.836 -> 0.632   32->32 0.838 -> 0.614
#              16->16 0.438 -> 0.293   16->32 0.431 -> 0.295   16->64 0.460 -> 0.431
#               8->64 0.440 -> 0.424    3->64 0.411 -> 0.405
#              64->32 0.836 -> 1.097  128->64 2.249 -> 2.674   64->128 1.692 -> 3.882
#   64->64 at  16x16 0.432 -> 0.330   64x64, batch 512 1.645 -> 1.122
#              batch 256 0.241 -> 0.220   5x5 4.444 -> 2.698
#
# x's channels play the batch of that product: more of them than output
# channels and the pair form loses; under 16 nothing is left to win.


def takes_pair_form(cin: int, cout: int, kernel_w: int, stride: Tuple[int, int],
                    pad_w: int, width: int) -> bool:
    """Whether a channel-last :func:`conv2d` of this geometry computes its
    weight gradient in the pair-of-columns form: stride 1, an odd window of 3
    or more with "same" padding in W, an even width (columns pair up), and
    ``16 <= cin <= cout <= 64`` (the table above)."""
    return (stride == (1, 1) and kernel_w >= 3 and kernel_w % 2 == 1
            and pad_w == (kernel_w - 1) // 2 and width % 2 == 0
            and 16 <= cin <= cout <= 64)


def _pair_weights(w: jax.Array) -> jax.Array:
    """``w[O, I, kh, k] -> w4[2O, I, kh, k+1]``: ``w`` for the even column of
    a pair, ``w`` moved one tap on for the odd one. Linear in ``w``; its
    transpose folds a gradient of ``w4`` back onto ``w`` (each tap is the sum
    of two entries)."""
    at = lambda r: jnp.pad(w, ((0, 0), (0, 0), (0, 0), (r, 1 - r)))  # noqa: E731
    return jnp.concatenate([at(0), at(1)], axis=0)


def _pair_weight_grad(x: jax.Array, dy: jax.Array, w: jax.Array, ph: int) -> jax.Array:
    """dL/dw of the channel-last ``conv2d(x, w, stride=1, padding=(ph,
    (k-1)/2))`` through the pair form: ``dw4[2O, C, kh, k+1]`` accumulated and
    returned in (at least) float32, folded onto ``w``'s taps, and only then
    rounded to ``w``'s dtype, so that each element is rounded once, as the
    plain product's is."""
    get_registry().counter(
        "nn_conv_pair_products_total",
        "weight gradients of traced programs computed in the pair-of-columns "
        "form (one per narrow stride-1 convolution of a training program)").inc()
    pw = (w.shape[3] - 1) // 2
    n, h, wd, o = dy.shape
    # The pair product's transpose in w4, written out because JAX's own rule
    # would return w4's dtype: N is contracted (the feature dimension of both
    # operands), x's channels play the batch, and the window's stride turns
    # into a dilation of dy2, which plays the kernel.
    acc = jnp.promote_types(w.dtype, jnp.float32)
    dw4 = lax.conv_general_dilated(
        x, dy.reshape(n, h, wd // 2, 2 * o),
        window_strides=(1, 1),
        padding=((ph, ph), (pw, pw)),
        rhs_dilation=(1, 2),
        dimension_numbers=lax.ConvDimensionNumbers((3, 0, 1, 2), (3, 0, 1, 2), (1, 0, 2, 3)),
        precision=get_precision(),
        preferred_element_type=acc,
    )
    (dw,) = jax.linear_transpose(_pair_weights, jax.ShapeDtypeStruct(w.shape, acc))(dw4)
    return dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _product_pair_grad(x: jax.Array, w: jax.Array, padding: Tuple[int, int]) -> jax.Array:
    """The plain channel-last stride-1 product where :func:`takes_pair_form`
    holds: its weight gradient takes the pair form."""
    return _product(x, w, (1, 1), padding, "NHWC")


def _product_pair_grad_fwd(x, w, padding):
    return _product_pair_grad(x, w, padding), (x, w)


def _product_pair_grad_bwd(padding, res, dy):
    x, w = res
    (dx,) = jax.linear_transpose(lambda x_: _product(x_, w, (1, 1), padding, "NHWC"),
                                 jax.ShapeDtypeStruct(x.shape, x.dtype))(dy)
    return dx, _pair_weight_grad(x, dy, w, padding[0])


_product_pair_grad.defvjp(_product_pair_grad_fwd, _product_pair_grad_bwd)


def conv2d_pool_phases(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    padding: IntOrPair = 0,
    data_format: str = "NCHW",
) -> List[jax.Array]:
    """The stride-1 :func:`conv2d` computed once per position of a 2x2/2
    pooling window: four stride-2 products, ``phases[2*di + dj]`` holding
    ``conv2d(x, w, b)[..., di::2, dj::2]`` (row-major window order, the order
    ``max_pool2d_phases`` takes). The conv's output size must be even.

    From ``out[2i+di] = sum_u w[u] * x[2i+di+u-p]``: a stride-2 product whose
    low padding is ``p-di`` starts at the same tap, and ``p-1+di`` on the high
    side is the least that reaches the last one. Same dot products and FLOPs
    as the stride-1 conv, no zero taps, and nothing downstream of the phases
    ever has to interleave them: a 2x2 pool (and its backward) over them is
    elementwise.
    """
    ph, pw = _pair(padding)
    phases = []
    for di in (0, 1):
        for dj in (0, 1):
            out = lax.conv_general_dilated(
                x, w,
                window_strides=(2, 2),
                padding=((ph - di, ph - 1 + di), (pw - dj, pw - 1 + dj)),
                dimension_numbers=_dims(data_format),
                precision=get_precision(),
            )
            phases.append(_add_bias(out, b, data_format))
    return phases


def conv2d_int8(
    x_q: jax.Array,
    w_q: jax.Array,
    *,
    stride: IntOrPair = 1,
    padding: IntOrPair = 0,
    data_format: str = "NCHW",
) -> jax.Array:
    """int8 × int8 → int32 convolution on the MXU's int8 path (~2× the bf16
    peak on v5e; measured in ``benchmarks/bench_int8.py``). Same geometry
    contract as :func:`conv2d` (OIHW weights, symmetric int padding); the
    caller owns the scales — dequantization is a per-channel multiply on the
    int32 output (``nn/quantize.py``). No ``precision`` arg: precision
    selects float MXU passes and does not apply to integer convs."""
    if x_q.dtype != jnp.int8 or w_q.dtype != jnp.int8:
        raise TypeError(f"conv2d_int8 expects int8 operands, got "
                        f"{x_q.dtype}/{w_q.dtype}")
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    return lax.conv_general_dilated(
        x_q, w_q,
        window_strides=(sh, sw),
        padding=((ph, ph), (pw, pw)),
        dimension_numbers=_dims(data_format),
        preferred_element_type=jnp.int32,
    )


def conv2d_weight_grad(
    x: jax.Array,
    grad_out: jax.Array,
    kernel_hw: Tuple[int, int],
    *,
    stride: IntOrPair = 1,
    padding: IntOrPair = 0,
    data_format: str = "NCHW",
) -> jax.Array:
    """dL/dW — reference ``compute_weight_gradients``
    (``src/nn/layers_impl/cpu/conv2d_ops.cpp``). Implemented via the
    transpose rule of the forward conv so numerics match autodiff exactly."""
    kh, kw = kernel_hw
    c_axis = 1 if data_format == "NCHW" else 3
    cin = x.shape[c_axis]
    cout = grad_out.shape[c_axis]
    w_shape = (cout, cin, kh, kw)
    _, vjp = jax.vjp(
        lambda w: conv2d(x, w, None, stride=stride, padding=padding, data_format=data_format),
        jnp.zeros(w_shape, x.dtype),
    )
    return vjp(grad_out)[0]


def conv2d_input_grad(
    w: jax.Array,
    grad_out: jax.Array,
    input_shape: Tuple[int, ...],
    *,
    stride: IntOrPair = 1,
    padding: IntOrPair = 0,
    data_format: str = "NCHW",
) -> jax.Array:
    """dL/dX — reference ``compute_input_gradients`` (GEMM→col2im)."""
    _, vjp = jax.vjp(
        lambda x: conv2d(x, w, None, stride=stride, padding=padding, data_format=data_format),
        jnp.zeros(input_shape, w.dtype),
    )
    return vjp(grad_out)[0]


def conv2d_bias_grad(grad_out: jax.Array, *, data_format: str = "NCHW") -> jax.Array:
    """dL/db — reference ``compute_bias_gradients`` (reduce over N,H,W)."""
    axes = (0, 2, 3) if data_format == "NCHW" else (0, 1, 2)
    return jnp.sum(grad_out, axis=axes)


def conv2d_output_shape(
    input_hw: Tuple[int, int],
    kernel_hw: Tuple[int, int],
    stride: IntOrPair = 1,
    padding: IntOrPair = 0,
) -> Tuple[int, int]:
    """Spatial output size, same formula as the reference
    ``compute_output_shape`` (conv2d_layer.hpp)."""
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    h = (input_hw[0] + 2 * ph - kernel_hw[0]) // sh + 1
    w = (input_hw[1] + 2 * pw - kernel_hw[1]) // sw + 1
    return (h, w)
