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

from typing import List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..core.precision import get_precision

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
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    out = lax.conv_general_dilated(
        x, w,
        window_strides=(sh, sw),
        padding=((ph, ph), (pw, pw)),
        dimension_numbers=_dims(data_format),
        precision=get_precision(),
    )
    return _add_bias(out, b, data_format)


def _add_bias(out: jax.Array, b: jax.Array | None, data_format: str) -> jax.Array:
    if b is None:
        return out
    if data_format == "NCHW":
        return out + b.reshape(1, -1, 1, 1)
    return out + b.reshape(1, 1, 1, -1)


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
