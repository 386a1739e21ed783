"""The language-model path's kernels compiled at the cell's real shapes for
a v5e that is described, not attached (no chip time, nothing runs): what the
Pallas interpreter cannot refuse, the chip's compiler does (tiling, VMEM).
All such compiles live in this one file, behind fixtures: only the process
that runs these tests loads the TPU's library, and only once a test asks.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """An executable for a described chip can be written to the persistent
    cache and never read back: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_kernels_compile_at_the_latent_attention_shapes(one_chip):
    """4 sequences x 16 heads x 4096, scores at 192, values at 128, the
    layer's tiles: the forward kernel and the fused backward kernel."""
    from dcnn_tpu.ops.attention import _flash_attention

    def loss(q, k, v):
        o = _flash_attention(q, k, v, True, 512, 1024, 0.1147, False)
        return jnp.sum(o.astype(jnp.float32))

    q = _spec((4, 16, 4096, 192), one_chip)
    v = _spec((4, 16, 4096, 128), one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, v).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("form,kernels", [("fused", 1), ("two_kernels", 2)])
@pytest.mark.parametrize("heads", [16, 32])
def test_flash_backward_compiles_at_both_language_models_shapes(one_chip, monkeypatch,
                                                                heads, form, kernels):
    """The backward alone at 4 sequences x 16 (DeepSeek-V2-Lite) and x 32
    (Kimi-Linear) heads x 4096, 192 | 128: the fused kernel within the VMEM
    limit it asks for, and the two kernels a longer sequence would take."""
    import importlib
    attention = importlib.import_module("dcnn_tpu.ops.attention")
    if form == "two_kernels":
        monkeypatch.setattr(attention, "_fused_backward_fits", lambda sq_p, d: False)

    def backward(q, k, v, o, lse, g):
        return attention._flash_backward(q, k, v, o, lse, g, causal=True, block_q=512,
                                         block_kv=1024, scale=0.1147, interpret=False)

    wide, narrow = _spec((4, heads, 4096, 192), one_chip), _spec((4, heads, 4096, 128), one_chip)
    lse = _spec((4, heads, 4096), one_chip, jnp.float32)
    text = jax.jit(backward).lower(wide, wide, narrow, narrow, lse, narrow).compile().as_text()
    assert text.count("tpu_custom_call") == kernels


def test_grouped_product_compiles_at_the_expert_layers_shapes(one_chip):
    """98,304 pair rows, 8 held experts, 2048 -> 1408 and back, with the
    input and weight gradients (the kernel's two transposes)."""
    from dcnn_tpu.ops.grouped import grouped_matmul

    def loss(x, up, down, sizes):
        h = grouped_matmul(x, up, sizes, interpret=False)
        return jnp.sum(grouped_matmul(h, down, sizes, interpret=False).astype(jnp.float32))

    args = (_spec((98304, 2048), one_chip), _spec((8, 2048, 1408), one_chip),
            _spec((8, 1408, 2048), one_chip), _spec((8,), one_chip, jnp.int32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 5     # up; then two transposes of each


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kda_kernels_compile_at_the_layers_shapes(one_chip, dtype):
    """One sequence's 32 heads x 4096 positions, keys and values 128 wide,
    chunks of 64: the kernel of a chunk's inside and its backward kernel."""
    from dcnn_tpu.ops.delta_rule import _inside_kernels

    def loss(*a):
        return sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in _inside_kernels(*a, 64, False))

    wide = _spec((32, 4096, 128), one_chip, dtype)
    args = (wide, wide, wide, _spec((32, 4096, 128), one_chip, jnp.float32),
            _spec((32, 1, 4096), one_chip, jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("what,kernels", [("scan", 2), ("rule", 4)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kda_scan_kernels_compile_at_the_layers_shapes(one_chip, dtype, what, kernels):
    """The scan over one sequence's 64 chunks of 64, 32 heads, 128 wide: its
    forward kernel (keeping the states the chunks start from) and its
    backward kernel alone, and the whole rule with the inside's two."""
    from dcnn_tpu.ops.delta_rule import _scan_kernels, chunked_gated_delta_rule

    if what == "scan":
        def loss(*six):
            return jnp.sum(_scan_kernels(*six, dtype, False).astype(jnp.float32) ** 2)
        args = (_spec((64, 32, 64, 128), one_chip, dtype),
                _spec((64, 32, 64, 128), one_chip, jnp.float32),
                _spec((64, 32, 64, 128), one_chip, dtype), _spec((64, 32, 64, 64), one_chip, dtype),
                _spec((64, 32, 64, 128), one_chip, dtype),
                _spec((32, 64, 128), one_chip, jnp.float32))
    else:
        def loss(*a):
            o = chunked_gated_delta_rule(*a, chunk=64, interpret=False)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        wide = _spec((1, 32, 4096, 128), one_chip, dtype)
        args = (wide, wide, wide, _spec((1, 32, 4096, 128), one_chip, jnp.float32),
                _spec((1, 32, 4096), one_chip, jnp.float32))
    grad = jax.grad(loss, argnums=tuple(range(len(args))))
    text = jax.jit(grad).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == kernels
