"""The Pallas kernels of a chunk's inside (``ops/delta_rule.py``) through the
interpreter on the CPU, at the cell's head geometry (chunks of 64, keys and
values 128 wide; 2 heads, 256 positions): against the recurrence position by
position and against XLA's products, which every other geometry and backend
takes; and which of the two a layer takes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcnn_tpu.core.precision import get_precision_mode, set_precision
from dcnn_tpu.nn.delta_attention import DeltaAttentionLayer
from dcnn_tpu.obs import get_registry
from dcnn_tpu.ops.delta_rule import (chunked_gated_delta_rule, gated_delta_rule_by_token,
                                     takes_kernel)

GRADS = ("q", "k", "v", "g", "beta")


@pytest.fixture(autouse=True)
def parity():
    before = get_precision_mode()
    set_precision("parity")
    yield
    set_precision(before)


def close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) <= tol * max(np.linalg.norm(b), 1e-30)


def inputs(rng, s, strength, dtype=jnp.float32, d=128, dv=128, lead=(2,)):
    q, k = (jnp.asarray(rng.normal(size=(*lead, s, d)), jnp.float32) for _ in range(2))
    q, k = (a / jnp.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = jnp.asarray(rng.normal(size=(*lead, s, dv)), jnp.float32)
    g = -strength * jnp.asarray(rng.uniform(0, 1, size=(*lead, s, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, size=(*lead, s)), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def with_gradients(f, args, pull):
    def both(*a):
        out, pullback = jax.vjp(lambda *b: f(*b).astype(jnp.float32), *a)
        return out, pullback(pull)
    return jax.jit(both)(*args)


def kernels(*a, chunk=64):
    return chunked_gated_delta_rule(*a, chunk=chunk, interpret=True)


def products(*a, chunk=64):
    return chunked_gated_delta_rule(*a, chunk=chunk)


@pytest.mark.parametrize("strength", [1e-3, 1.0, 30.0])
@pytest.mark.parametrize("dtype,to_token,to_products",
                         [(jnp.float32, 1e-5, 5e-5), (jnp.bfloat16, 1.2e-2, 1.2e-2)],
                         ids=["float32", "bfloat16"])
def test_kernels_are_the_recurrence_and_xlas_products(rng, strength, dtype, to_token,
                                                      to_products):
    """The output and all five gradients. In float32 the kernels are nearer
    the recurrence than XLA's products are where the decay is strong (their
    exponents are sums of ``g``, not differences of cumulative sums)."""
    args = inputs(rng, 256, strength, dtype)
    pull = jnp.asarray(rng.normal(size=args[2].shape), jnp.float32)
    assert not takes_kernel(64, 128, 128)                  # on the CPU nothing but interpret does
    (out, grads), (xla, xla_grads), (token, token_grads) = (
        with_gradients(f, args, pull) for f in (kernels, products, gated_delta_rule_by_token))
    assert out.dtype == jnp.float32 and np.isfinite(np.asarray(out)).all()
    assert close(out, token, to_token) and close(out, xla, to_products)
    for name, got, want, other in zip(GRADS, grads, token_grads, xla_grads):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert close(got, want, to_token), name
        assert close(got, other, to_products), name


def test_kernels_where_exp_of_minus_g_overflows(rng):
    """A decay of 40 a position: ``exp(-G)`` leaves float32 within three
    positions of a chunk, and no factor of the kernels exceeds 1."""
    args = inputs(rng, 128, 40.0)
    assert not np.isfinite(np.asarray(jnp.exp(-jnp.cumsum(args[3][..., :64, :], axis=-2)))).all()
    pull = jnp.asarray(rng.normal(size=args[2].shape), jnp.float32)
    (out, grads), (token, token_grads) = (
        with_gradients(f, args, pull) for f in (kernels, gated_delta_rule_by_token))
    assert close(out, token, 1e-5)
    for name, got, want in zip(GRADS, grads, token_grads):
        assert np.isfinite(np.asarray(got)).all(), name
        assert close(got, want, 1e-5), name


def test_kernels_where_every_key_is_nearly_the_same(rng):
    """The triangular system at its worst: keys within a hundredth of one
    direction, beta 0.98, hardly any decay."""
    q, k, v, g, beta = inputs(rng, 128, 1e-3)
    k = jnp.broadcast_to(k[..., :1, :], k.shape) + 0.01 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jnp.full_like(beta, 0.98)
    pull = jnp.asarray(rng.normal(size=v.shape), jnp.float32)
    (out, grads), (token, token_grads) = (
        with_gradients(f, (q, k, v, g, beta), pull) for f in (kernels, gated_delta_rule_by_token))
    assert close(out, token, 2e-5)
    for name, got, want in zip(GRADS, grads, token_grads):
        assert close(got, want, 2e-4), name


@pytest.mark.parametrize("positions", [200, 600])
def test_kernels_on_a_sequence_that_is_no_multiple_of_the_chunk(rng, positions):
    """200 positions: four chunks, the last ragged, one program. 600: ten
    chunks, padded to two programs of eight."""
    args = inputs(rng, positions, 1.0, lead=(1, 2))
    pull = jnp.asarray(rng.normal(size=args[2].shape), jnp.float32)
    (out, grads), (token, token_grads) = (
        with_gradients(f, args, pull) for f in (kernels, gated_delta_rule_by_token))
    assert out.shape == token.shape and close(out, token, 1e-5)
    for name, got, want in zip(GRADS, grads, token_grads):
        assert got.shape == want.shape and close(got, want, 1e-5), name


def test_kernels_at_values_wider_than_keys_and_chunks_of_32(rng):
    args = inputs(rng, 96, 1.0, dv=256)
    run = lambda f: jax.jit(lambda *a: f(*a, chunk=32))(*args)  # noqa: E731
    assert close(run(kernels), jax.jit(gated_delta_rule_by_token)(*args), 1e-5)


def _kernel_calls(f, *args):
    return str(jax.make_jaxpr(f)(*args)).count("pallas_call")


def test_a_geometry_the_kernels_refuse_takes_xlas_products(rng):
    """Heads 16 wide, or chunks of 8: no kernel, even where one is asked
    for; and the layer's counter says which path its program took."""
    assert not takes_kernel(64, 16, 128, True) and not takes_kernel(64, 128, 20, True)
    assert not takes_kernel(8, 128, 128, True) and not takes_kernel(128, 128, 128, True)
    assert takes_kernel(64, 128, 128, True) and takes_kernel(16, 256, 128, True)
    narrow = inputs(rng, 64, 1.0, d=16, dv=20)
    assert _kernel_calls(lambda *a: kernels(*a), *narrow) == 0
    wide = inputs(rng, 64, 1.0)
    assert _kernel_calls(lambda *a: kernels(*a, chunk=8), *wide) == 0
    assert _kernel_calls(lambda *a: kernels(*a), *wide) == 1
    assert _kernel_calls(lambda *a: products(*a), *wide) == 0
    assert close(jax.jit(lambda *a: kernels(*a, chunk=8))(*wide),
                 jax.jit(gated_delta_rule_by_token)(*wide), 2e-5)


def test_the_layers_counter_reads_the_layers_that_took_the_kernels(monkeypatch):
    """0 off the TPU and for a geometry the kernels refuse; one a KDA layer
    of a traced program where they engage (the backend answered for)."""
    kernel_total = lambda: get_registry().snapshot().get("nn_kda_kernel_total", 0)  # noqa: E731
    chunked_total = lambda: get_registry().snapshot().get("nn_kda_chunked_total", 0)  # noqa: E731

    def trace(layer, hidden=64):
        params, state = layer.init(jax.random.PRNGKey(0), (1, 128, hidden))
        x = jnp.zeros((1, 128, hidden), jnp.float32)
        return _kernel_calls(lambda p: layer.apply(p, state, x)[0], params)

    cell = DeltaAttentionLayer(2, 128, chunk=64, name="l0.kda")
    tiny = DeltaAttentionLayer(2, 16, chunk=8, name="l0.kda")
    kernels_before, chunked_before = kernel_total(), chunked_total()
    assert trace(cell) == 0 and trace(tiny) == 0
    assert kernel_total() == kernels_before and chunked_total() == chunked_before + 2
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trace(cell) == 1 and trace(cell) == 1 and trace(tiny) == 0
    assert kernel_total() == kernels_before + 2 and chunked_total() == chunked_before + 5
