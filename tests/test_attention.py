"""Attention ops + sequence-parallelism tests.

Strategy mirrors the reference's kernel-test pattern (SURVEY.md §4.2): run
the optimised implementation, compare against the naive materialising oracle
elementwise. Ring/Ulysses run on the 8-virtual-device CPU mesh from conftest
and must match single-device full attention exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcnn_tpu.core.mesh import make_mesh, SEQ_AXIS
from dcnn_tpu.nn import MultiHeadAttentionLayer, SequentialBuilder
from dcnn_tpu.nn.factory import layer_from_config
from dcnn_tpu.ops.attention import (
    attention, blockwise_attention, flash_attention,
)
from dcnn_tpu.parallel import (
    make_ring_attention, make_ulysses_attention, shard_sequence,
)


def _qkv(rng, b=2, h=4, s=64, d=16):
    q = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_naive(rng, causal):
    q, k, v = _qkv(rng)
    ref = attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_kv=16)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_blockwise_unpadded_block_edge(rng):
    # kv length not a multiple of the block: padding mask must zero the tail
    q, k, v = _qkv(rng, s=50)
    ref = attention(q, k, v)
    out = blockwise_attention(q, k, v, block_kv=16)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_arbitrary_mask_matches_naive(rng, causal):
    """Padding/segment masks on the memory-efficient path (ADVICE r1 #4)."""
    q, k, v = _qkv(rng, s=48)
    # per-batch key-padding mask: batch 0 attends to first 33 keys only
    kmask = np.ones((2, 1, 1, 48), bool)
    kmask[0, ..., 33:] = False
    kmask = jnp.asarray(kmask)
    ref = attention(q, k, v, causal=causal, mask=kmask)
    out = blockwise_attention(q, k, v, causal=causal, block_kv=16, mask=kmask)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    # flash routes masked calls to blockwise (Pallas kernel is causal-only)
    out_f = flash_attention(q, k, v, causal=causal, mask=kmask)
    np.testing.assert_allclose(out_f, ref, atol=1e-5, rtol=1e-5)


def test_fully_masked_rows_return_zero(rng):
    """Oracle and blockwise agree: zero output for fully-masked rows."""
    q, k, v = _qkv(rng, s=32)
    mask = np.ones((1, 1, 32, 32), bool)
    mask[..., 5, :] = False                     # query 5 attends to nothing
    mask = jnp.asarray(mask)
    ref = attention(q, k, v, mask=mask)
    out = blockwise_attention(q, k, v, block_kv=16, mask=mask)
    np.testing.assert_array_equal(np.asarray(ref[:, :, 5]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[:, :, 5]), 0.0)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_blockwise_mask_validation(rng):
    q, k, v = _qkv(rng, s=32)
    with pytest.raises(ValueError, match="mask last dim"):
        blockwise_attention(q, k, v, mask=jnp.ones((1, 1, 32, 7), bool))


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_gradients_match_naive(rng, causal):
    q, k, v = _qkv(rng, b=1, h=2, s=24, d=8)

    def loss_ref(q, k, v):
        return jnp.sum(attention(q, k, v, causal=causal) ** 2)

    def loss_blk(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, causal=causal,
                                           block_kv=8) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_naive(rng, causal):
    q, k, v = _qkv(rng, s=48)
    ref = attention(q, k, v, causal=causal)
    # interpret=True: exercise the Pallas kernel itself on CPU (without it
    # the off-TPU path falls back to blockwise_attention)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_kv=16,
                          interpret=jax.default_backend() != "tpu")
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_flash_gradients_match_naive(rng):
    q, k, v = _qkv(rng, b=1, h=2, s=32, d=8)

    g_ref = jax.grad(lambda *a: jnp.sum(attention(*a) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, block_q=16, block_kv=16,
                        interpret=jax.default_backend() != "tpu") ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def _attention_module():
    # `dcnn_tpu.ops.attention` the attribute is the function that ops/__init__
    # re-exports; the module itself comes from importlib
    import importlib
    return importlib.import_module("dcnn_tpu.ops.attention")


@pytest.fixture(params=["fused", "two_kernels"])
def backward_form(request, monkeypatch):
    """Each Pallas backward form on the same cases: the fused kernel where
    one row's dQ fits (every shape here), the two kernels where the rule is
    made to say it does not."""
    if request.param == "two_kernels":
        monkeypatch.setattr(_attention_module(), "_fused_backward_fits",
                            lambda sq_p, d: False)
    return request.param


@pytest.mark.parametrize("causal,sq,sk,d,dv", [
    (True, 40, 40, 8, 8),      # padding: 40 % 16 != 0
    (False, 24, 56, 8, 8),     # cross-attention, Sq != Sk, both padded
    (True, 48, 32, 8, 8),      # Sq > Sk: leading causal rows fully masked
    (True, 64, 64, 24, 16),    # latent attention's geometry scaled down: d != dv
])
def test_flash_pallas_backward_cases(rng, backward_form, causal, sq, sk, d, dv):
    """The Pallas backward, in either form, vs the materialising oracle:
    padding, cross-attention shapes, fully-masked rows (whose lse is
    ~NEG_INF — the backward must mask P explicitly, never via exp), and
    values narrower than the scores."""
    b, h = 2, 2
    q = jnp.asarray(rng.normal(size=(b, h, sq, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, h, sk, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, h, sk, dv)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(b, h, sq, dv)).astype(np.float32))

    g_ref = jax.grad(lambda *a: jnp.sum(attention(*a, causal=causal) * w),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, causal=causal, block_q=16, block_kv=16,
                        interpret=jax.default_backend() != "tpu") * w),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=1e-4)


def test_flash_pallas_backward_bf16(rng, backward_form):
    """bf16 inputs: fp32 accumulators inside the kernels keep gradients close
    to the fp32 oracle (bf16-level tolerance), in either backward form."""
    q, k, v = _qkv(rng, b=1, h=2, s=32, d=8)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))

    g_ref = jax.grad(lambda *a: jnp.sum(attention(*a, causal=True) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, causal=True, block_q=16, block_kv=16,
                        interpret=jax.default_backend() != "tpu"
                        ).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(qb, kb, vb)
    for a, b_ in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b_, np.float32), a,
                                   atol=0.15, rtol=0.1)


def _backward(q, k, v, w, *, causal=True, block_q=16, block_kv=32):
    """dQ, dK, dV of ``sum(flash(q, k, v) * w)`` by the Pallas kernels."""
    return jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, causal=causal, block_q=block_q, block_kv=block_kv,
                        interpret=True) * w), argnums=(0, 1, 2))(q, k, v)


def test_flash_backward_forms_agree(rng, monkeypatch):
    """The fused kernel and the two kernels on the same inputs: each product
    is accumulated in the same order, so they agree to float32 rounding."""
    A = _attention_module()
    q, k = (jnp.asarray(rng.normal(size=(1, 2, 72, 24)).astype(np.float32))
            for _ in range(2))
    v, w = (jnp.asarray(rng.normal(size=(1, 2, 72, 16)).astype(np.float32))
            for _ in range(2))
    fused = _backward(q, k, v, w)
    monkeypatch.setattr(A, "_fused_backward_fits", lambda sq_p, d: False)
    for a, b_ in zip(fused, _backward(q, k, v, w)):
        np.testing.assert_allclose(a, b_, atol=1e-6, rtol=1e-6)


def test_flash_backward_form_is_decided_by_one_rows_dq(rng, monkeypatch):
    """The fused form wherever one row's fp32 dQ fits its budget — both
    language models' 4096 x 192 — and the two kernels past it, by shape
    alone: one backward kernel in the traced program, or two."""
    A = _attention_module()
    assert A._fused_backward_fits(4096, 192)
    assert A._fused_backward_fits(16384, 128)
    assert not A._fused_backward_fits(32768, 128)
    assert not A._fused_backward_fits(8192, 512)
    q, k, v, w = (jnp.asarray(rng.normal(size=(1, 1, 64, 24)).astype(np.float32))
                  for _ in range(4))

    def kernels():
        return str(jax.make_jaxpr(lambda *a: _backward(*a))(q, k, v, w)
                   ).count("pallas_call")
    assert kernels() == 2                                # forward + fused
    monkeypatch.setattr(A, "_FUSED_DQ_BYTES", 64 * 24 * 4 - 1)
    assert kernels() == 3                                # forward + dQ + dK/dV


def test_fused_backward_counter_counts_traced_fused_backwards(rng):
    """``nn_flash_fused_bwd_total``: 0 for flash_attention's blockwise form
    off the TPU, one for each backward traced through the fused kernel."""
    from dcnn_tpu.obs import get_registry
    fused_total = lambda: get_registry().snapshot().get(  # noqa: E731
        "nn_flash_fused_bwd_total", 0)
    q, k, v = _qkv(rng, b=1, h=2, s=32, d=8)
    before = fused_total()
    jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, causal=True))))(q)
    if jax.default_backend() != "tpu":
        assert fused_total() == before
    before = fused_total()
    for _ in range(2):
        jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=16, block_kv=16, interpret=True))))(q)
    assert fused_total() == before + 2


def test_flash_off_tpu_defaults_to_blockwise(rng, monkeypatch):
    """ADVICE r1 (medium): off-TPU without explicit interpret, flash must
    route to the exact blockwise path, never the Pallas interpreter."""
    A = _attention_module()
    q, k, v = _qkv(rng, b=1, h=1, s=16, d=8)
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU routing test")
    calls = {}
    real = A.blockwise_attention

    def spy(*a, **kw):
        calls["hit"] = True
        return real(*a, **kw)

    monkeypatch.setattr(A, "blockwise_attention", spy)
    A.flash_attention(q, k, v)
    assert calls.get("hit")


def test_blockwise_bf16_accumulates_fp32(rng):
    """ADVICE r1: bf16 inputs must produce near-fp32-quality softmax output
    (state carried in fp32), and output dtype matches input dtype."""
    q, k, v = _qkv(rng, b=1, h=2, s=64, d=8)
    ref = attention(q, k, v, causal=True)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = blockwise_attention(qb, kb, vb, causal=True, block_kv=16)
    assert out.dtype == jnp.bfloat16
    # tolerance dominated by the bf16 *inputs*, not the accumulator
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               atol=5e-2, rtol=5e-2)


# ---------------------------------------------------------------------------
# sequence parallelism over the 8-device mesh
# ---------------------------------------------------------------------------

@pytest.fixture
def seq_mesh():
    return make_mesh((8,), (SEQ_AXIS,))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(rng, seq_mesh, causal):
    q, k, v = _qkv(rng, b=2, h=2, s=64, d=8)
    ref = attention(q, k, v, causal=causal)
    ring = make_ring_attention(seq_mesh, causal=causal)
    qs, ks, vs = shard_sequence((q, k, v), seq_mesh)
    out = ring(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-5)


def test_ring_attention_grads_match_full(rng, seq_mesh):
    q, k, v = _qkv(rng, b=1, h=2, s=32, d=8)
    ring = make_ring_attention(seq_mesh, causal=True)

    g_ref = jax.grad(lambda *a: jnp.sum(attention(*a, causal=True) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(lambda *a: jnp.sum(ring(*a) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), a, atol=1e-4, rtol=1e-4)


def test_zigzag_ring_matches_full(rng, seq_mesh):
    from dcnn_tpu.parallel import (make_zigzag_ring_attention,
                                   zigzag_permutation, zigzag_shard)

    q, k, v = _qkv(rng, b=2, h=2, s=64, d=8)
    ref = attention(q, k, v, causal=True)
    n = seq_mesh.shape["seq"]
    zz = make_zigzag_ring_attention(seq_mesh)
    qs, ks, vs = zigzag_shard((q, k, v), seq_mesh)
    out_zz = zz(qs, ks, vs)
    inv = jnp.argsort(zigzag_permutation(64, n))
    out = jnp.take(out_zz, inv, axis=2)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-5)


def test_zigzag_ring_grads_match_full(rng, seq_mesh):
    from dcnn_tpu.parallel import (make_zigzag_ring_attention,
                                   zigzag_permutation)

    q, k, v = _qkv(rng, b=1, h=2, s=32, d=8)
    n = seq_mesh.shape["seq"]
    perm = zigzag_permutation(32, n)
    inv = jnp.argsort(perm)
    zz = make_zigzag_ring_attention(seq_mesh)

    def loss_zz(q, k, v):
        out = zz(jnp.take(q, perm, 2), jnp.take(k, perm, 2),
                 jnp.take(v, perm, 2))
        return jnp.sum(jnp.take(out, inv, 2) ** 2)

    g_ref = jax.grad(lambda *a: jnp.sum(attention(*a, causal=True) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    g_zz = jax.grad(loss_zz, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_zz):
        np.testing.assert_allclose(np.asarray(b), a, atol=1e-4, rtol=1e-4)


def test_zigzag_balance_property():
    """The zigzag layout's reason to exist: live (unmasked) chunk-pairs per
    device are equal across the ring — the plain causal ring's live-round
    count is i+1 (maximally imbalanced)."""
    for n in (2, 4, 8):
        live = []
        for i in range(n):
            cnt = 0
            for t in range(n):
                src = (i - t) % n
                for off_q in (i, 2 * n - 1 - i):
                    for off_k in (src, 2 * n - 1 - src):
                        if off_k <= off_q:   # chunk-level any-allowed
                            cnt += 1
            live.append(cnt)
        assert len(set(live)) == 1, (n, live)
        assert live[0] == 2 * n + 1


def test_zigzag_ring_validation(rng, seq_mesh):
    from dcnn_tpu.parallel import make_zigzag_ring_attention

    q, k, v = _qkv(rng, s=24)   # 24 % 16 != 0
    with pytest.raises(ValueError, match="divisible"):
        make_zigzag_ring_attention(seq_mesh)(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(rng, seq_mesh, causal):
    q, k, v = _qkv(rng, b=2, h=8, s=64, d=8)  # heads divisible by 8
    ref = attention(q, k, v, causal=causal)
    uly = make_ulysses_attention(seq_mesh, causal=causal)
    qs, ks, vs = shard_sequence((q, k, v), seq_mesh)
    out = uly(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-5)


def test_ulysses_grads_match_full(rng, seq_mesh):
    """Gradients through Ulysses: custom-VJP flash kernels (forced Pallas
    interpreter off-TPU) composed with all_to_all's transpose rule — the
    exact composition TPU training runs (review r3 finding)."""
    q, k, v = _qkv(rng, b=1, h=8, s=32, d=8)
    interp = jax.default_backend() != "tpu"
    uly = make_ulysses_attention(seq_mesh, causal=True, interpret=interp)

    g_ref = jax.grad(lambda *a: jnp.sum(attention(*a, causal=True) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.grad(lambda *a: jnp.sum(uly(*a) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_uly):
        np.testing.assert_allclose(np.asarray(b), a, atol=1e-4, rtol=1e-4)


def test_ulysses_rejects_indivisible_heads(rng, seq_mesh):
    q, k, v = _qkv(rng, h=3)
    with pytest.raises(ValueError, match="divisible"):
        make_ulysses_attention(seq_mesh)(q, k, v)


def test_ring_rejects_indivisible_sequence(rng, seq_mesh):
    """ADVICE r1: uneven sequence shards must fail with a clear error, not
    an opaque shard_map one."""
    q, k, v = _qkv(rng, s=60)  # 60 % 8 != 0
    with pytest.raises(ValueError, match="divisible"):
        make_ring_attention(seq_mesh)(q, k, v)


# ---------------------------------------------------------------------------
# MultiHeadAttention layer
# ---------------------------------------------------------------------------

def test_mha_layer_impls_agree(rng):
    x = jnp.asarray(rng.normal(size=(2, 32, 64)).astype(np.float32))
    key = jax.random.PRNGKey(0)
    outs = {}
    for impl in ("naive", "blockwise", "flash"):
        layer = MultiHeadAttentionLayer(num_heads=4, impl=impl, causal=True)
        params, state = layer.init(key, (32, 64))
        outs[impl], _ = layer.apply(params, state, x)
    np.testing.assert_allclose(outs["blockwise"], outs["naive"],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(outs["flash"], outs["naive"],
                               atol=1e-5, rtol=1e-5)


def test_mha_classifier_trains_end_to_end(rng):
    """Zoo MHA model through the full Trainer stack: an attention-friendly
    synthetic task (class = position of the marked token) must reach >90%
    train accuracy in a few epochs (the verify-recipe gate)."""
    from dcnn_tpu.core.config import TrainingConfig
    from dcnn_tpu.data import ArrayDataLoader
    from dcnn_tpu.models import create_mha_classifier
    from dcnn_tpu.optim import Adam
    from dcnn_tpu.train import Trainer
    from dcnn_tpu.train.trainer import create_train_state

    n, s, e = 256, 32, 64
    y_idx = rng.integers(0, 10, n)
    x = rng.normal(0, 0.1, (n, s, e)).astype(np.float32)
    x[np.arange(n), y_idx * 3, :8] += 2.5     # class marker at position 3*c
    y = np.eye(10, dtype=np.float32)[y_idx]
    ld = ArrayDataLoader(x, y, batch_size=32, shuffle=True)
    ld.load_data()

    model = create_mha_classifier()
    opt = Adam(1e-3)
    tr = Trainer(model, opt, "softmax_crossentropy",
                 config=TrainingConfig(epochs=6, progress_interval=0,
                                       snapshot_dir=None))
    ts = create_train_state(model, opt, jax.random.PRNGKey(0))
    tr.fit(ts, ld)
    assert tr.history[-1]["train_acc"] > 0.9, tr.history[-1]

    # and it round-trips through the factory like every zoo model
    from dcnn_tpu.nn import Sequential
    clone = Sequential.from_config(model.get_config())
    assert clone.get_config() == model.get_config()


def test_mha_layer_config_roundtrip_and_builder(rng):
    layer = MultiHeadAttentionLayer(num_heads=4, causal=True, impl="blockwise")
    params, _ = layer.init(jax.random.PRNGKey(0), (16, 32))
    rebuilt = layer_from_config(layer.get_config())
    assert rebuilt.num_heads == 4 and rebuilt.causal and rebuilt.impl == "blockwise"

    model = (SequentialBuilder("attn_model")
             .input((16, 32))
             .add_layer(MultiHeadAttentionLayer(num_heads=4, impl="blockwise"))
             .add_layer(MultiHeadAttentionLayer(num_heads=2, impl="blockwise"))
             .build())
    p, s = model.init(jax.random.PRNGKey(1))
    x = jnp.asarray(rng.normal(size=(3, 16, 32)).astype(np.float32))
    y, _ = model.apply(p, s, x, training=False)
    assert y.shape == (3, 16, 32)
    assert np.all(np.isfinite(np.asarray(y)))


def test_flash_geometry_safety_gate(rng):
    """VMEM-safety routing for the Pallas backward (VERDICT r4 #5): tiny
    head dims at long sequence must take the blockwise fallback instead of
    failing Mosaic compilation; the measured-good geometries stay on the
    Pallas path."""
    from dcnn_tpu.ops.attention import _flash_geometry_safe

    # measured failure on v5e: E=128/H=8 -> d=16 at S=8192 (b=2, h=8)
    assert not _flash_geometry_safe(2, 8, 8192, 8192, 16)
    # the proven long-context config: d=64 at S=8192 streams fine
    assert _flash_geometry_safe(4, 8, 8192, 8192, 64)
    # small-S d=16 fits comfortably
    assert _flash_geometry_safe(2, 8, 512, 512, 16)
    # and the fallback is the same math: flash == naive on an unsafe-shaped
    # (scaled-down d) geometry, gradients included
    q, k, v = _qkv(rng, b=1, h=2, s=96, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    np.testing.assert_allclose(loss_flash(q, k, v), loss_ref(q, k, v),
                               rtol=1e-5)
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
