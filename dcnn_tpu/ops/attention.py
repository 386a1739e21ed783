"""Scaled-dot-product attention ops: naive, blockwise (flash-style), Pallas.

No reference analog — the reference is a CNN-only framework with no attention
anywhere (SURVEY.md §5.7 verified absence). Attention is nonetheless
first-class here because it is the op whose memory behaviour defines
long-context scaling on TPU: the blockwise/online-softmax formulation keeps
the S×S score matrix out of HBM, and is also the local compute step of ring
attention (``dcnn_tpu/parallel/sequence.py``).

Shapes follow (B, H, S, D): batch, heads, sequence, head dim. ``v`` (and so
the output) may have a head dim of its own, ``Dv != D``: latent attention
scores at 192 and mixes values of 128. All functions are jittable with static
shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.precision import get_precision, precision_keyed_jit

NEG_INF = -1e30
# jax.ad_checkpoint names of the flash forward's output and logsumexp
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"


def _check_mask_rank(mask: jax.Array) -> jax.Array:
    """Masks must be 2-D (Sq, Sk) or 4-D (B|1, H|1, Sq, Sk). 3-D masks are
    rejected: a (B, Sq, Sk) key-padding mask would silently broadcast as
    (1, H=B, Sq, Sk) — head-aligned, not batch-aligned — whenever B == H
    (ADVICE r2 #5). Callers with a batch mask must add the head axis
    explicitly: ``mask[:, None]``."""
    mask = jnp.asarray(mask, bool)   # accept 0/1 float masks like jnp.where did
    if mask.ndim == 3:
        raise ValueError(
            "3-D attention masks are ambiguous (batch- vs head-aligned); "
            "pass (Sq, Sk) or (B|1, H|1, Sq, Sk) — for a batch key-padding "
            "mask use mask[:, None].")
    if mask.ndim > 4:
        raise ValueError(
            f"attention mask rank {mask.ndim} > 4; expected (Sq, Sk) or "
            f"(B|1, H|1, Sq, Sk)")
    while mask.ndim < 4:
        mask = mask[None]
    return mask


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = False, mask: Optional[jax.Array] = None,
              scale: Optional[float] = None) -> jax.Array:
    """Reference (materialising) attention: ``softmax(q·kᵀ·scale)·v``.

    ``mask``: (Sq, Sk) or (B|1, H|1, Sq, Sk); True = attend (3-D rejected —
    see :func:`_check_mask_rank`). O(S²) memory — the numerics oracle for the
    blockwise/pallas/ring variants.

    Fully-masked rows return 0 (zero softmax mass), the same convention as
    :func:`blockwise_attention` / :func:`flash_attention` — NOT the uniform
    average a plain softmax over all-NEG_INF scores would produce.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=get_precision()) * scale
    allowed = None
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        allowed = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
    if mask is not None:
        mask = _check_mask_rank(mask)
        allowed = mask if allowed is None else (allowed & mask)
    if allowed is not None:
        scores = jnp.where(allowed, scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    if allowed is not None:
        # zero fully-masked rows (softmax of all-NEG_INF is uniform 1/Sk)
        any_allowed = jnp.any(jnp.broadcast_to(allowed, scores.shape),
                              axis=-1, keepdims=True)
        weights = jnp.where(any_allowed, weights, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v,
                      precision=get_precision())


def _online_block(acc, m, l, q, k_blk, v_blk, scale, score_mask):
    """One online-softmax accumulation step for query block against one
    K/V block. Returns updated (acc, m, l). score_mask: (Sq, Skb) or None.

    The running state (acc, m, l) is float32 regardless of input dtype —
    bf16 statistics lose 8+ bits of softmax mass and fp16 can't even hold
    the -1e30 mask sentinel — matching the Pallas kernel's fp32 VMEM
    scratch. Callers cast the final normalised output back to input dtype.
    """
    s = jnp.einsum("...qd,...kd->...qk", q, k_blk,
                   precision=get_precision(),
                   preferred_element_type=jnp.float32) * scale
    if score_mask is not None:
        s = jnp.where(score_mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1
    p = jnp.exp(s - m_new[..., None])
    if score_mask is not None:
        p = jnp.where(score_mask, p, 0.0)
    correction = jnp.exp(m - m_new)
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p.astype(v_blk.dtype), v_blk,
        precision=get_precision(), preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = False, block_kv: int = 512,
                        scale: Optional[float] = None,
                        mask: Optional[jax.Array] = None) -> jax.Array:
    """Flash-style attention: online softmax over K/V blocks via ``lax.scan``
    — never materialises the (Sq, Sk) score matrix. Exact (not approximate);
    matches :func:`attention` to float tolerance.

    Masking: ``causal`` plus an optional ``mask`` of rank 2 (Sq, Sk) or 4
    (B|1, H|1, Sq, Sk), True = attend (padding/segment masks; 3-D rejected —
    see :func:`_check_mask_rank`). The mask is consumed one K/V block at a
    time, so this path keeps its O(Sq·block_kv) working set (the caller's
    mask array itself may of course be O(Sq·Sk) — pass broadcastable
    singleton dims where possible). Fully-masked rows return 0 (zero softmax
    mass), the same convention as :func:`attention`. The Pallas
    :func:`flash_attention` kernel remains causal-only; masked calls route
    here.
    """
    if mask is not None:
        mask = _check_mask_rank(mask)
    return _blockwise_attention_jit(q, k, v, mask, causal=causal,
                                    block_kv=block_kv, scale=scale)


@functools.partial(precision_keyed_jit,
                   static_argnames=("causal", "block_kv", "scale"))
def _blockwise_attention_jit(q, k, v, mask, causal, block_kv, scale):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_kv = min(block_kv, sk)
    nblk = -(-sk // block_kv)
    pad = nblk * block_kv - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, h, nblk, block_kv, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nblk, block_kv, v.shape[-1]).transpose(2, 0, 1, 3, 4)

    if mask is not None:
        mask = _check_mask_rank(mask)  # idempotent; guards direct callers
        if mask.shape[-1] not in (1, sk):
            raise ValueError(
                f"mask last dim {mask.shape[-1]} must be 1 or Sk={sk}")
        if pad and mask.shape[-1] == sk:
            mask = jnp.pad(mask, ((0, 0),) * 3 + ((0, pad),))

    q_pos = jnp.arange(sq)                       # global query positions
    diag_offset = sk - sq                        # causal diag when Sq != Sk

    def body(carry, blk):
        acc, m, l = carry
        k_blk, v_blk, blk_idx = blk
        kv_pos = blk_idx * block_kv + jnp.arange(block_kv)
        valid = kv_pos < sk                      # padding mask
        if causal:
            allowed = kv_pos[None, :] <= (q_pos[:, None] + diag_offset)
            score_mask = (allowed & valid[None, :])[None, None]
        else:
            score_mask = jnp.broadcast_to(valid[None, :],
                                          (sq, block_kv))[None, None]
        if mask is not None:
            mask_blk = (mask if mask.shape[-1] == 1 else
                        jax.lax.dynamic_slice_in_dim(
                            mask, blk_idx * block_kv, block_kv, axis=-1))
            score_mask = score_mask & mask_blk
        acc, m, l = _online_block(acc, m, l, q, k_blk, v_blk, scale,
                                  score_mask)
        return (acc, m, l), None

    # fp32 online-softmax state irrespective of q.dtype (see _online_block)
    acc0 = jnp.zeros((b, h, sq, v.shape[-1]), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (kb, vb, jnp.arange(nblk)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash-attention forward kernel
# ---------------------------------------------------------------------------

def _tile_geometry(q_start, kv_start, block_q, block_kv, sk, sq, causal):
    """Shared (live, mask) for one (q, kv) tile — used identically by the
    forward and both backward kernels so their masking can never diverge.
    ``live``: causal block-skip predicate (False = tile strictly above the
    q tile's diagonal band, all FLOPs skippable). ``mask``: kv-padding
    validity & the per-element causal triangle (diag offset sk-sq)."""
    live = (jnp.asarray(True) if not causal
            else kv_start <= q_start + block_q - 1 + (sk - sq))
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kv_pos = kv_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = kv_pos < sk
    if causal:
        mask &= kv_pos <= (q_pos + (sk - sq))
    return live, mask


def _kernel_precision(dtype):
    """MXU contract precision the Pallas kernels ask for. The precision
    policy (``HIGHEST`` in parity mode) selects fp32 multi-pass matmuls and
    only means something for fp32 operands; Mosaic rejects it on bf16 ones
    ("Bad lhs type" on v5e / jax 0.9.0), which already are single-pass."""
    return get_precision() if dtype == jnp.float32 else None


def _tile_scores(q, k_blk, scale, precision):
    """scale·(q·k_blkᵀ) in fp32 — the QKᵀ tile every kernel starts from."""
    return jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32) * scale


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                  *, nkv: int, sk: int, sq: int, causal: bool, scale: float,
                  precision):
    """One (batch·head, q-block, kv-block) program. K/V are *streamed*: each
    program sees one (block_kv, d) tile (grid's innermost axis walks the kv
    blocks), so VMEM holds one K and one V tile — never the whole sequence.
    Online-softmax running state (acc, m, l) lives in VMEM scratch carried
    across the kv axis; the output block AND the per-row logsumexp (saved for
    the Pallas backward) are written on the last kv step.
    Refs carry a leading size-1 batch·head block dim."""
    t = pl.program_id(2)
    q = q_ref[0]
    block_q = q.shape[0]
    block_kv = k_ref.shape[1]

    @pl.when(t == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = pl.program_id(1) * block_q
    # causal block skip: a kv tile strictly above the diagonal band of this
    # q tile contributes nothing — skip its FLOPs entirely (the DMA still
    # runs; the kernel is compute-bound so this ~halves causal time)
    live, mask = _tile_geometry(q_start, t * block_kv, block_q, block_kv,
                                sk, sq, causal)

    @pl.when(live)
    def _accumulate():
        k_blk, v_blk = k_ref[0], v_ref[0]
        s = jnp.where(mask, _tile_scores(q, k_blk, scale, precision), NEG_INF)
        m = m_ref[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=-1)
        m_ref[:, 0] = m_new
        acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    @pl.when(t == nkv - 1)
    def _finalize():
        l_fin = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[:] / l_fin[:, None]).astype(o_ref.dtype)
        # logsumexp per row; fully-masked rows get ~NEG_INF (the backward
        # masks their probabilities to 0 explicitly, never via exp)
        lse_ref[0] = (m_ref[:, :1] + jnp.log(l_fin)[:, None])


def _flash_forward(q, k, v, *, causal, block_q, block_kv, scale, interpret):
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[-1]
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    pad_q = -sq % block_q
    pad_kv = -sk % block_kv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0))) if pad_kv else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0))) if pad_kv else v
    sq_p, sk_p = sq + pad_q, sk + pad_kv
    nkv = sk_p // block_kv
    qf = qp.reshape(b * h, sq_p, d)
    kf = kp.reshape(b * h, sk_p, d)
    vf = vp.reshape(b * h, sk_p, dv)
    kernel = functools.partial(_flash_kernel, nkv=nkv, sk=sk, sq=sq,
                               causal=causal, scale=scale,
                               precision=_kernel_precision(q.dtype))
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((b * h, sq_p, dv), q.dtype),
                   jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32)],
        # kv axis innermost: TPU grids run sequentially with the last axis
        # fastest, so scratch accumulators carry across kv steps per q block
        grid=(b * h, sq_p // block_q, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec((1, block_kv, dv), lambda i, j, t: (i, t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, t: (i, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq_p, dv)[:, :, :sq], lse.reshape(b, h, sq_p)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, nkv: int, sk: int, sq: int,
                         causal: bool, scale: float, precision):
    """dQ program: grid (batch·head, q-block, kv-block), kv innermost.
    For each kv tile: P = exp(S - lse), dS = P*(dO·Vᵀ - Δ), dQ += dS·K·scale
    where Δ = rowsum(dO*O) (precomputed). All accumulation in fp32 VMEM."""
    t = pl.program_id(2)
    q, k_blk, v_blk, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    block_q = q.shape[0]
    block_kv = k_blk.shape[0]

    @pl.when(t == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = pl.program_id(1) * block_q
    live, mask = _tile_geometry(q_start, t * block_kv, block_q, block_kv,
                                sk, sq, causal)

    @pl.when(live)
    def _accumulate():
        s = _tile_scores(q, k_blk, scale, precision)
        # mask FIRST (never rely on exp of a masked sentinel: fully-masked
        # rows carry lse ~ NEG_INF and exp(s - lse) would overflow)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 precision=precision,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_acc[:] += jax.lax.dot_general(ds.astype(k_blk.dtype), k_blk,
                                         (((1,), (0,)), ((), ())),
                                         precision=precision,
                                         preferred_element_type=jnp.float32)

    @pl.when(t == nkv - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, nq: int, sk: int,
                          sq: int, causal: bool, scale: float, precision):
    """dK/dV program: grid (batch·head, kv-block, q-block), q innermost.
    dV += Pᵀ·dO ; dK += dSᵀ·Q·scale. Zero-padded dO rows contribute exactly
    zero (their Δ is also zero), so sq padding needs no special casing."""
    j = pl.program_id(2)
    q, k_blk, v_blk, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    block_q = q.shape[0]
    block_kv = k_blk.shape[0]

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    kv_start = pl.program_id(1) * block_kv
    # causal skip: a q tile strictly left of this kv tile's diagonal band
    # (q_max + offset < kv_start) contributes nothing to dK/dV
    live, mask = _tile_geometry(j * block_q, kv_start, block_q, block_kv,
                                sk, sq, causal)

    @pl.when(live)
    def _accumulate():
        s = _tile_scores(q, k_blk, scale, precision)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         precision=precision,
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 precision=precision,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         precision=precision,
                                         preferred_element_type=jnp.float32)

    @pl.when(j == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, *, causal, block_q, block_kv, scale,
                    interpret):
    """Pallas flash backward: two sequential-grid kernels (dQ over kv tiles;
    dK/dV over q tiles), FlashAttention-2 math — P is recomputed from the
    saved logsumexp, never materialised in HBM. v, o, dO and dV have v's
    head dim ``dv``, which need not be q's and k's ``d``."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[-1]
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    # q-side padding MUST use the forward's block_q: the saved lse is
    # already padded to that length (see _flash_forward). Tile shrinking
    # below only halves, so any smaller tile still divides sq_p evenly.
    pad_q = -sq % block_q
    pad_kv = -sk % block_kv
    sq_p, sk_p = sq + pad_q, sk + pad_kv
    # Scoped-VMEM guard (measured on v5e, 16M limit): the backward kernels
    # hold ~5 (block_q × block_kv) fp32 intermediates; at the tuned
    # 1024×512 tiles the largest geometries overflow marginally — observed
    # "scoped allocation 16.70M > 16.00M" at b·h=64, S=8192, d=64, while
    # b·h=16 at S=8192 and b·h=32 at S=4096 fit. Beyond that measured
    # frontier, halve tiles (kv first) until the working set is safely
    # under the limit; tuned-good configs keep their blocks.
    if b * h * max(sq, sk) >= (1 << 19):
        while block_q * block_kv > 1024 * 256 and block_kv > 128:
            block_kv //= 2
        while block_q * block_kv > 1024 * 256 and block_q > 128:
            block_q //= 2
        pad_kv = -sk % block_kv
        sk_p = sk + pad_kv

    # Δ = rowsum(dO * O), fp32 (a cheap fused elementwise+reduce in XLA)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def padq(a):
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else a

    def padkv(a):
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad_kv), (0, 0))) if pad_kv else a

    qf = padq(q).reshape(b * h, sq_p, d)
    gf = padq(g).reshape(b * h, sq_p, dv)
    kf = padkv(k).reshape(b * h, sk_p, d)
    vf = padkv(v).reshape(b * h, sk_p, dv)
    # forward and backward derive sq_p from the same nondiff (block_q, sq),
    # so the saved lse is already padded-length — reshape only
    lse_f = lse.reshape(b * h, sq_p, 1)
    delta_f = (jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q))) if pad_q
               else delta).reshape(b * h, sq_p, 1)

    nq = sq_p // block_q
    nkv = sk_p // block_kv
    prec = _kernel_precision(q.dtype)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, nkv=nkv, sk=sk, sq=sq,
                          causal=causal, scale=scale, precision=prec),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        grid=(b * h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec((1, block_kv, dv), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec((1, block_q, dv), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, t: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, t: (i, j, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, gf, lse_f, delta_f)

    dk, dv_ = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq=nq, sk=sk, sq=sq,
                          causal=causal, scale=scale, precision=prec),
        out_shape=[jax.ShapeDtypeStruct((b * h, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, sk_p, dv), v.dtype)],
        grid=(b * h, nkv, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, t, j: (i, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda i, t, j: (i, t, 0)),
            pl.BlockSpec((1, block_kv, dv), lambda i, t, j: (i, t, 0)),
            pl.BlockSpec((1, block_q, dv), lambda i, t, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, t, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, t, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, d), lambda i, t, j: (i, t, 0)),
            pl.BlockSpec((1, block_kv, dv), lambda i, t, j: (i, t, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, dv), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, gf, lse_f, delta_f)

    unflat = lambda a, s_p, s: a.reshape(b, h, s_p, a.shape[-1])[:, :, :s]
    return unflat(dq, sq_p, sq), unflat(dk, sk_p, sk), unflat(dv_, sk_p, sk)


def _flash_geometry_safe(b: int, h: int, sq: int, sk: int, d: int) -> bool:
    """Can the Pallas backward kernels run this geometry without VMEM
    overflow? Mosaic lane-pads the trailing head dim to 128; for d >= 32 the
    blocked pipeline streams tiles and any length fits, but at very small
    head dims (measured: d=16, S=8192, b·h=16 on v5e) Mosaic falls back to a
    layout that materialises whole lane-padded (b·h, S, 128) operands in
    VMEM — "scoped allocation exceeded 16M" at compile time. Gate on the
    padded whole-operand footprint with a safety margin so those shapes take
    the numerically-equivalent blockwise path instead of failing to
    compile."""
    if d >= 32:
        return True
    padded_bytes = b * h * max(sq, sk) * 128 * 4
    return padded_bytes <= 12 * 2**20


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, block_q, block_kv, scale, interpret):
    out, _ = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                            block_kv=block_kv, scale=scale,
                            interpret=interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_kv, scale, interpret):
    out, lse = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                              block_kv=block_kv, scale=scale,
                              interpret=interpret)
    # named, so that a caller's jax.checkpoint policy can keep the kernel's
    # two results and not run it again in the backward pass
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_kv, scale, interpret, res, g):
    # Pallas flash backward (dq/dk/dv kernels) — replaces the r2
    # recompute-through-blockwise VJP (VERDICT r2 #5): the probability matrix
    # is rebuilt tile-by-tile from the saved logsumexp instead of re-running
    # the whole forward online-softmax scan.
    q, k, v, o, lse = res
    return _flash_backward(q, k, v, o, lse, g, causal=causal,
                           block_q=block_q, block_kv=block_kv, scale=scale,
                           interpret=interpret)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, block_q: int = 1024,
                    block_kv: int = 512, scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    mask: Optional[jax.Array] = None) -> jax.Array:
    """Pallas flash-attention forward (online softmax, scores stay in VMEM),
    differentiable via Pallas dq/dk/dv backward kernels (FlashAttention-2
    math: probabilities rebuilt per tile from the saved O + logsumexp
    residuals — see :func:`_flash_backward`). Causal-only masking in the kernel
    (see :func:`blockwise_attention` docstring); ``mask`` routes to the
    blockwise path. Falls back to :func:`blockwise_attention` — numerically
    equivalent, same memory profile — when the backend is not TPU, and on
    TPU for head dims under 32 at long S (:func:`_flash_geometry_safe`);
    pass ``interpret=True`` explicitly to force the (slow) Pallas
    interpreter off-TPU for kernel tests.

    Default block sizes are the measured v5e optimum (causal S=4096 b4·h8·
    d64 sweep: q1024/kv512 = 7.35 TFLOP/s vs 6.22 for the XLA blockwise scan
    and 5.46 for the previous 256/256 blocks); both are clamped to the
    sequence length, so short sequences are unaffected.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if mask is not None:
        # the Pallas kernel is causal-only; arbitrary masks take the
        # numerically-equivalent blockwise path (same memory profile)
        return blockwise_attention(q, k, v, causal=causal,
                                   block_kv=block_kv, scale=scale, mask=mask)
    if interpret is None and jax.default_backend() != "tpu":
        return blockwise_attention(q, k, v, causal=causal,
                                   block_kv=block_kv, scale=scale)
    b, h, sq, _ = q.shape
    if not interpret and not _flash_geometry_safe(b, h, sq, k.shape[2],
                                                  q.shape[-1]):
        # tiny head dims at long S overflow VMEM in the Pallas backward
        # (see _flash_geometry_safe) — auto-fallback, same math. The limit
        # is a Mosaic TPU-lowering property, so an explicit interpret=True
        # (kernel debugging) bypasses the gate.
        return blockwise_attention(q, k, v, causal=causal,
                                   block_kv=block_kv, scale=scale)
    return _flash_attention(q, k, v, causal, block_q, block_kv, float(scale),
                            bool(interpret))
