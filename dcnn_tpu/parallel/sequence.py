"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

No reference analog — the reference has no attention or sequence axis
(SURVEY.md §5.7); its only long-input scaling axes are microbatching and
pipeline stages. For the TPU framework, long-context is first-class: the
sequence dim is sharded over a mesh axis and attention runs without ever
gathering the full sequence on one chip.

Two standard strategies, both exact:

- **Ring attention** (:func:`ring_attention`): each device keeps its local
  Q shard and rotates K/V shards around the ring with ``ppermute`` (ICI
  neighbour hops), accumulating online-softmax partials — compute overlaps
  the rotation, memory per chip is O(S/n). Causality is enforced per
  (q-shard, kv-shard) pair from global offsets.
- **Ulysses** (:func:`ulysses_attention`): ``all_to_all`` swaps the sharded
  axis from sequence to heads, runs dense local attention on full sequences
  for H/n heads, and swaps back. Cheaper collectives for moderate S; requires
  heads % n == 0.

Both run under ``shard_map`` over the ``"seq"`` mesh axis and compose with the
``"data"`` axis (batch sharding) of the same mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.mesh import SEQ_AXIS
from ..core.precision import precision_keyed_jit
from ..ops.attention import NEG_INF, _online_block


def shard_sequence(tree, mesh: Mesh, axis: str = SEQ_AXIS, seq_dim: int = 2):
    """Place (B, H, S, D) arrays with S sharded over ``axis``."""
    def put(x):
        spec = [None] * x.ndim
        spec[seq_dim] = axis
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))
    return jax.tree_util.tree_map(put, tree)


def _ring_local(q, k, v, *, axis: str, n: int, causal: bool, scale: float):
    """Per-device body: local q (B,H,Sq/n,D) attends to every kv shard as it
    rotates by. ppermute sends each block from device d to d+1, so after t
    rounds device i holds the block originally owned by (i - t) mod n; the
    causal mask for each round derives from that owner's global offset."""
    idx = jax.lax.axis_index(axis)
    sq = q.shape[2]
    b, h = q.shape[0], q.shape[1]

    # fp32 online-softmax state irrespective of q.dtype (ADVICE r1: bf16
    # statistics drop softmax mass; fp16 can't hold the NEG_INF sentinel)
    acc = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)

    # ppermute perm: device d sends its kv block to d+1, so after t rounds
    # device i holds the block originally owned by (i - t) mod n.
    perm = [(d, (d + 1) % n) for d in range(n)]
    q_pos = idx * sq + jnp.arange(sq)            # global query positions

    def accumulate(carry, t, k_cur, v_cur):
        acc, m, l = carry
        src = (idx - t) % n                       # owner of current kv block
        kv_pos = src * sq + jnp.arange(sq)        # global key positions
        if causal:
            score_mask = kv_pos[None, :] <= q_pos[:, None]
            score_mask = score_mask[None, None]   # (1,1,Sq,Skb)
        else:
            score_mask = None
        return _online_block(acc, m, l, q, k_cur, v_cur, scale, score_mask)

    def round_t(t, carry):
        # rotate first (t >= 1), then accumulate — n-1 rotations total; a
        # rotate-after-accumulate loop would pay one dead ppermute pair that
        # XLA cannot eliminate from the loop body.
        acc, m, l, k_cur, v_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis, perm)
        v_cur = jax.lax.ppermute(v_cur, axis, perm)
        if causal:
            # Causal round skip: when the arriving kv block's owner is ahead
            # of this device (src > idx) every (q, k) pair is masked — skip
            # the attention compute entirely. The ppermutes above still run
            # every round on every device (collectives must stay uniform
            # across the SPMD program); only the local compute is gated, so
            # device i does i+1 of n accumulations (~2x FLOP saving overall).
            # Wall-clock is still gated by the last device, which skips
            # nothing — full balance needs a zigzag block layout (device i
            # owning blocks i and 2n-1-i), a known future optimisation.
            src = (idx - t) % n
            acc, m, l = jax.lax.cond(
                src > idx,
                lambda c: c,
                lambda c: accumulate(c, t, k_cur, v_cur),
                (acc, m, l))
        else:
            acc, m, l = accumulate((acc, m, l), t, k_cur, v_cur)
        return acc, m, l, k_cur, v_cur

    acc, m, l = accumulate((acc, m, l), 0, k, v)   # own block, no rotation
    acc, m, l, _, _ = jax.lax.fori_loop(
        1, n, round_t, (acc, m, l, k, v))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def make_ring_attention(mesh: Mesh, *, axis: str = SEQ_AXIS,
                        causal: bool = False, scale: Optional[float] = None):
    """Build ``f(q, k, v) -> out`` with the sequence dim (axis 2) sharded
    over ``mesh[axis]``. Exact: matches full attention on the gathered
    sequence. Requires S divisible by the axis size (standard for
    long-context training; pad the sequence otherwise).

    Causal mode skips the attention compute for fully-masked rounds
    (kv owner ahead of the query shard): device i accumulates only i+1 of
    the n rounds, halving total FLOPs. Rotations still run every round
    (uniform collectives). The skip is imbalanced (device n-1 never skips);
    :func:`make_zigzag_ring_attention` balances it so wall-clock also drops.
    """
    n = mesh.shape[axis]

    def f(q, k, v):
        nonlocal scale
        if k.shape[2] != q.shape[2] or v.shape[2] != q.shape[2]:
            raise ValueError(
                f"ring attention requires equal q/k/v sequence lengths, got "
                f"Sq={q.shape[2]} Sk={k.shape[2]} Sv={v.shape[2]} (global kv "
                f"positions are derived from the q shard length)")
        if q.shape[2] % n:
            raise ValueError(
                f"ring attention needs sequence length ({q.shape[2]}) "
                f"divisible by mesh axis {axis!r} size {n}; pad the sequence")
        s = q.shape[-1] ** -0.5 if scale is None else scale
        local = functools.partial(_ring_local, axis=axis, n=n,
                                  causal=causal, scale=s)
        spec = P(None, None, axis, None)
        return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)

    return precision_keyed_jit(f)


def zigzag_permutation(seq_len: int, n: int) -> "jnp.ndarray":
    """Sequence-position permutation for zigzag ring attention: split the
    sequence into 2n blocks; device i owns blocks i and 2n-1-i. Returns
    ``perm`` such that ``x[:, :, perm]`` is in zigzag order (device shards
    are then the usual contiguous S/n slices). Invert with
    ``jnp.argsort(perm)``."""
    if seq_len % (2 * n):
        raise ValueError(f"zigzag needs seq_len ({seq_len}) divisible by "
                         f"2*n ({2 * n})")
    c = seq_len // (2 * n)
    blocks = []
    for i in range(n):
        blocks.append(jnp.arange(i * c, (i + 1) * c))
        j = 2 * n - 1 - i
        blocks.append(jnp.arange(j * c, (j + 1) * c))
    return jnp.concatenate(blocks)


def zigzag_shard(tree, mesh: Mesh, axis: str = SEQ_AXIS, seq_dim: int = 2):
    """Permute (B, H, S, D) arrays into zigzag order and shard S over
    ``axis``. The paired :func:`make_zigzag_ring_attention` output is in the
    same zigzag order; recover natural order with
    ``out.take(jnp.argsort(zigzag_permutation(S, n)), axis=2)``."""
    n = mesh.shape[axis]

    def put(x):
        perm = zigzag_permutation(x.shape[seq_dim], n)
        return jnp.take(x, perm, axis=seq_dim)
    return shard_sequence(jax.tree_util.tree_map(put, tree), mesh, axis,
                          seq_dim)


def _zigzag_local(q, k, v, *, axis: str, n: int, scale: float):
    """Per-device body for causal zigzag ring attention. The local S/n rows
    are TWO chunks: block ``idx`` (early positions) and block ``2n-1-idx``
    (late positions). Each arriving kv shard likewise carries blocks
    ``src`` and ``2n-1-src``; each of the 4 (q-chunk, kv-chunk) pairs is
    computed only when not fully masked. Per round, the number of live pairs
    per device is constant (2n+1 live of 4n total across all rounds), so —
    unlike the plain causal ring, where device n-1 computes every round
    while device 0 computes once — wall-clock drops with the FLOPs."""
    idx = jax.lax.axis_index(axis)
    b, h, s_loc, d = q.shape
    c = s_loc // 2
    qa, qb = q[:, :, :c], q[:, :, c:]

    def init_state():
        return (jnp.zeros((b, h, c, d), jnp.float32),
                jnp.full((b, h, c), NEG_INF, jnp.float32),
                jnp.zeros((b, h, c), jnp.float32))

    st_a, st_b = init_state(), init_state()
    off_qa = idx * c
    off_qb = (2 * n - 1 - idx) * c
    pos = jnp.arange(c)
    perm = [(dd, (dd + 1) % n) for dd in range(n)]

    def pair(state, q_chunk, off_q, k_chunk, v_chunk, off_k):
        """Accumulate one (q-chunk, kv-chunk) pair unless fully masked."""
        def compute(st):
            m_ok = (off_k + pos)[None, :] <= (off_q + pos)[:, None]
            return _online_block(st[0], st[1], st[2], q_chunk, k_chunk,
                                 v_chunk, scale, m_ok[None, None])

        # fully masked iff the earliest key is after the latest query
        return jax.lax.cond(off_k > off_q + c - 1, lambda st: st, compute,
                            state)

    def accumulate(st_a, st_b, k_cur, v_cur, src):
        ka, kb = k_cur[:, :, :c], k_cur[:, :, c:]
        va, vb = v_cur[:, :, :c], v_cur[:, :, c:]
        off_ka = src * c
        off_kb = (2 * n - 1 - src) * c
        st_a = pair(st_a, qa, off_qa, ka, va, off_ka)
        st_a = pair(st_a, qa, off_qa, kb, vb, off_kb)
        st_b = pair(st_b, qb, off_qb, ka, va, off_ka)
        st_b = pair(st_b, qb, off_qb, kb, vb, off_kb)
        return st_a, st_b

    st_a, st_b = accumulate(st_a, st_b, k, v, idx)   # own shard, no rotation

    def round_t(t, carry):
        st_a, st_b, k_cur, v_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis, perm)
        v_cur = jax.lax.ppermute(v_cur, axis, perm)
        src = (idx - t) % n
        st_a, st_b = accumulate(st_a, st_b, k_cur, v_cur, src)
        return st_a, st_b, k_cur, v_cur

    st_a, st_b, _, _ = jax.lax.fori_loop(1, n, round_t, (st_a, st_b, k, v))

    def finalize(st):
        acc, m, l = st
        return acc / jnp.maximum(l, 1e-30)[..., None]

    return jnp.concatenate([finalize(st_a), finalize(st_b)],
                           axis=2).astype(q.dtype)


def make_zigzag_ring_attention(mesh: Mesh, *, axis: str = SEQ_AXIS,
                               scale: Optional[float] = None):
    """Causal ring attention over zigzag-sharded sequences: same numerics as
    :func:`make_ring_attention` (causal=True) but with the causal-skip work
    balanced across the ring, so the skipped rounds buy wall-clock, not just
    FLOPs. Inputs must be in zigzag order (:func:`zigzag_shard` /
    :func:`zigzag_permutation`); the output is in the same order. Requires
    S divisible by 2*n. Causal only — for non-causal use the plain ring,
    which is already balanced."""
    n = mesh.shape[axis]

    def f(q, k, v):
        nonlocal scale
        if k.shape[2] != q.shape[2] or v.shape[2] != q.shape[2]:
            raise ValueError("zigzag ring requires equal q/k/v lengths")
        if q.shape[2] % (2 * n):
            raise ValueError(
                f"zigzag ring needs sequence length ({q.shape[2]}) divisible "
                f"by 2*mesh axis size ({2 * n}); pad the sequence")
        s = q.shape[-1] ** -0.5 if scale is None else scale
        local = functools.partial(_zigzag_local, axis=axis, n=n, scale=s)
        spec = P(None, None, axis, None)
        return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)

    return precision_keyed_jit(f)


def _ulysses_local(q, k, v, *, axis: str, n: int, causal: bool, scale: float,
                   interpret=None):
    """Per-device body: all_to_all seq-shard → head-shard, full local
    attention, all_to_all back. Local shapes in: (B, H, S/n, D).

    The local attention is the Pallas flash kernel (fwd + dq/dk/dv backward
    with causal tile skipping — the r3 kernels): on TPU this is the 3-6×
    path; off-TPU it falls back to the numerically-identical blockwise scan,
    so mesh tests stay exact."""
    from ..ops.attention import flash_attention

    # (B, H, S/n, D) -> (B, H/n, S, D): split heads across devices, gather seq
    def swap_in(x):
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    def swap_out(x):
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = swap_in(q), swap_in(k), swap_in(v)
    out = flash_attention(qh, kh, vh, causal=causal, scale=scale,
                          interpret=interpret)
    return swap_out(out)


def make_ulysses_attention(mesh: Mesh, *, axis: str = SEQ_AXIS,
                           causal: bool = False,
                           scale: Optional[float] = None,
                           interpret=None):
    """Build Ulysses-style sequence-parallel attention over ``mesh[axis]``.
    Requires H divisible by the axis size. ``interpret`` forwards to
    :func:`~dcnn_tpu.ops.attention.flash_attention` (tests force the Pallas
    interpreter off-TPU to cover the kernel+all_to_all composition)."""
    n = mesh.shape[axis]

    def f(q, k, v):
        if q.shape[1] % n:
            raise ValueError(
                f"ulysses needs heads ({q.shape[1]}) divisible by mesh axis "
                f"{axis!r} size {n}")
        s = q.shape[-1] ** -0.5 if scale is None else scale
        local = functools.partial(_ulysses_local, axis=axis, n=n,
                                  causal=causal, scale=s,
                                  interpret=interpret)
        spec = P(None, None, axis, None)
        return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)

    return precision_keyed_jit(f)
