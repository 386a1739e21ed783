"""The gated delta rule with a decay of its own for every key channel (Kimi
Delta Attention, arXiv:2510.26692), in chunks: the one computation in this
repo that carries a state along the sequence.

For one head, ``t`` the position, ``S`` a state of ``Dk x Dv`` that starts at
zero, ``alpha_t = exp(g_t)`` in (0, 1] a vector of ``Dk``, ``beta_t`` a
scalar:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``chunked_gated_delta_rule`` computes it ``chunk`` positions at a time (the
WY form). With ``G`` the cumulative log decay inside a chunk, ``S_0`` the
state a chunk starts from and ``D(i, j) = exp(G_i - G_j)``, ``j <= i``:

    A_ij = k_i^T D(i, j) k_j  (j < i),      B_ij = q_i^T D(i, j) k_j  (j <= i)
    (I + Diag(beta) A) [W | U'] = Diag(beta) [exp(G) * K | V]
    U = U' - W S_0                          (the chunk's corrected values)
    O = (exp(G) * Q) S_0 + B U
    S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) * K)^T U

Everything but the last three lines is computed for all chunks at once; one
``lax.scan`` over the chunks carries ``S`` through those three.

**No factor exceeds 1.** ``D(i, j)`` is not a product of a factor of ``i`` and
one of ``j`` that stay in range (``exp(-G_j)`` overflows float32 after a few
positions of a strong decay), so ``A`` and ``B`` are put together from blocks:
rows ``[m, m + h)`` against columns ``[m - h, m)`` as a product of
``exp(G_i - G_{m-1}) *`` row and ``exp(G_{m-1} - G_j) *`` column, both at most
1, for ``h = chunk / 2`` down to 1, and the diagonal by itself. **No array is
narrower than a chunk.** The TPU lays an array out in tiles of 8 x 128, so a
block of 16 x 16 costs what 16 x 128 costs: each level is therefore one
whole ``C x D x C`` product of which the level's blocks are kept, and the unit
lower triangular system is inverted by whole ``C x C`` products too
(``unit_lower_inverse``), in float32. The state, the cumulative decay and the
system are float32 whatever the operands' dtype; the tile products take
their operands in ``q``'s dtype (bfloat16 in the bf16 mode) and accumulate in
float32.

On the chip the inside of a chunk is XLA's own products (measured by
``tools/bench_lm_kernels.py kda``; ``CHANGES.md``, PR 35): no Pallas kernel.
``gated_delta_rule_by_token`` is the recurrence as written above, a
``lax.scan`` over positions: what the chunked form is tested against, and no
path of a model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.precision import get_precision

BASE = 8          # the diagonal blocks unit_lower_inverse inverts by doubling


def gated_delta_rule_by_token(q, k, v, g, beta):
    """The recurrence position by position, in float32. ``q, k, g``
    ``[..., S, Dk]``, ``v [..., S, Dv]``, ``beta [..., S]`` -> ``[..., S, Dv]``."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    hi = jax.lax.Precision.HIGHEST

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., :, None] * state
        seen = jnp.einsum("...k,...kv->...v", k_t, state, precision=hi)
        state = state + jnp.einsum("...k,...v->...kv", k_t,
                                   b_t[..., None] * (v_t - seen), precision=hi)
        return state, jnp.einsum("...k,...kv->...v", q_t, state, precision=hi)

    first = jnp.zeros(q.shape[:-2] + (q.shape[-1], v.shape[-1]), jnp.float32)
    along = tuple(jnp.moveaxis(a, -2 if a.ndim == q.ndim else -1, 0)
                  for a in (q, k, v, g, beta))
    return jnp.moveaxis(jax.lax.scan(step, first, along)[1], 0, -2)


def _product(a, b, dtype):
    """``a @ b`` over the last two axes, operands in ``dtype``, float32 out.
    (``jnp.matmul``, not ``einsum``: an einsum names its operations after its
    subscripts, and a trace would file them under that name.)"""
    return jnp.matmul(a.astype(dtype), b.astype(dtype), precision=get_precision(),
                      preferred_element_type=jnp.float32)


def _transposed(a):
    return jnp.swapaxes(a, -1, -2)


def _siblings(c: int, h: int):
    """``[C, C]`` bool: position ``i`` lies in the later and ``j`` in the
    earlier half of one block of ``2 h`` positions."""
    block = jnp.arange(c) // h
    return (block[:, None] == block[None, :] + 1) & (block[:, None] % 2 == 1)


def decayed_products(rows, cols, cum, dtype):
    """``M_ij = sum_d rows_i[d] cols_j[d] exp(cum_i[d] - cum_j[d])`` for
    ``j <= i`` and 0 above the diagonal: ``rows [..., R, C, D]`` (``R``
    matrices share ``cols [..., C, D]`` and ``cum [..., C, D]``) ->
    ``[..., R, C, C]``. One whole ``C x D x C`` product a level ``h = 1, 2,
    .. C / 2``, of which the level keeps the blocks of sibling halves (module
    docstring): no exponent is positive, and no array is narrower than ``C``."""
    c, d = cols.shape[-2:]
    lead = cols.shape[:-2]
    rows32, cols32 = rows.astype(jnp.float32), cols.astype(jnp.float32)
    out = (jnp.sum(rows32 * cols32[..., None, :, :], axis=-1)[..., :, None]
           * jnp.eye(c, dtype=jnp.float32))
    h = 1
    while h < c:
        blocks = c // h
        cb = cum.reshape(*lead, blocks, h, d)
        ends = cb[..., -1:, :]                       # a block's last position
        before = jnp.concatenate([ends[..., :1, :, :], ends[..., :-1, :, :]], axis=-3)
        later = (jnp.arange(blocks) % 2 == 1)[:, None, None]
        # rows of a later half seen from the end of the earlier, columns of an
        # earlier half seen from their own end: both at most 1, the rest 0
        row_f = jnp.where(later, jnp.exp(jnp.minimum(cb - before, 0.0)), 0.0).reshape(*lead, c, d)
        col_f = jnp.where(later, 0.0, jnp.exp(ends - cb)).reshape(*lead, c, d)
        whole = _product(rows32 * row_f[..., None, :, :],
                         _transposed(cols32 * col_f)[..., None, :, :], dtype)
        out = out + jnp.where(_siblings(c, h), whole, 0.0)
        h *= 2
    return out


def unit_lower_inverse(lower):
    """``(I + lower)^-1`` for ``lower [..., C, C]`` strictly lower triangular,
    in float32, by whole ``C x C`` products: inside the diagonal blocks of
    ``BASE`` positions ``(I - L)(I + L^2)(I + L^4) ..`` (``L^BASE = 0``; a power's
    entries stay under ``binom(BASE - 1, k)``, 35 at the most, so nothing is
    lost where every key is the same), then halves joined, ``X <- X - X R X``
    for ``R`` the blocks below the diagonal of a level."""
    c = lower.shape[-1]
    hi = jax.lax.Precision.HIGHEST
    eye = jnp.eye(c, dtype=jnp.float32)
    base = min(BASE, c)
    block = jnp.arange(c) // base
    inside = jnp.where(block[:, None] == block[None, :], lower, 0.0)
    inv, power, n = eye - inside, inside, 2
    while n < base:
        power = jnp.matmul(power, power, precision=hi)
        inv = jnp.matmul(inv, eye + power, precision=hi)
        n *= 2
    h = base
    while h < c:
        below = jnp.where(_siblings(c, h), lower, 0.0)
        inv = inv - jnp.matmul(jnp.matmul(inv, below, precision=hi), inv, precision=hi)
        h *= 2
    return inv


def chunked_gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """``q, k [..., S, Dk]``, ``v [..., S, Dv]``, the log decay ``g [..., S, Dk]``
    (at most 0) and ``beta [..., S]`` -> ``o [..., S, Dv]`` in ``v``'s dtype.
    ``chunk`` is a power of two; a sequence that is no multiple of it is
    padded with positions that leave the state as it is."""
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is no power of two")
    s, dk = q.shape[-2:]
    dv, dtype, lead = v.shape[-1], q.dtype, q.shape[:-2]
    n = -(-s // chunk)
    if n * chunk != s:
        pad = [(0, 0)] * len(lead) + [(0, n * chunk - s)]
        q, k, v, g = (jnp.pad(a, pad + [(0, 0)]) for a in (q, k, v, g))
        beta = jnp.pad(beta, pad)

    def chunks(a, width):
        return a.reshape(*lead, n, chunk, width)
    q, k, v, g = chunks(q, dk), chunks(k, dk), chunks(v, dv), chunks(g.astype(jnp.float32), dk)
    beta = beta.astype(jnp.float32).reshape(*lead, n, chunk)
    cum = jnp.cumsum(g, axis=-2)
    both = decayed_products(jnp.stack([k, q], axis=-3), k, cum, dtype)
    strictly = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)
    inverse = unit_lower_inverse(both[..., 0, :, :] * strictly * beta[..., None])
    decay = jnp.exp(cum)
    right = jnp.concatenate([decay * k, v.astype(jnp.float32)], axis=-1) * beta[..., None]
    solved = _product(inverse, right, dtype)
    w, u_fresh = solved[..., :dk], solved[..., dk:]
    q_seen = decay * q                                     # q as the chunk's first state sees it
    k_left = jnp.exp(cum[..., -1:, :] - cum) * k           # k as the chunk's last state keeps it
    last = decay[..., -1, :]

    def one_chunk(state, at):
        w, u_fresh, q_seen, scores, k_left, last = at
        u = u_fresh - _product(w, state, dtype)
        o = _product(q_seen, state, dtype) + _product(scores, u, dtype)
        state = last[..., :, None] * state + _product(_transposed(k_left), u, dtype)
        return state, o

    at = len(lead)
    along = tuple(jnp.moveaxis(a, at, 0)
                  for a in (w, u_fresh, q_seen, both[..., 1, :, :], k_left, last))
    first = jnp.zeros((*lead, dk, dv), jnp.float32)
    o = jnp.moveaxis(jax.lax.scan(one_chunk, first, along)[1], 0, at)
    return o.reshape(*lead, n * chunk, dv)[..., :s, :].astype(v.dtype)
