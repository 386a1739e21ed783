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

**On the TPU the inside of a chunk is two Pallas kernels** under one
``jax.custom_vjp`` (``_inside_kernels``): everything above the last three
lines, which carries no state. The forward kernel reads a chunk's ``q, k, v,
g, beta`` once and writes the six arrays the scan takes (``W``, ``U'``,
``exp(G) * Q``, ``B``, ``exp(G_C - G) * K``, ``exp(G_C)``; those that only
ever enter a tile product in ``q``'s dtype, ``U'`` and ``exp(G_C)`` in
float32; the five large ones with the chunks in front, as the scan takes
them, so that nothing is transposed on the way); the backward kernel reads the same inputs and the six cotangents
the scan's transpose gives, computes the chunk's inside anew and writes ``dq,
dk, dv, dg, dbeta``: nothing but the inputs is kept between them. In VMEM a
chunk's exponents are sums of ``g`` over positions (one product with a
constant of 0 and 1, ``_sums``, exact in three bfloat16 passes; none is a
difference of cumulative sums, so none is positive and none loses digits to
cancellation), the levels' products keep their sibling blocks, the system is
inverted by ``unit_lower_inverse`` as it stands (float32 products at
``HIGHEST``), and the backward pass takes ``dL = -X^T dX X^T`` for the
inverse and two products a level for the decayed products. No ``[.., C, D]``
factor, no level's whole product and no power of the system goes to HBM. A
program takes ``PER_PROGRAM`` chunks of one head and computes each step of
the algorithm for all of them at once: the ten products of one chunk's
inverse hang on one another, those of eight chunks fill the MXU's pipeline
(a chunk at a time in a loop: 5.9 ms forward for one sequence's 2,048 chunks,
3.0 of them the inverse, of which one bfloat16 pass for six gave back 1.1;
eight at once: 3.6). The grid is (sequence x head, group of chunks). **Which
path runs** is ``takes_kernel``'s to say: the kernels on the TPU
(``interpret=True`` anywhere, for tests) where the head widths are multiples
of 128 and the chunk is 16 to 64 positions; any other geometry or backend
takes XLA's products (``_inside``), which is also what the kernels are
tested against. Measured on one v5e (``tools/bench_lm_kernels.py kda``;
``CHANGES.md``, PR 36), 4 sequences x 32 heads x 4096 positions of 128, a
sequence at a time under a checkpoint: 14.4 ms forward and 59.5 with the
backward, against 60.5 and 245.2 by XLA's products; the inside of one
sequence's chunks alone 3.5 and 7.1 ms against 15.8 and 39.6.

``gated_delta_rule_by_token`` is the recurrence as written above, a
``lax.scan`` over positions: what both chunked forms are tested against, and
no path of a model.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.precision import get_precision
from .attention import _kernel_precision

BASE = 8          # the diagonal blocks unit_lower_inverse inverts by doubling
PER_PROGRAM = 8   # chunks one program of a kernel takes (one row of ``last`` each)


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


def _positions(c: int):
    """``i, j`` of a ``[C, C]`` matrix (two-dimensional iotas: a kernel lowers
    no other)."""
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _siblings(c: int, h: int):
    """``[C, C]`` bool: position ``i`` lies in the later and ``j`` in the
    earlier half of one block of ``2 h`` positions (``h`` a power of two:
    the highest bit in which ``i`` and ``j`` differ is ``h``'s, and ``i`` has it)."""
    i, j = _positions(c)
    return (jnp.right_shift(jnp.bitwise_xor(i, j), h.bit_length() - 1) == 1) & (i > j)


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
    i, j = _positions(c)
    eye = (i == j).astype(jnp.float32)
    base = min(BASE, c)
    inside = jnp.where(jnp.bitwise_xor(i, j) < base, lower, 0.0)
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


def _inside(q, k, v, g, beta, dtype):
    """Everything of a chunk that carries no state, for all chunks at once, by
    XLA's products: ``q, k [..., N, C, Dk]``, ``v [..., N, C, Dv]``, ``g``
    float32 and ``beta [..., N, C]`` -> the six arrays the scan takes."""
    dk = q.shape[-1]
    chunk = q.shape[-2]
    cum = jnp.cumsum(g, axis=-2)
    both = decayed_products(jnp.stack([k, q], axis=-3), k, cum, dtype)
    strictly = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)
    inverse = unit_lower_inverse(both[..., 0, :, :] * strictly * beta[..., None])
    decay = jnp.exp(cum)
    right = jnp.concatenate([decay * k, v.astype(jnp.float32)], axis=-1) * beta[..., None]
    solved = _product(inverse, right, dtype)
    q_seen = decay * q                                     # q as the chunk's first state sees it
    k_left = jnp.exp(cum[..., -1:, :] - cum) * k           # k as the chunk's last state keeps it
    return (solved[..., :dk], solved[..., dk:], q_seen, both[..., 1, :, :], k_left,
            decay[..., -1, :])


# ---------------------------------------------------------------------------
# The inside of a chunk as Pallas kernels (module docstring, "On the TPU")
# ---------------------------------------------------------------------------

def _kernel_geometry_safe(chunk: int, dk: int, dv: int) -> bool:
    """The geometry the kernels take: head widths that are whole lanes, and a
    chunk of whole bfloat16 sublane tiles of which a program's eight, every
    step of theirs at once, fit VMEM (at 128 positions in float32 they do
    not: the chip's compiler refuses the backward kernel)."""
    return dk % 128 == 0 and dv % 128 == 0 and 16 <= chunk <= 64


def takes_kernel(chunk: int, dk: int, dv: int, interpret: Optional[bool] = None) -> bool:
    """Whether ``chunked_gated_delta_rule`` computes a chunk's inside by the
    Pallas kernels: on the TPU (or through the interpreter, for tests), on a
    geometry they take."""
    if interpret is None and jax.default_backend() != "tpu":
        return False
    return _kernel_geometry_safe(chunk, dk, dv)


def _sums(chunk: int) -> np.ndarray:
    """``[(2 + levels) C, C]`` of 0 and 1: the sums of ``g`` over positions
    that give every exponent of a chunk, none of them positive. Block 0: up to
    and with ``i`` (``G_i``); block 1: after ``i`` (``G_C - G_i``); block
    ``2 + l``, ``h = 2**l``: for ``i`` in the later half of its block of ``2 h``
    from that half's first position to ``i`` (``G_i - G_{m-1}``), in the
    earlier half after ``i`` to that half's last (``G_{m-1} - G_i``)."""
    i, t = np.arange(chunk)[:, None], np.arange(chunk)[None, :]
    blocks = [t <= i, t > i]
    h = 1
    while h < chunk:
        first = i // h * h
        later = (i // h) % 2 == 1
        blocks.append(np.where(later, (first <= t) & (t <= i), (i < t) & (t < first + h)))
        h *= 2
    return np.concatenate(blocks).astype(np.float32)


def _exactly(sums, x):
    """``sums @ x`` for ``sums`` of 0 and 1 in bfloat16 and ``x`` float32, to
    float32's precision in three single passes: ``x`` as the sum of three
    bfloat16 terms, each product exact, the accumulation float32."""
    total = None
    for _ in range(3):
        term = x.astype(jnp.bfloat16)
        part = jnp.dot(sums, term, preferred_element_type=jnp.float32)
        total = part if total is None else total + part
        x = x - term.astype(jnp.float32)
    return total


def _dot(a, b, dims, dtype):
    """Tile products, one a chunk (``a, b [G, ., .]``), operands in ``dtype``,
    float32 out."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (dims, ((0,), (0,))),
        precision=_kernel_precision(dtype), preferred_element_type=jnp.float32)


NN, NT, TN = ((2,), (1,)), ((2,), (2,)), ((1,), (1,))


def _diagonal(positions: int):
    """``[positions, positions]`` bool. ``beta`` lies along the lanes (``[1,
    positions]``: a column of it would be padded 128 times over in HBM); a
    sum over the lanes of its diagonal is the column the rows are scaled by."""
    return (jax.lax.broadcasted_iota(jnp.int32, (positions, positions), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (positions, positions), 1))


def _tiles(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref, c):
    """A program's ``G`` chunks in VMEM, each step of the algorithm for all of
    them at once (the products of one chunk hang on one another; those of
    ``G`` chunks fill the MXU's pipeline): blocks ``q, k, g [G C, Dk]``, ``v
    [G C, Dv]``, ``beta [1, G C]`` -> what both kernels need of them, ``[G,
    C, .]`` each."""
    dtype = q_ref.dtype
    q32, k32, v32, g = (ref[...].astype(jnp.float32).reshape(-1, c, ref.shape[-1])
                        for ref in (q_ref, k_ref, v_ref, g_ref))
    diagonal = _diagonal(beta_ref.shape[-1])
    beta = jnp.sum(jnp.where(diagonal, beta_ref[...], 0.0), axis=1, keepdims=True)
    beta = beta.reshape(-1, c, 1)
    sums = sums_ref[...]
    factors = jnp.exp(jnp.stack([_exactly(sums, one) for one in g]))   # every one at most 1
    decay, left = factors[:, :c], factors[:, c:2 * c]
    i, j = _positions(c)
    a = jnp.zeros((q32.shape[0], c, c), jnp.float32)
    b = jnp.where(i == j, jnp.sum(q32 * k32, axis=2, keepdims=True), 0.0)
    for level in range(c.bit_length() - 1):
        f = factors[:, (2 + level) * c:(3 + level) * c]
        # rows of a later half seen from the end of the earlier, columns of an
        # earlier half seen from their own end; the level keeps those blocks
        y = (k32 * f).astype(dtype)
        whole = _dot(jnp.concatenate([y, (q32 * f).astype(dtype)], axis=1), y, NT, dtype)
        kept = _siblings(c, 1 << level)
        a, b = jnp.where(kept, whole[:, :c], a), jnp.where(kept, whole[:, c:], b)
    return SimpleNamespace(
        dtype=dtype, q32=q32, k32=k32, v32=v32, beta=beta, diagonal=diagonal, factors=factors,
        decay=decay, left=left, a=a, b=b, inverse=unit_lower_inverse(a * beta),
        right_k=decay * k32 * beta, right_v=v32 * beta)


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref,
                    w_ref, u_ref, q_seen_ref, scores_ref, k_left_ref, last_ref, *, chunk: int):
    t = _tiles(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref, chunk)
    w_ref[...] = _dot(t.inverse, t.right_k, NN, t.dtype).astype(w_ref.dtype)
    u_ref[...] = _dot(t.inverse, t.right_v, NN, t.dtype)
    q_seen_ref[...] = (t.decay * t.q32).astype(q_seen_ref.dtype)
    scores_ref[...] = t.b.astype(scores_ref.dtype)
    k_left_ref[...] = (t.left * t.k32).astype(k_left_ref.dtype)
    last_ref[...] = t.decay[:, chunk - 1]


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref, sums_t_ref,
                     dw_ref, du_ref, dq_seen_ref, dscores_ref, dk_left_ref, dlast_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *, chunk: int):
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    rows = lambda a: a.reshape(-1, a.shape[-1])  # noqa: E731
    i, j = _positions(chunk)
    t = _tiles(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref, chunk)
    dtype, q32, k32, beta, decay, left, inverse = (
        t.dtype, t.q32, t.k32, t.beta, t.decay, t.left, t.inverse)
    dw, du = dw_ref[...], du_ref[...]
    # [W | U'] = inverse x [right_k | right_v]
    dinverse = _dot(dw, t.right_k, NT, dtype) + _dot(du, t.right_v, NT, dtype)
    dright_k, dright_v = _dot(inverse, dw, TN, dtype), _dot(inverse, du, TN, dtype)
    # X = (I + L)^-1: dL = -X^T dX X^T, of which L's strictly lower part counts
    dlower = jax.lax.dot_general(
        jax.lax.dot_general(inverse, dinverse, (TN, ((0,), (0,))), precision=hi,
                            preferred_element_type=f32),
        inverse, (NT, ((0,), (0,))), precision=hi, preferred_element_type=f32)
    dlower = jnp.where(i > j, -dlower, 0.0)
    dbeta = (jnp.sum(dlower * t.a, axis=2, keepdims=True)
             + jnp.sum(dright_k * decay * k32, axis=2, keepdims=True)
             + jnp.sum(dright_v * t.v32, axis=2, keepdims=True))
    dbeta_ref[...] = jnp.sum(jnp.where(t.diagonal, rows(dbeta), 0.0), axis=0, keepdims=True)
    da, db = dlower * beta, dscores_ref[...].astype(f32)
    dright_k = dright_k * beta
    dq_seen, dk_left = dq_seen_ref[...].astype(f32), dk_left_ref[...].astype(f32)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    ddecay = (dright_k * k32 + dq_seen * q32
              + jnp.where(last_row, dlast_ref[...][:, None, :], 0.0))
    on_diagonal = jnp.sum(jnp.where(i == j, db, 0.0), axis=2, keepdims=True)
    dq = decay * dq_seen + on_diagonal * k32
    dk = decay * dright_k + left * dk_left + on_diagonal * q32
    dsums = [ddecay * decay, dk_left * k32 * left]
    for level in range(chunk.bit_length() - 1):
        f = t.factors[:, (2 + level) * chunk:(3 + level) * chunk]
        both = jnp.concatenate([(k32 * f).astype(dtype), (q32 * f).astype(dtype)], axis=1)
        kept = _siblings(chunk, 1 << level)
        dwhole = jnp.concatenate([jnp.where(kept, da, 0.0), jnp.where(kept, db, 0.0)], axis=1)
        as_rows = _dot(dwhole, both[:, :chunk], NN, dtype)
        dy = as_rows[:, :chunk] + _dot(dwhole, both, TN, dtype)
        dz = as_rows[:, chunk:]
        dk, dq = dk + f * dy, dq + f * dz
        dsums.append(f * (k32 * dy + q32 * dz))
    dq_ref[...] = rows(dq).astype(dq_ref.dtype)
    dk_ref[...] = rows(dk).astype(dk_ref.dtype)
    dv_ref[...] = rows(dright_v * beta).astype(dv_ref.dtype)
    dsums = jnp.concatenate(dsums, axis=1)
    dg_ref[...] = jnp.concatenate([_exactly(sums_t_ref[...], one) for one in dsums])


def _calls(inputs, chunk):
    """What both ``pallas_call``s share: the grid of (sequence x head, group
    of ``G`` chunks); the five inputs' blocks (by position: ``[BH, N C,
    width]``, ``beta [BH, 1, N C]``) and shapes; the six arrays' (by chunk,
    as the scan takes them: ``[N, BH, C, width]``; but ``last [BH, N, Dk]``:
    a block of one row a chunk is more than the chip's compiler lowers)."""
    (bh, s, dk), dv, dtype = inputs[0].shape, inputs[2].shape[-1], inputs[0].dtype
    n = s // chunk
    per_program = min(n, PER_PROGRAM)
    rows = per_program * chunk

    def by_position(width):
        return pl.BlockSpec((None, rows, width), lambda b, m: (b, m, 0))

    def by_chunk(tall, width):
        return pl.BlockSpec((per_program, None, tall, width), lambda b, m: (m, b, 0, 0))
    blocks = [by_position(dk), by_position(dk), by_position(dv), by_position(dk),
              pl.BlockSpec((None, 1, rows), lambda b, m: (b, 0, m))]
    five = [(chunk, dk, dtype), (chunk, dv, jnp.float32), (chunk, dk, dtype),
            (chunk, chunk, dtype), (chunk, dk, dtype)]
    return dict(
        grid=(bh, n // per_program), inputs=blocks,
        input_shapes=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in inputs],
        six=[by_chunk(tall, width) for tall, width, _ in five]
        + [pl.BlockSpec((None, per_program, dk), lambda b, m: (b, m, 0))],
        six_shapes=[jax.ShapeDtypeStruct((n, bh, tall, width), kind) for tall, width, kind in five]
        + [jax.ShapeDtypeStruct((bh, n, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # a program's blocks twice over and its chunks' tiles, all live at
            # once (float32, chunks of 64: over the default scope of 16 MiB)
            vmem_limit_bytes=64 * 2 ** 20))


def _constant(a):
    return pl.BlockSpec(a.shape, lambda b, m: (0, 0))


def _inside_forward(q, k, v, g, beta, chunk, interpret):
    call = _calls((q, k, v, g, beta), chunk)
    sums = jnp.asarray(_sums(chunk), jnp.bfloat16)
    return pl.pallas_call(
        functools.partial(_forward_kernel, chunk=chunk), grid=call["grid"],
        in_specs=call["inputs"] + [_constant(sums)],
        out_specs=call["six"], out_shape=call["six_shapes"],
        compiler_params=call["compiler_params"], interpret=interpret,
    )(q, k, v, g, beta, sums)


def _inside_backward(q, k, v, g, beta, cotangents, chunk, interpret):
    call = _calls((q, k, v, g, beta), chunk)
    sums = _sums(chunk)
    sums, sums_t = jnp.asarray(sums, jnp.bfloat16), jnp.asarray(sums.T, jnp.bfloat16)
    return pl.pallas_call(
        functools.partial(_backward_kernel, chunk=chunk), grid=call["grid"],
        in_specs=call["inputs"] + [_constant(sums), _constant(sums_t)] + call["six"],
        out_specs=call["inputs"], out_shape=call["input_shapes"],
        compiler_params=call["compiler_params"], interpret=interpret,
    )(q, k, v, g, beta, sums, sums_t, *cotangents)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _inside_kernels(q, k, v, g, beta, chunk, interpret):
    """``_inside`` by the kernels: ``q, k, g [BH, N C, Dk]``, ``v [BH, N C,
    Dv]``, ``beta [BH, 1, N C]`` -> the six arrays, the five large ones with
    the chunks in front as the scan takes them (``[N, BH, C, .]``: no
    transposed copy on the way in or, for the cotangents, out; ``last [BH, N,
    Dk]``); what only ever enters a tile product leaves in ``q``'s dtype. Its
    backward pass is the second kernel, which keeps nothing but the inputs."""
    return tuple(_inside_forward(q, k, v, g, beta, chunk, interpret))


def _inside_kernels_fwd(q, k, v, g, beta, chunk, interpret):
    return _inside_kernels(q, k, v, g, beta, chunk, interpret), (q, k, v, g, beta)


def _inside_kernels_bwd(chunk, interpret, inputs, cotangents):
    return tuple(_inside_backward(*inputs, cotangents, chunk, interpret))


_inside_kernels.defvjp(_inside_kernels_fwd, _inside_kernels_bwd)


def chunked_gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64,
                             interpret: Optional[bool] = None):
    """``q, k [..., S, Dk]``, ``v [..., S, Dv]``, the log decay ``g [..., S, Dk]``
    (at most 0) and ``beta [..., S]`` -> ``o [..., S, Dv]`` in ``v``'s dtype.
    ``chunk`` is a power of two; a sequence that is no multiple of it is
    padded with positions that leave the state as it is. The inside of a
    chunk is the kernels' on the TPU on a geometry they take
    (``takes_kernel``; ``interpret=True`` runs them through the Pallas
    interpreter anywhere, for tests), XLA's products otherwise."""
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is no power of two")
    s, dk = q.shape[-2:]
    dv, dtype, lead = v.shape[-1], q.dtype, q.shape[:-2]
    kernels = takes_kernel(chunk, dk, dv, interpret)
    n = -(-s // chunk)
    if kernels and n > PER_PROGRAM:
        n = -(-n // PER_PROGRAM) * PER_PROGRAM             # whole programs
    if n * chunk != s:
        pad = [(0, 0)] * len(lead) + [(0, n * chunk - s)]
        q, k, v, g = (jnp.pad(a, pad + [(0, 0)]) for a in (q, k, v, g))
        beta = jnp.pad(beta, pad)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)

    at = len(lead)
    if kernels:
        flat = lambda a: a.reshape(-1, *a.shape[at:])  # noqa: E731
        *along, last = _inside_kernels(flat(q), flat(k), flat(v), flat(g),
                                       flat(beta)[:, None, :], chunk, bool(interpret))
        along = [a.reshape(n, *lead, *a.shape[2:]) for a in along]
        along.append(jnp.moveaxis(last.reshape(*lead, n, dk), at, 0))
    else:
        chunks = lambda a: a.reshape(*lead, n, chunk, a.shape[-1])  # noqa: E731
        along = [jnp.moveaxis(a, at, 0) for a in _inside(
            chunks(q), chunks(k), chunks(v), chunks(g), beta.reshape(*lead, n, chunk), dtype)]

    def one_chunk(state, at):
        w, u_fresh, q_seen, scores, k_left, last = at
        u = u_fresh - _product(w, state, dtype)
        o = _product(q_seen, state, dtype) + _product(scores, u, dtype)
        state = last[..., :, None] * state + _product(_transposed(k_left), u, dtype)
        return state, o

    first = jnp.zeros((*lead, dk, dv), jnp.float32)
    o = jnp.moveaxis(jax.lax.scan(one_chunk, first, tuple(along))[1], 0, at)
    return o.reshape(*lead, n * chunk, dv)[..., :s, :].astype(v.dtype)
