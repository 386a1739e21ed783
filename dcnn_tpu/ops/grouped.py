"""Grouped matrix product: rows of ``x`` sorted by group, one weight matrix
per group, no row dropped and none padded.

``grouped_matmul(x [M, K], w [G, K, N], group_sizes [G]) -> [M, N]``: rows
``sum(group_sizes[:g]) .. sum(group_sizes[:g + 1])`` are multiplied by
``w[g]``. ``sum(group_sizes)`` may be less than ``M``: the work follows the
group sizes, and the rows past the last group come out as zeros and take no
gradient. This is the product of an expert layer that computes every (token,
expert) pair whatever the imbalance (``nn/moe.py``, which calls it on rounds
of ``padded_rows`` rows, as many as a step's held pairs need).

On the TPU it is the Pallas grouped product that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox``: a grid over the row tiles the
groups cover, its two transposes by the same kernels). Measured against
``jax.lax.ragged_dot`` on one v5e at the cell's shapes (98,304 rows of which
12,288 in 8 uneven groups, 2048 -> 1408 -> 2048, the three products forward
and backward, by ``tools/bench_lm_kernels.py``, PR 32): 14.42 ms against
19.27 (even groups 12.88 against 17.17; every row held 47.3 against 75.6),
and XLA names its own kernels ``ragged-dot-none`` whatever the scope, so a
trace cannot tell their layers apart. Of those 14.42 ms about 9.7 were paid
at zero rows held: the passes round the kernel over all 98,304 rows. One
expert layer's whole routed part, dispatch through combine, forward and
backward (``bench_lm_kernels.py routed``, PR 33, PR 32's form over
98,304-row buffers against ``nn/moe.py``'s rounds of 24,576 rows): 41.16 ->
19.92 ms at 12,288 held pairs, 48.76 -> 35.08 at 25,800, 87.44 -> 87.17 at
98,304. Both products leave rows past the last group as they find them
(read at 11.3 where nought was due), hence the masks below. Off the TPU the
product is ``ragged_dot``, as ``flash_attention`` is ``blockwise_attention``
there; ``interpret=True`` forces the kernel through the Pallas interpreter
for tests.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.precision import get_precision
from ..obs import get_registry

# (rows, contraction, columns) a program of the kernel's grid works on: the
# best of those tried at the shapes above (512/1024/1024: 14.57 ms)
TILING = (512, 512, 1024)
ROW_TILES = (TILING[0], 256, 128, 64, 32, 16, 8)


def _row_tile(m: int) -> int:
    for tile in ROW_TILES:
        if m % tile == 0:
            return tile
    raise ValueError(f"grouped_matmul: {m} rows are not a multiple of 8")


def padded_rows(rows: int) -> int:
    """``rows`` rounded up to a row tile of the product: the largest that is
    at most a sixteenth of them, so that little of the buffer is padding."""
    tile = next((t for t in ROW_TILES if 16 * t <= rows), ROW_TILES[-1])
    return -(-rows // tile) * tile


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    get_registry().counter(
        "moe_grouped_products_total",
        "grouped matrix products in traced programs (forward ones; three per "
        "expert layer)").inc()
    sizes = group_sizes.astype(jnp.int32)
    live = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]
    x = jnp.where(live, x, 0)             # its transpose: no gradient past the groups
    if interpret is None and jax.default_backend() != "tpu":
        out = jax.lax.ragged_dot(x, w, sizes, precision=get_precision(),
                                 preferred_element_type=x.dtype)
    else:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        out = megablox.gmm(x, w, sizes, preferred_element_type=x.dtype,
                           tiling=(_row_tile(x.shape[0]),) + TILING[1:],
                           interpret=bool(interpret))
    return jnp.where(live, out, 0)
