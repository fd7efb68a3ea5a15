"""The held experts' matrix products over rows sorted by expert.

``grouped_matmul(rows, weights, group_sizes)`` is ``rows[a:b] @ weights[g]``
for each group ``g`` of consecutive rows. One writing (ROADMAP D2): the
grouped kernel of ``jax.experimental.pallas.ops.tpu.megablox`` with its
hand-written backward (the same kernel transposed for the rows' gradient, its
twin ``tgmm`` for the weights'), compiled on a TPU and run in interpret mode
everywhere else (``ops.interpret_mode``). Its grid runs over the row tiles
that a group really covers, read from ``group_sizes`` on the device: tiles
past the last group are not visited, so the time follows the rows really
routed here and not the buffer, which is sized for the worst case.

Rows past the last group are never written by the kernel; they come back
as zeros, and take no gradient.
"""

from __future__ import annotations

import jax.numpy as jnp

from replication_faster_rcnn_tpu.ops import interpret_mode

Array = jnp.ndarray

TILE_ROWS = 512
TILE_DEPTH = 1024  # along the contraction
TILE_COLS = 1024


def row_tile(m: int) -> int:
    """The row tile used for ``m`` rows; ``m`` must be a multiple of it."""
    for tile in (TILE_ROWS, 256, 128):
        if m % tile == 0:
            return tile
    raise ValueError(f"{m} rows: the grouped product wants a multiple of 128")


def grouped_matmul(rows: Array, weights: Array, group_sizes: Array) -> Array:
    """``rows``: ``[M, K]`` sorted by group; ``weights``: ``[G, K, N]``;
    ``group_sizes``: ``[G]`` int32 with ``sum <= M``. Returns ``[M, N]`` in
    ``rows``' dtype (float32 accumulation), zero past the last group."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = rows.shape
    n = weights.shape[2]
    tiling = (row_tile(m), min(TILE_DEPTH, k), min(TILE_COLS, n))
    valid = (jnp.arange(m) < jnp.sum(group_sizes))[:, None]
    # select on both sides: the kernel leaves rows past the last group
    # unwritten, in the product and in the rows' gradient alike
    rows = jnp.where(valid, rows, 0)
    out = gmm(
        rows, weights.astype(rows.dtype), group_sizes.astype(jnp.int32),
        rows.dtype, tiling, None, None, False, interpret_mode(),
    )
    return jnp.where(valid, out, 0)
