"""Pallas dense IoU matrix + anchor matching — exact vs the jnp pass.

Target assignment (`targets/anchor_targets.py`, `targets/proposal_targets.py`)
opens with the same shape of work: a dense ``[N, G]`` IoU matrix against the
(padded) gt boxes, masked to -1 on padded gt columns, then row argmax/max and
— for the RPN pass — the per-gt best anchor (column argmax). For 16k+ anchors
that matrix is the dominant cost of the pass and XLA materializes it through
HBM; here it is tiled over the anchor axis with the matching reductions fused
in VMEM, one grid step per anchor tile.

Exactness: the in-kernel IoU replicates `ops/boxes.py::iou` op-for-op
(elementwise IEEE arithmetic — bitwise equal); row max is the same
``jnp.max(jnp.maximum(x, 0.0))`` on the same values; each argmax is the
lowest index holding the maximum, which is ``jnp.argmax``'s first-occurrence
rule (the IoUs are never NaN); and the column argmax streams across tiles
with a strictly-greater update, which keeps that rule across tiles (padded
anchor rows are forced to -1 and sit after all real rows, so they can tie but
never win). Tier-1 pins all four outputs bitwise (tests/test_pallas_iou.py).

Like the NMS kernel, everything in the body is 2-D with reductions keeping
their dims: per-anchor results are columns ``[tile, 1]``, per-gt results rows
``[1, G]``, and the indices are reduced as float32 (exact below 2**24).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from replication_faster_rcnn_tpu.ops.pallas.nms_kernel import (
    _cols,
    _iou_grid,
    _rows,
)

Array = jnp.ndarray


def _match_kernel(
    z_ref,
    a_ref,
    g_ref,
    m_ref,
    iou_ref,
    am_ref,
    mx_ref,
    best_ref,
    bval_ref,
    *,
    tile: int,
    n_rows: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        best_ref[...] = jnp.zeros_like(best_ref)
        bval_ref[...] = jnp.full_like(bval_ref, -jnp.inf)

    g_count = g_ref.shape[1]
    ious = _iou_grid(_cols(a_ref), _rows(g_ref), z_ref[0, 0])  # [tile, G]
    ious = jnp.where(m_ref[...] != 0, ious, -1.0)  # padded gt cols
    # padded anchor rows (beyond n_rows) must never win the column argmax;
    # they sit after every real row, so forcing -1 lets them tie but not beat
    row_id = jax.lax.broadcasted_iota(jnp.int32, (tile, g_count), 0) + i * tile
    col_id = jax.lax.broadcasted_iota(jnp.int32, (tile, g_count), 1)
    ious = jnp.where(row_id < n_rows, ious, -1.0)

    iou_ref[...] = ious
    row_max = jnp.max(ious, axis=1, keepdims=True)  # [tile, 1]
    am_ref[...] = jnp.min(
        jnp.where(ious == row_max, col_id.astype(jnp.float32), float(g_count)),
        axis=1,
        keepdims=True,
    ).astype(jnp.int32)
    mx_ref[...] = jnp.max(jnp.maximum(ious, 0.0), axis=1, keepdims=True)

    # streaming column argmax: strictly-greater keeps the earliest row on
    # ties, matching jnp.argmax(axis=0) first-occurrence semantics
    col_max = jnp.max(ious, axis=0, keepdims=True)  # [1, G]
    col_arg = jnp.min(
        jnp.where(ious == col_max, row_id.astype(jnp.float32), float(2**24)),
        axis=0,
        keepdims=True,
    ).astype(jnp.int32)
    prev = bval_ref[...]
    beat = col_max > prev
    bval_ref[...] = jnp.where(beat, col_max, prev)
    best_ref[...] = jnp.where(beat, col_arg, best_ref[...])


@partial(jax.jit, static_argnames=("tile", "interpret", "want_col"))
def _match_boxes_pallas(
    boxes: Array,
    gt_boxes: Array,
    gt_mask: Array,
    tile: int,
    interpret: bool,
    want_col: bool,
):
    n = boxes.shape[0]
    g = gt_boxes.shape[0]
    tile = min(tile, max(n, 1))
    n_tiles = -(-n // tile)
    pad = n_tiles * tile - n

    rows = jnp.pad(boxes.astype(jnp.float32), ((0, pad), (0, 0)))  # [n_pad, 4]
    gt_cols = gt_boxes.astype(jnp.float32).T  # [4, G]
    mask_row = gt_mask.astype(jnp.int32)[None, :]  # [1, G]

    zero = jnp.zeros((1, 1), jnp.float32)  # runtime +0.0, see _iou_grid
    # keep the pad/transpose producers out of the kernel body's fusion: on
    # XLA:CPU, fusing them in changes LLVM vectorization of the inlined
    # (interpret-mode) kernel and can drift the final division by 1 ulp
    zero, rows, gt_cols, mask_row = jax.lax.optimization_barrier(
        (zero, rows, gt_cols, mask_row)
    )
    ious_p, am_p, mx_p, best_p = pl.pallas_call(
        partial(_match_kernel, tile=tile, n_rows=n),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((tile, 4), lambda i: (i, 0)),
            pl.BlockSpec((4, g), lambda i: (0, 0)),
            pl.BlockSpec((1, g), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, g), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, g), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles * tile, g), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles * tile, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles * tile, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, g), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, g), jnp.float32)],
        interpret=interpret,
    )(zero, rows, gt_cols, mask_row)

    out = (ious_p[:n], am_p[:n, 0], mx_p[:n, 0])
    if want_col:
        return out + (best_p[0],)
    return out


def _resolve_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def match_boxes_pallas(
    boxes: Array,
    gt_boxes: Array,
    gt_mask: Array,
    tile: int = 512,
    interpret: bool | None = None,
) -> tuple[Array, Array, Array, Array]:
    """The RPN matching pass: boxes [N, 4], gt [G, 4], gt_mask [G] ->
    (ious [N, G] masked to -1 on padded gt, argmax [N] int32,
    max_iou [N] f32, gt_best [G] int32) — all bitwise equal to the jnp
    formulation in `targets/anchor_targets.py`."""
    return _match_boxes_pallas(
        boxes, gt_boxes, gt_mask, tile, _resolve_interpret(interpret), True
    )


def iou_matrix_pallas(
    boxes: Array,
    gt_boxes: Array,
    gt_mask: Array,
    tile: int = 512,
    interpret: bool | None = None,
) -> tuple[Array, Array, Array]:
    """The head-assignment variant (no column argmax): returns
    (ious [N, G], argmax [N], max_iou [N]) as in
    `targets/proposal_targets.py`."""
    return _match_boxes_pallas(
        boxes, gt_boxes, gt_mask, tile, _resolve_interpret(interpret), False
    )
