"""Tiled exact greedy NMS — fewer sequential steps than the selection loop.

`ops/nms.py::nms_fixed` runs one sequential iteration per SELECTED box
(``max_out`` = 600 at the training budget), each doing a small vector pass —
on TPU that cost is dispatch/latency, not FLOPs. This module computes the
identical greedy result with one sequential step per TILE of candidates
plus a short in-tile fixpoint, the structure TPU NMS implementations use
(cf. TF's ``non_max_suppression_padded``): for 12k candidates at tile 512
that is ~25-75 sequential steps of dense [512, 512] / [max_out, 512] IoU
matrix work (VPU-friendly) instead of 600.

Exactness argument (parity-tested against ``nms_fixed``):
  * candidates are processed in descending-score order (stable sort — ties
    break on the lower original index, same as the loop's first-max argmax);
  * a box is greedy-kept iff it is valid and no earlier-ordered KEPT box
    overlaps it above threshold. Within a tile this recurrence
    ``g[b] = m0[b] & ~any_{a<b}(g[a] & S[a,b])`` is solved by fixpoint
    iteration of the whole vector: after k sweeps the first k entries are
    exact, and any fixpoint satisfies the (uniquely-determined) recurrence,
    so the early-exit-on-stable while_loop returns exactly greedy;
  * boxes selected in earlier tiles are the only cross-tile suppressors,
    and at most ``max_out`` selections are ever needed, so cross-tile
    suppression tests each tile against the compact selected-box buffer in
    ONE matrix op; the outer loop stops as soon as the buffer fills.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from replication_faster_rcnn_tpu.ops import boxes as box_ops

Array = jnp.ndarray

_NEG = -jnp.inf


@partial(jax.jit, static_argnames=("max_out", "tile", "assume_sorted"))
def nms_fixed_tiled(
    boxes: Array,
    scores: Array,
    iou_thresh: float,
    max_out: int,
    mask: Array | None = None,
    tile: int = 512,
    assume_sorted: bool = False,
) -> tuple[Array, Array]:
    """Drop-in replacement for :func:`ops.nms.nms_fixed` (same contract:
    [max_out] int32 indices in selection order + [max_out] validity).

    ``assume_sorted``: the caller guarantees ``scores`` (after applying
    ``mask``) are already non-increasing, so the internal stable sort and
    its gathers are skipped. The proposal path uses this to sort ONCE:
    its top-pre_nms selection is one stable sort that carries the boxes
    with the scores (`models/rpn.py::select_proposals`), so what arrives
    here is already the descending candidate list, and neither a second
    sort nor a pick by index stands between the selection and the loop.
    """
    n = boxes.shape[0]
    tile = min(tile, max(n, 1))
    s = scores.astype(jnp.float32)
    s = jnp.where(jnp.isfinite(s), s, _NEG)
    if mask is not None:
        s = jnp.where(mask, s, _NEG)

    n_tiles = -(-n // tile)
    n_pad = n_tiles * tile
    pad = n_pad - n
    if assume_sorted:
        order_p = jnp.pad(jnp.arange(n, dtype=jnp.int32), (0, pad))
        s_sorted = jnp.pad(s, (0, pad), constant_values=_NEG)
        b_sorted = jnp.pad(boxes.astype(jnp.float32), ((0, pad), (0, 0)))
    else:
        # stable descending-score order; ties keep ascending original
        # index, matching nms_fixed's first-occurrence argmax
        order = jnp.argsort(-s)
        order_p = jnp.pad(order, (0, pad)).astype(jnp.int32)
        s_sorted = jnp.pad(s[order], (0, pad), constant_values=_NEG)
        b_sorted = jnp.pad(
            boxes.astype(jnp.float32)[order], ((0, pad), (0, 0))
        )
    valid_sorted = s_sorted > _NEG

    later = (
        jnp.arange(tile, dtype=jnp.int32)[:, None]
        < jnp.arange(tile, dtype=jnp.int32)[None, :]
    )  # a before b

    def outer_cond(st):
        i, count, _, _ = st
        return (i < n_tiles) & (count < max_out)

    def outer_body(st):
        i, count, sel_boxes, sel_idx = st
        tb = jax.lax.dynamic_slice_in_dim(b_sorted, i * tile, tile)
        tv = jax.lax.dynamic_slice_in_dim(valid_sorted, i * tile, tile)
        ti = jax.lax.dynamic_slice_in_dim(order_p, i * tile, tile)

        # cross-tile: suppressed by any already-selected box (one matrix op)
        kmask = jnp.arange(max_out, dtype=jnp.int32) < count
        cross = box_ops.iou(sel_boxes, tb) > iou_thresh  # [max_out, tile]
        m0 = tv & ~jnp.any(cross & kmask[:, None], axis=0)

        # in-tile greedy via fixpoint sweeps (exact; see module docstring)
        suppress = (box_ops.iou(tb, tb) > iou_thresh) & later

        def sweep_cond(gs):
            _, stable = gs
            return ~stable

        def sweep_body(gs):
            g, _ = gs
            g2 = m0 & ~jnp.any(suppress & g[:, None], axis=0)
            return g2, jnp.all(g2 == g)

        g, _ = jax.lax.while_loop(sweep_cond, sweep_body, (m0, jnp.array(False, dtype=bool)))

        # append this tile's selections to the compact buffers (in order)
        pos = count + jnp.cumsum(g) - 1
        slot = jnp.where(g & (pos < max_out), pos, max_out)  # overflow -> drop
        sel_boxes = sel_boxes.at[slot].set(tb, mode="drop")
        sel_idx = sel_idx.at[slot].set(ti, mode="drop")
        count = jnp.minimum(count + jnp.sum(g), max_out).astype(jnp.int32)
        return i + 1, count, sel_boxes, sel_idx

    init = (
        jnp.int32(0),
        jnp.int32(0),
        jnp.zeros((max_out, 4), jnp.float32),
        jnp.zeros((max_out,), jnp.int32),
    )
    _, count, _, sel_idx = jax.lax.while_loop(outer_cond, outer_body, init)
    valid = jnp.arange(max_out, dtype=jnp.int32) < count
    return jnp.where(valid, sel_idx, 0), valid
