"""RPN (first-stage) target assignment — device-side, fixed-shape.

Capability parity with reference ``AnchorTargetCreator``
(`utils/utils.py:122-204`), redesigned to run inside the jitted train step
(the reference runs it per-image in host numpy inside the training loop,
`train.py:71-79` — SURVEY.md layering violation #1):

  * label -1 = ignore (default), 0 = negative (max IoU < neg_thresh),
    1 = positive (max IoU >= pos_thresh)           (`utils/utils.py:181-189`)
  * each gt's best-overlapping anchor is force-positive, and its regression
    target points at that gt                        (`utils/utils.py:169-173,187-189`)
  * random subsample: at most pos_ratio * n_sample positives, negatives
    fill the rest of n_sample                       (`utils/utils.py:190-202`)
  * regression targets encode(anchor, matched gt) for ALL anchors; zeros
    when the image has no gt                        (`utils/utils.py:145-150,162-163`)

GT boxes arrive padded to a fixed max count with a validity mask (the data
pipeline pads with -1 labels, reference `utils/data_loader.py:88-89`).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from replication_faster_rcnn_tpu.config import RPNTargetConfig
from replication_faster_rcnn_tpu.ops import boxes as box_ops
from replication_faster_rcnn_tpu.targets.sampling import random_subset_mask

Array = jnp.ndarray


def matched_boxes(gt_boxes: Array, argmax: Array) -> Array:
    """Each anchor's matched gt box, ``gt_boxes[argmax]`` without a gather.

    gt_boxes: [G, 4]; argmax: [A] int in [0, G) -> [A, 4]. G is
    ``data.max_boxes`` (32; 100 on COCO), so compare-and-select over the G
    rows is A x G x 4 selects an image, and exact: one row survives and the
    sum adds zeros. The indexed writing is an XLA gather of A indices into
    those G rows, which the chip serves at 9-13 ns an index: 1.85 ms a step
    at 32 x 12,996 anchors (PERF.md, PR 28).
    """
    picked = argmax[:, None] == jnp.arange(gt_boxes.shape[0], dtype=argmax.dtype)
    return jnp.where(picked[:, :, None], gt_boxes[None], 0.0).sum(1)


def anchor_targets(
    rng: Array,
    gt_boxes: Array,
    gt_mask: Array,
    anchors: Array,
    cfg: RPNTargetConfig,
) -> Tuple[Array, Array]:
    """Per-image RPN targets.

    Args:
      rng: PRNG key (subsampling).
      gt_boxes: [G, 4] padded gt boxes; gt_mask: [G] bool validity.
      anchors: [A, 4].
      cfg: thresholds/budgets.

    Returns:
      (reg_targets [A, 4] float32, labels [A] int32 in {-1, 0, 1}).
    """
    a = anchors.shape[0]
    has_gt = jnp.any(gt_mask)

    from replication_faster_rcnn_tpu import ops as ops_pkg

    if ops_pkg.want_pallas("anchor_match"):
        # the fused matching kernel: same ious/argmax/max/column-argmax as
        # the jnp lines below (tests/test_pallas_iou.py pins all four)
        from replication_faster_rcnn_tpu.ops.pallas import match_boxes_pallas

        ious, argmax, max_iou, gt_best_anchor = match_boxes_pallas(
            anchors, gt_boxes, gt_mask, interpret=ops_pkg.interpret_mode()
        )
    else:
        ious = box_ops.iou(anchors, gt_boxes)  # [A, G]
        ious = jnp.where(gt_mask[None, :], ious, -1.0)  # never match padded gt

        argmax = jnp.argmax(ious, axis=1)  # [A] best gt per anchor
        max_iou = jnp.max(jnp.maximum(ious, 0.0), axis=1)  # [A]

        # Force-positive each gt's best anchor and redirect its match to
        # that gt (`utils/utils.py:169-173`).
        gt_best_anchor = jnp.argmax(ious, axis=0)  # [G]

    scatter_rows = jnp.where(gt_mask, gt_best_anchor, a)  # a = dropped
    argmax = argmax.at[scatter_rows].set(
        jnp.arange(gt_boxes.shape[0], dtype=jnp.int32), mode="drop"
    )
    forced = jnp.zeros((a,), bool).at[scatter_rows].set(True, mode="drop")

    labels = jnp.full((a,), -1, jnp.int32)
    labels = jnp.where(max_iou < cfg.neg_iou_thresh, 0, labels)
    labels = jnp.where(max_iou >= cfg.pos_iou_thresh, 1, labels)
    labels = jnp.where(forced & has_gt, 1, labels)

    # Subsample (`utils/utils.py:190-202`): cap positives at n_pos, then
    # negatives fill to n_sample.
    n_pos = int(cfg.pos_ratio * cfg.n_sample)
    rng_pos, rng_neg = jax.random.split(rng)
    pos_keep = random_subset_mask(rng_pos, labels == 1, n_pos, k_max=n_pos)
    labels = jnp.where((labels == 1) & ~pos_keep, -1, labels)
    n_neg = cfg.n_sample - jnp.sum(labels == 1)
    neg_keep = random_subset_mask(rng_neg, labels == 0, n_neg, k_max=cfg.n_sample)
    labels = jnp.where((labels == 0) & ~neg_keep, -1, labels)

    reg = box_ops.encode(anchors, matched_boxes(gt_boxes, argmax))
    reg = jnp.where(has_gt, reg, 0.0)  # empty-gt path (`utils/utils.py:162-163`)
    labels = jnp.where(has_gt, labels, jnp.where(labels == 1, -1, labels))
    return reg.astype(jnp.float32), labels


def batched_anchor_targets(
    rng: Array,
    gt_boxes: Array,
    gt_mask: Array,
    anchors: Array,
    cfg: RPNTargetConfig,
    positions: Array = None,
) -> Tuple[Array, Array]:
    """vmap over the batch: gt_boxes [N, G, 4], gt_mask [N, G] ->
    (reg [N, A, 4], labels [N, A]).

    ``positions`` (global batch positions, [N] int) makes the per-image
    keys sharding-invariant — fold_in(rng, position) gives each image the
    same key whether the batch is whole (jit auto-partitioning) or a
    shard_map slice (`parallel/spmd.py`). Without it, keys are split by
    local batch size (fine when every caller sees the full batch).
    """
    if positions is None:
        keys = jax.random.split(rng, gt_boxes.shape[0])
    else:
        keys = jax.vmap(lambda p: jax.random.fold_in(rng, p))(positions)
    return jax.vmap(lambda k, b, m: anchor_targets(k, b, m, anchors, cfg))(
        keys, gt_boxes, gt_mask
    )
