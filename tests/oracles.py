"""Independent numpy oracles for golden tests.

These reimplement the *documented semantics* of the ops under test (greedy
NMS, Caffe-style ROIPool, torchvision ROIAlign, the reference's box coder /
IoU / target assignment) in straightforward numpy, written separately from
the jnp implementations so a shared bug can't hide. The reference repo's
numpy code is the behavioral spec (file:line cites in each function) but the
code here is written fresh — torchvision is not installed in this image, so
these stand in for the torchvision CPU goldens SURVEY.md §4b suggests.
"""

from __future__ import annotations

import numpy as np


# ---------------------------------------------------------------- box coder

def encode_np(anchors: np.ndarray, boxes: np.ndarray) -> np.ndarray:
    """Spec: reference bbox2reg (utils/utils.py:75-100)."""
    ah = anchors[:, 2] - anchors[:, 0]
    aw = anchors[:, 3] - anchors[:, 1]
    ar = (anchors[:, 0] + anchors[:, 2]) / 2
    ac = (anchors[:, 1] + anchors[:, 3]) / 2
    bh = boxes[:, 2] - boxes[:, 0]
    bw = boxes[:, 3] - boxes[:, 1]
    br = (boxes[:, 0] + boxes[:, 2]) / 2
    bc = (boxes[:, 1] + boxes[:, 3]) / 2
    return np.stack(
        [(br - ar) / ah, (bc - ac) / aw, np.log(bh / ah), np.log(bw / aw)], axis=1
    )


def decode_np(anchors: np.ndarray, deltas: np.ndarray) -> np.ndarray:
    """Spec: reference reg2bbox (utils/utils.py:47-73)."""
    ah = anchors[:, 2] - anchors[:, 0]
    aw = anchors[:, 3] - anchors[:, 1]
    ar = (anchors[:, 0] + anchors[:, 2]) / 2
    ac = (anchors[:, 1] + anchors[:, 3]) / 2
    r = deltas[:, 0] * ah + ar
    c = deltas[:, 1] * aw + ac
    h = np.exp(deltas[:, 2]) * ah
    w = np.exp(deltas[:, 3]) * aw
    return np.stack([r - h / 2, c - w / 2, r + h / 2, c + w / 2], axis=1)


def iou_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Spec: reference bbox_iou (utils/utils.py:102-119), safe division."""
    tl = np.maximum(a[:, None, :2], b[None, :, :2])
    br = np.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = np.clip(br - tl, 0, None)
    inter = wh[..., 0] * wh[..., 1]
    area_a = (a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1])
    area_b = (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
    union = area_a[:, None] + area_b[None, :] - inter
    out = np.zeros_like(inter, dtype=np.float64)
    np.divide(inter, union, out=out, where=union > 0)
    return out


# ------------------------------------------------------------------- NMS

def nms_np(boxes: np.ndarray, scores: np.ndarray, thresh: float) -> list[int]:
    """Sort-by-score greedy suppression (torchvision.ops.nms semantics:
    drop IoU strictly greater than thresh)."""
    order = np.argsort(-scores, kind="stable")
    keep: list[int] = []
    alive = np.ones(len(boxes), bool)
    for i in order:
        if not alive[i]:
            continue
        keep.append(int(i))
        ious = iou_np(boxes[i : i + 1], boxes)[0]
        alive &= ~(ious > thresh)
    return keep


# ----------------------------------------------------------------- ROI ops

def roi_pool_np(feat: np.ndarray, rois: np.ndarray, out: int = 7) -> np.ndarray:
    """Legacy Caffe/torchvision ROIPool: round coords, +1 extents,
    floor/ceil bin edges, empty bin -> 0. feat [H, W, C] -> [R, out, out, C]."""
    h, w, c = feat.shape
    res = np.zeros((len(rois), out, out, c), feat.dtype)
    for ri, roi in enumerate(rois):
        r1, c1, r2, c2 = np.round(roi)
        rh = max(r2 - r1 + 1, 1)
        rw = max(c2 - c1 + 1, 1)
        bh, bw = rh / out, rw / out
        for i in range(out):
            hs = int(np.clip(np.floor(i * bh) + r1, 0, h))
            he = int(np.clip(np.ceil((i + 1) * bh) + r1, 0, h))
            for j in range(out):
                ws = int(np.clip(np.floor(j * bw) + c1, 0, w))
                we = int(np.clip(np.ceil((j + 1) * bw) + c1, 0, w))
                if he > hs and we > ws:
                    res[ri, i, j] = feat[hs:he, ws:we].max(axis=(0, 1))
    return res


def roi_align_np(
    feat: np.ndarray, rois: np.ndarray, out: int = 7, sampling: int = 2
) -> np.ndarray:
    """torchvision ROIAlign (aligned=False): fixed sampling^2 bilinear
    samples per bin, averaged; out-of-range samples contribute 0."""
    h, w, c = feat.shape

    def bilin(r, cc):
        if r < -1 or r > h or cc < -1 or cc > w:
            return np.zeros(c, feat.dtype)
        r = min(max(r, 0.0), h - 1.0)
        cc = min(max(cc, 0.0), w - 1.0)
        r0, c0 = int(np.floor(r)), int(np.floor(cc))
        r1, c1 = min(r0 + 1, h - 1), min(c0 + 1, w - 1)
        ar, ac = r - r0, cc - c0
        return (
            feat[r0, c0] * (1 - ar) * (1 - ac)
            + feat[r0, c1] * (1 - ar) * ac
            + feat[r1, c0] * ar * (1 - ac)
            + feat[r1, c1] * ar * ac
        )

    res = np.zeros((len(rois), out, out, c), feat.dtype)
    for ri, (r1, c1, r2, c2) in enumerate(rois):
        bh = max(r2 - r1, 1.0) / out  # aligned=False: 1px minimum extent
        bw = max(c2 - c1, 1.0) / out
        for i in range(out):
            for j in range(out):
                acc = np.zeros(c, feat.dtype)
                for si in range(sampling):
                    for sj in range(sampling):
                        rr = r1 + (i + (si + 0.5) / sampling) * bh
                        cc2 = c1 + (j + (sj + 0.5) / sampling) * bw
                        acc += bilin(rr, cc2)
                res[ri, i, j] = acc / (sampling * sampling)
    return res


# ------------------------------------------------------ target assignment

def anchor_labels_np(
    anchors: np.ndarray,
    gt: np.ndarray,
    pos_thresh: float = 0.7,
    neg_thresh: float = 0.3,
) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic part of reference AnchorTargetCreator._create_label
    (utils/utils.py:176-189, before random subsampling): returns
    (labels in {-1,0,1}, argmax gt per anchor with force-match redirects)."""
    if len(gt) == 0:
        # Reference: empty gt -> max_ious all 0 -> every anchor labeled
        # negative (utils/utils.py:163,181-183).
        return np.zeros(len(anchors), np.int32), np.zeros(len(anchors), np.int32)
    ious = iou_np(anchors, gt)
    argmax = ious.argmax(axis=1)
    max_iou = ious.max(axis=1)
    gt_best = ious.argmax(axis=0)
    for g, a in enumerate(gt_best):
        argmax[a] = g
    labels = np.full(len(anchors), -1, np.int32)
    labels[max_iou < neg_thresh] = 0
    labels[max_iou >= pos_thresh] = 1
    labels[gt_best] = 1
    return labels, argmax


def proposal_match_np(
    rois: np.ndarray, gt: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic part of reference ProposalTargetCreator (utils/
    utils.py:234-246): best gt index and IoU per candidate roi; empty gt
    matches nothing (reference guards len(bbox)==0)."""
    if len(gt) == 0:
        return np.zeros(len(rois), np.int32), np.zeros(len(rois))
    ious = iou_np(rois, gt)
    return ious.argmax(axis=1), ious.max(axis=1)


# ------------------------------------- the gather writings (before PR 28)
#
# Three sites of the train step picked values by an integer index through
# XLA's gather until PR 28 replaced each by a sort that carries its payload
# or by compare-and-select. The old writings live on here, in jnp, as the
# oracles the new ones must equal to the bit (jax is imported on use: the
# oracles above are numpy alone).

def select_proposals_gather(anchors, fg_scores, deltas, img_h, img_w, cfg, train):
    """`models/rpn.py::select_proposals` as it was: one stable argsort of
    the negated scores, then `scores[idx]` and `props[idx]`."""
    import jax
    import jax.numpy as jnp

    from replication_faster_rcnn_tpu.ops import boxes as box_ops
    from replication_faster_rcnn_tpu.ops.nms import nms_fixed_auto

    pre_nms = min(cfg.pre_nms(train), anchors.shape[0])
    props = box_ops.clip(box_ops.decode(anchors, deltas), img_h, img_w)
    hs = props[:, 2] - props[:, 0]
    ws = props[:, 3] - props[:, 1]
    keep = (hs >= cfg.min_size) & (ws >= cfg.min_size)
    scores = jnp.where(keep, fg_scores, -jnp.inf)
    top_idx = jax.lax.slice_in_dim(jnp.argsort(-scores), 0, pre_nms)
    top_scores = scores[top_idx]
    top_boxes = props[top_idx]
    idx, valid = nms_fixed_auto(
        top_boxes, top_scores, cfg.nms_thresh, cfg.post_nms(train),
        mask=jnp.isfinite(top_scores), assume_sorted=True,
    )
    return top_boxes[idx] * valid[:, None], valid


def ignore_cross_entropy_optax(logits, labels):
    """`train/losses.py::ignore_cross_entropy` as it was (axis_name=None):
    optax's integer-label CE, whose label pick is a `take_along_axis`."""
    import jax.numpy as jnp
    import optax

    valid = labels >= 0
    safe = jnp.where(valid, labels, 0).astype(jnp.int32)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
    return jnp.where(valid, ce, 0.0).sum() / jnp.maximum(valid.sum(), 1)


def matched_boxes_gather(gt_boxes, argmax):
    """The matched-box lookup of `targets/anchor_targets.py` as it was."""
    return gt_boxes[argmax]


def largest_gather(lowered_text: str) -> int:
    """The most index vectors any `stablehlo.gather` of a lowered module
    (`jax.jit(f).lower(...).as_text()`) takes; 0 where it holds none."""
    import math
    import re

    most = 0
    for line in lowered_text.splitlines():
        if '"stablehlo.gather"' not in line:
            continue
        found = re.search(r"index_vector_dim = (\d+)", line)  # printed unless 0
        vector_dim = int(found.group(1)) if found else 0
        # the operand types: (operand, start_indices) -> result
        indices = re.search(r": \(tensor<[^>]*>, tensor<([^>]*)>\)", line).group(1)
        shape = [int(d) for d in indices.split("x")[:-1]]
        if vector_dim < len(shape):
            del shape[vector_dim]
        most = max(most, math.prod(shape))
    return most


# ------------------------- the stem's norm, ReLU and max-pool (before PR 30)

def relu_max_pool_oracle(z):
    """The ImageNet stem after its norm as it was written until PR 30:
    flax's ReLU and its 3x3 / stride 2 / pad 1 max-pool, whose backward XLA
    writes as `select-and-scatter`."""
    import flax.linen as nn

    return nn.max_pool(nn.relu(z), (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))


def norm_relu_max_pool_oracle(y, mean, mul, bias, dtype):
    """`ops/pool_ops.py::norm_relu_max_pool` in the old writing: the affine
    as flax's `_normalize` applies it, over the whole map, then the above."""
    import jax.numpy as jnp

    return relu_max_pool_oracle(jnp.asarray((y - mean) * mul + bias, dtype))


def oracle_stem_pool(x, dtype, train, axis_name, kind):
    """`models/resnet.py::_stem_pool` in the old writing: the norm layer
    applied to the map, then ReLU and the max-pool."""
    from replication_faster_rcnn_tpu.models import resnet

    return relu_max_pool_oracle(resnet._norm(dtype, train, "bn1", axis_name, kind)(x))
