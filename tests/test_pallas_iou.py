"""Pallas IoU/matching kernel (`ops/pallas/iou_kernel.py`, ISSUE 13):
EXACT parity — float outputs bitwise equal, integer outputs equal.

The kernel is strict-IEEE by construction (runtime-zero products inside
`_iou_grid` plus an optimization_barrier on the wrapper's kernel inputs,
so XLA:CPU can neither FMA-contract the products nor fuse producers into
the inlined interpret-mode body). Direct calls are therefore bitwise
equal both to the XLA reference (`ops/boxes.py::iou` + jnp reductions)
and to a strict float32 numpy oracle. In heavily-fused jit contexts it
is the XLA reference that can drift 1 ulp from strict IEEE — never the
kernel — so the integrated assertions here pin the target-assignment
OUTPUTS (labels/regs/indices) across backends, not intermediate floats
inside someone else's fusion."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.config import ROITargetConfig, RPNTargetConfig
from replication_faster_rcnn_tpu import ops as ops_pkg
from replication_faster_rcnn_tpu.ops import boxes as box_ops
from replication_faster_rcnn_tpu.ops.pallas import (
    iou_matrix_pallas,
    match_boxes_pallas,
)
from replication_faster_rcnn_tpu.targets.anchor_targets import anchor_targets
from replication_faster_rcnn_tpu.targets.proposal_targets import (
    proposal_targets,
)
from tests.test_boxes import rand_boxes

pytestmark = pytest.mark.pallas_interpret


def _strict_iou_f32(a, b):
    """box_ops.iou's exact op order in strict-IEEE float32 numpy."""
    a, b = a.astype(np.float32), b.astype(np.float32)
    tl = np.maximum(a[:, None, :2], b[None, :, :2])
    br = np.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = (br - tl).astype(np.float32)
    valid = (wh > 0).all(-1)
    inter = np.where(valid, wh[..., 0] * wh[..., 1], np.float32(0))
    area_a = ((a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1])).astype(np.float32)
    area_b = ((b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])).astype(np.float32)
    union = (area_a[:, None] + area_b[None, :] - inter).astype(np.float32)
    return np.where(
        union > 0, inter / np.where(union > 0, union, np.float32(1)), 0
    ).astype(np.float32)


def _xla_match(anchors, gt, gt_mask):
    ious = jnp.where(gt_mask[None, :], box_ops.iou(anchors, gt), -1.0)
    return (
        ious,
        jnp.argmax(ious, axis=1),
        jnp.max(jnp.maximum(ious, 0.0), axis=1),
        jnp.argmax(ious, axis=0),
    )


def _inputs(n, g, seed, n_valid=None):
    rng = np.random.default_rng(seed)
    anchors = jnp.asarray(rand_boxes(n, rng, size=80.0))
    gt = jnp.asarray(rand_boxes(g, rng, size=80.0))
    n_valid = g if n_valid is None else n_valid
    mask = jnp.asarray(np.arange(g) < n_valid)
    return anchors, gt, mask


def test_match_bitwise_exact_across_sizes_and_tiles():
    for n, g, tile in [(1, 1, 512), (144, 8, 512), (700, 16, 160), (513, 5, 33)]:
        anchors, gt, mask = _inputs(n, g, seed=n)
        ref = _xla_match(anchors, gt, mask)
        got = match_boxes_pallas(anchors, gt, mask, tile=tile, interpret=True)
        for r, p in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(p), np.asarray(r))


def test_match_matches_strict_numpy_oracle():
    anchors, gt, mask = _inputs(350, 12, seed=42, n_valid=7)
    ious, argmax, max_iou, gt_best = match_boxes_pallas(
        anchors, gt, mask, interpret=True
    )
    want = np.where(
        np.asarray(mask)[None, :],
        _strict_iou_f32(np.asarray(anchors), np.asarray(gt)),
        np.float32(-1),
    )
    np.testing.assert_array_equal(np.asarray(ious), want)
    np.testing.assert_array_equal(np.asarray(argmax), want.argmax(1))
    np.testing.assert_array_equal(
        np.asarray(max_iou), np.maximum(want, 0).max(1).astype(np.float32)
    )
    np.testing.assert_array_equal(np.asarray(gt_best), want.argmax(0))


def test_padded_gt_never_matches():
    anchors, gt, mask = _inputs(64, 6, seed=9, n_valid=0)
    ious, argmax, max_iou = iou_matrix_pallas(
        anchors, gt, mask, interpret=True
    )
    assert (np.asarray(ious) == -1.0).all()
    assert (np.asarray(max_iou) == 0.0).all()


def test_iou_matrix_three_tuple_matches_match():
    anchors, gt, mask = _inputs(200, 10, seed=11, n_valid=6)
    a = iou_matrix_pallas(anchors, gt, mask, interpret=True)
    b = match_boxes_pallas(anchors, gt, mask, interpret=True)
    for x, y in zip(a, b[:3]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_vmap_batched_matching_exact():
    rng = np.random.default_rng(13)
    batch, n, g = 3, 120, 8
    anchors = jnp.asarray(rand_boxes(n, rng, size=60.0))
    gts = jnp.asarray(
        np.stack([rand_boxes(g, rng, size=60.0) for _ in range(batch)])
    )
    masks = jnp.asarray(np.arange(g)[None, :] < np.array([[8], [3], [1]]))
    got = jax.vmap(
        lambda b, m: match_boxes_pallas(anchors, b, m, interpret=True)
    )(gts, masks)
    for i in range(batch):
        ref = _xla_match(anchors, gts[i], masks[i])
        for r, p in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(p[i]), np.asarray(r))


class TestTargetsParityAcrossBackends:
    """The real dispatch seams: targets/{anchor,proposal}_targets.py must
    produce IDENTICAL outputs under backend_scope('pallas') — same rng,
    same sampling decisions, same labels/regs, bit for bit."""

    def test_anchor_targets_identical(self):
        rng = np.random.default_rng(21)
        anchors = jnp.asarray(rand_boxes(256, rng, size=64.0))
        gt = jnp.asarray(rand_boxes(8, rng, size=64.0))
        mask = jnp.asarray(np.arange(8) < 5)
        key = jax.random.PRNGKey(3)
        cfg = RPNTargetConfig()
        reg_x, lab_x = anchor_targets(key, gt, mask, anchors, cfg)
        with ops_pkg.backend_scope("pallas"):
            reg_p, lab_p = anchor_targets(key, gt, mask, anchors, cfg)
        np.testing.assert_array_equal(np.asarray(reg_p), np.asarray(reg_x))
        np.testing.assert_array_equal(np.asarray(lab_p), np.asarray(lab_x))

    def test_proposal_targets_identical(self):
        rng = np.random.default_rng(22)
        rois = jnp.asarray(rand_boxes(48, rng, size=64.0))
        roi_valid = jnp.asarray(np.arange(48) < 40)
        gt = jnp.asarray(rand_boxes(8, rng, size=64.0))
        labels = jnp.asarray(rng.integers(1, 5, 8).astype(np.int32))
        mask = jnp.asarray(np.arange(8) < 4)
        key = jax.random.PRNGKey(5)
        cfg = ROITargetConfig(n_sample=16)
        out_x = proposal_targets(key, rois, roi_valid, gt, labels, mask, cfg)
        with ops_pkg.backend_scope("pallas"):
            out_p = proposal_targets(
                key, rois, roi_valid, gt, labels, mask, cfg
            )
        for p, x in zip(out_p, out_x):
            np.testing.assert_array_equal(np.asarray(p), np.asarray(x))
