"""Parity + invariant tests for device-side target assignment
(SURVEY.md §4c: distributional parity vs the reference's numpy creators).

The deterministic parts (labeling thresholds, force-positive, gt matching,
encoding) must match the numpy oracle exactly; the random subsampling is
checked via its invariants (budgets, only-demotions, uniform coverage).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.config import ROITargetConfig, RPNTargetConfig
from replication_faster_rcnn_tpu.ops import anchors as anchor_ops
from replication_faster_rcnn_tpu.targets import (
    anchor_targets,
    batched_anchor_targets,
    batched_proposal_targets,
    proposal_targets,
    random_subset_mask,
)
from tests import oracles

# the module: the package re-exports the function under the same name
_ANCHOR_TARGETS = importlib.import_module("replication_faster_rcnn_tpu.targets.anchor_targets")


@pytest.fixture
def anchors():
    return anchor_ops.make_anchors.__wrapped__ if False else anchor_ops.grid_anchors(
        anchor_ops.anchor_base(16), 16, 8, 8
    )  # [576, 4] small grid


def _random_gt(rng, n, img=128.0):
    r1 = rng.uniform(0, img - 20, (n, 1))
    c1 = rng.uniform(0, img - 20, (n, 1))
    h = rng.uniform(10, img / 2, (n, 1))
    w = rng.uniform(10, img / 2, (n, 1))
    return np.concatenate([r1, c1, np.minimum(r1 + h, img), np.minimum(c1 + w, img)], 1).astype(
        np.float32
    )


class TestRandomSubset:
    def test_budget_and_membership(self):
        member = jnp.arange(100) < 40
        keep = random_subset_mask(jax.random.PRNGKey(0), member, 10)
        assert int(keep.sum()) == 10
        assert bool(jnp.all(~keep[40:]))

    def test_under_budget_keeps_all(self):
        member = jnp.arange(100) < 5
        keep = random_subset_mask(jax.random.PRNGKey(0), member, 10)
        assert bool(jnp.all(keep[:5])) and int(keep.sum()) == 5

    def test_zero_budget(self):
        member = jnp.ones(16, bool)
        keep = random_subset_mask(jax.random.PRNGKey(0), member, 0)
        assert int(keep.sum()) == 0

    def test_dynamic_traced_budget(self):
        @jax.jit
        def f(k, member, budget):
            return random_subset_mask(k, member, budget)

        keep = f(jax.random.PRNGKey(1), jnp.ones(50, bool), jnp.asarray(7))
        assert int(keep.sum()) == 7

    def test_k_max_matches_full_sort(self):
        # the static-bound top_k cut must select the identical subset the
        # full-sort cut does (same kk-th-largest value, same rng draw)
        for seed in range(20):
            rng = jax.random.PRNGKey(seed)
            member = jax.random.bernoulli(jax.random.fold_in(rng, 1), 0.3, (500,))
            k = int(jax.random.randint(jax.random.fold_in(rng, 2), (), 0, 40))
            a = random_subset_mask(rng, member, k)
            b = random_subset_mask(rng, member, k, k_max=64)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_k_max_with_traced_budget(self):
        @jax.jit
        def f(rng, member, budget):
            return random_subset_mask(rng, member, budget, k_max=16)

        keep = f(jax.random.PRNGKey(1), jnp.ones(50, bool), jnp.asarray(7))
        assert int(keep.sum()) == 7

    def test_k_max_zero_keeps_nothing(self):
        keep = random_subset_mask(
            jax.random.PRNGKey(0), jnp.ones(16, bool), 0, k_max=0
        )
        assert int(keep.sum()) == 0

    def test_k_max_exceeded_raises(self):
        import pytest

        with pytest.raises(ValueError):
            random_subset_mask(
                jax.random.PRNGKey(0), jnp.ones(16, bool), 10, k_max=4
            )

    def test_uniform_coverage(self):
        member = jnp.ones(20, bool)
        counts = np.zeros(20)
        for s in range(200):
            counts += np.asarray(
                random_subset_mask(jax.random.PRNGKey(s), member, 5)
            )
        # each element expected 200 * 5/20 = 50 times
        assert counts.min() > 25 and counts.max() < 75


class TestAnchorTargets:
    cfg = RPNTargetConfig()

    def test_label_semantics_vs_oracle(self, anchors):
        rng = np.random.RandomState(0)
        gt = _random_gt(rng, 3)
        gt_pad = np.zeros((8, 4), np.float32)
        gt_pad[:3] = gt
        mask = np.arange(8) < 3

        reg, labels = anchor_targets(
            jax.random.PRNGKey(0), jnp.asarray(gt_pad), jnp.asarray(mask),
            jnp.asarray(anchors), self.cfg,
        )
        labels = np.asarray(labels)
        oracle_labels, oracle_argmax = oracles.anchor_labels_np(
            np.asarray(anchors), gt, self.cfg.pos_iou_thresh, self.cfg.neg_iou_thresh
        )
        # subsampling only demotes (1->-1, 0->-1): every surviving label must
        # match the oracle's pre-subsample assignment
        surviving = labels >= 0
        np.testing.assert_array_equal(labels[surviving], oracle_labels[surviving])
        # budgets (utils/utils.py:190-202)
        n_pos = int((labels == 1).sum())
        assert n_pos <= self.cfg.n_sample * self.cfg.pos_ratio
        assert (labels >= 0).sum() <= self.cfg.n_sample

    def test_force_positive_every_gt(self, anchors):
        # 2 gts, plenty of sample budget: each gt's best anchor must be positive
        rng = np.random.RandomState(1)
        gt = _random_gt(rng, 2)
        gt_pad = np.zeros((8, 4), np.float32)
        gt_pad[:2] = gt
        mask = np.arange(8) < 2
        _, labels = anchor_targets(
            jax.random.PRNGKey(0), jnp.asarray(gt_pad), jnp.asarray(mask),
            jnp.asarray(anchors), self.cfg,
        )
        ious = oracles.iou_np(np.asarray(anchors), gt)
        for g in range(2):
            assert labels[ious[:, g].argmax()] == 1

    def test_reg_targets_match_oracle_encoding(self, anchors):
        rng = np.random.RandomState(2)
        gt = _random_gt(rng, 3)
        gt_pad = np.zeros((8, 4), np.float32)
        gt_pad[:3] = gt
        mask = np.arange(8) < 3
        reg, labels = anchor_targets(
            jax.random.PRNGKey(3), jnp.asarray(gt_pad), jnp.asarray(mask),
            jnp.asarray(anchors), self.cfg,
        )
        _, oracle_argmax = oracles.anchor_labels_np(np.asarray(anchors), gt)
        expect = oracles.encode_np(np.asarray(anchors), gt[oracle_argmax])
        got = np.asarray(reg)
        pos = np.asarray(labels) == 1
        np.testing.assert_allclose(got[pos], expect[pos], rtol=1e-4, atol=1e-5)

    def test_empty_gt(self, anchors):
        gt_pad = np.zeros((8, 4), np.float32)
        mask = np.zeros(8, bool)
        reg, labels = anchor_targets(
            jax.random.PRNGKey(0), jnp.asarray(gt_pad), jnp.asarray(mask),
            jnp.asarray(anchors), self.cfg,
        )
        assert not bool((labels == 1).any())
        np.testing.assert_array_equal(np.asarray(reg), 0.0)

    @pytest.mark.parametrize("n_gt", [3, 0], ids=["padded_gt_rows", "no_gt"])
    def test_equals_the_gather_writing_to_the_bit(self, anchors, n_gt, monkeypatch):
        # `gt_boxes[argmax]`, the lookup before PR 28, put back as the oracle
        rng = np.random.RandomState(4)
        gt_pad = rng.uniform(1, 9, (8, 4)).astype(np.float32)  # padding is not zeros
        gt_pad[:n_gt] = _random_gt(rng, n_gt)
        args = (
            jax.random.PRNGKey(5), jnp.asarray(gt_pad), jnp.asarray(np.arange(8) < n_gt),
            jnp.asarray(anchors), self.cfg,
        )
        reg, labels = anchor_targets(*args)
        monkeypatch.setattr(_ANCHOR_TARGETS, "matched_boxes", oracles.matched_boxes_gather)
        want_reg, want_labels = anchor_targets(*args)
        assert bool((np.asarray(want_labels) == 1).any()) == (n_gt > 0)
        np.testing.assert_array_equal(np.asarray(labels), np.asarray(want_labels))
        np.testing.assert_array_equal(np.asarray(reg), np.asarray(want_reg))

    def test_looks_up_no_box_per_anchor_by_index(self, anchors, monkeypatch):
        # the engagement check: no gather of the lowered text takes more
        # indices than there are gt rows (data.max_boxes)
        def lowered():
            return jax.jit(
                lambda k, b, m, a: anchor_targets(k, b, m, a, self.cfg)
            ).lower(
                jax.random.PRNGKey(0), jnp.zeros((8, 4)), jnp.arange(8) < 3, jnp.asarray(anchors)
            ).as_text()

        assert oracles.largest_gather(lowered()) <= 8
        monkeypatch.setattr(_ANCHOR_TARGETS, "matched_boxes", oracles.matched_boxes_gather)
        assert oracles.largest_gather(lowered()) == anchors.shape[0]  # what it catches

    def test_batched_shapes_and_jit(self, anchors):
        rng = np.random.RandomState(3)
        gt = np.stack([_random_gt(rng, 8), _random_gt(rng, 8)])
        mask = np.stack([np.arange(8) < 3, np.arange(8) < 0])

        f = jax.jit(
            lambda k, b, m: batched_anchor_targets(
                k, b, m, jnp.asarray(anchors), self.cfg
            )
        )
        reg, labels = f(jax.random.PRNGKey(0), jnp.asarray(gt), jnp.asarray(mask))
        assert reg.shape == (2, len(anchors), 4)
        assert labels.shape == (2, len(anchors))
        # image 1 has no gt: no positives
        assert not bool((labels[1] == 1).any())


class TestProposalTargets:
    cfg = ROITargetConfig()

    def _setup(self, seed=0, n_gt=4, n_roi=200):
        rng = np.random.RandomState(seed)
        gt = _random_gt(rng, n_gt)
        gt_pad = np.zeros((8, 4), np.float32)
        gt_pad[:n_gt] = gt
        gt_mask = np.arange(8) < n_gt
        gt_labels = np.full(8, -1, np.int32)
        gt_labels[:n_gt] = rng.randint(1, 21, n_gt)
        rois = _random_gt(rng, n_roi)
        roi_valid = np.ones(n_roi, bool)
        return gt, gt_pad, gt_mask, gt_labels, rois, roi_valid

    def test_fixed_output_and_budgets(self):
        gt, gt_pad, gt_mask, gt_labels, rois, roi_valid = self._setup()
        s_rois, reg, labels = proposal_targets(
            jax.random.PRNGKey(0), jnp.asarray(rois), jnp.asarray(roi_valid),
            jnp.asarray(gt_pad), jnp.asarray(gt_labels), jnp.asarray(gt_mask),
            self.cfg,
        )
        assert s_rois.shape == (self.cfg.n_sample, 4)
        labels = np.asarray(labels)
        assert (labels > 0).sum() <= self.cfg.n_pos_max
        # packed positives-first, then negatives, then -1 filler
        kinds = np.where(labels > 0, 0, np.where(labels == 0, 1, 2))
        assert (np.diff(kinds) >= 0).all()

    def test_positive_labels_match_gt(self):
        gt, gt_pad, gt_mask, gt_labels, rois, roi_valid = self._setup(seed=1)
        s_rois, reg, labels = proposal_targets(
            jax.random.PRNGKey(1), jnp.asarray(rois), jnp.asarray(roi_valid),
            jnp.asarray(gt_pad), jnp.asarray(gt_labels), jnp.asarray(gt_mask),
            self.cfg,
        )
        s_rois, labels = np.asarray(s_rois), np.asarray(labels)
        pos = labels > 0
        if pos.any():
            assign, max_iou = oracles.proposal_match_np(s_rois[pos], gt)
            np.testing.assert_array_equal(labels[pos], gt_labels[assign])
            assert (max_iou >= self.cfg.pos_iou_thresh).all()

    def test_gt_boxes_join_candidate_pool(self):
        # With zero proposals, gt boxes themselves must appear as positives
        # ("add the true boxes to the rois", utils/utils.py:229-230).
        gt, gt_pad, gt_mask, gt_labels, _, _ = self._setup(seed=2)
        rois = np.zeros((50, 4), np.float32)
        roi_valid = np.zeros(50, bool)
        s_rois, reg, labels = proposal_targets(
            jax.random.PRNGKey(2), jnp.asarray(rois), jnp.asarray(roi_valid),
            jnp.asarray(gt_pad), jnp.asarray(gt_labels), jnp.asarray(gt_mask),
            self.cfg,
        )
        labels = np.asarray(labels)
        assert (labels > 0).sum() == gt_mask.sum()
        # a gt matched to itself encodes to ~0, normalized still ~0
        np.testing.assert_allclose(
            np.asarray(reg)[labels > 0], 0.0, atol=1e-4
        )

    def test_reg_normalization(self):
        gt, gt_pad, gt_mask, gt_labels, rois, roi_valid = self._setup(seed=3)
        s_rois, reg, labels = proposal_targets(
            jax.random.PRNGKey(3), jnp.asarray(rois), jnp.asarray(roi_valid),
            jnp.asarray(gt_pad), jnp.asarray(gt_labels), jnp.asarray(gt_mask),
            self.cfg,
        )
        s_rois, labels, reg = map(np.asarray, (s_rois, labels, reg))
        pos = labels > 0
        if pos.any():
            assign, _ = oracles.proposal_match_np(s_rois[pos], gt)
            raw = oracles.encode_np(s_rois[pos], gt[assign])
            expect = raw / np.array(self.cfg.reg_std, np.float32)
            np.testing.assert_allclose(reg[pos], expect, rtol=1e-3, atol=1e-4)

    def test_empty_gt_all_background_or_filler(self):
        _, _, _, _, rois, roi_valid = self._setup()
        gt_pad = np.zeros((8, 4), np.float32)
        s_rois, reg, labels = proposal_targets(
            jax.random.PRNGKey(0), jnp.asarray(rois), jnp.asarray(roi_valid),
            jnp.asarray(gt_pad), jnp.asarray(np.full(8, -1, np.int32)),
            jnp.asarray(np.zeros(8, bool)), self.cfg,
        )
        assert not bool((np.asarray(labels) > 0).any())

    def test_batched_jit(self):
        gt, gt_pad, gt_mask, gt_labels, rois, roi_valid = self._setup()
        B = 3
        f = jax.jit(
            lambda k, r, v, b, lbl, m: batched_proposal_targets(
                k, r, v, b, lbl, m, self.cfg
            )
        )
        s_rois, reg, labels = f(
            jax.random.PRNGKey(0),
            jnp.asarray(np.stack([rois] * B)),
            jnp.asarray(np.stack([roi_valid] * B)),
            jnp.asarray(np.stack([gt_pad] * B)),
            jnp.asarray(np.stack([gt_labels] * B)),
            jnp.asarray(np.stack([gt_mask] * B)),
        )
        assert s_rois.shape == (B, self.cfg.n_sample, 4)
        assert labels.shape == (B, self.cfg.n_sample)
