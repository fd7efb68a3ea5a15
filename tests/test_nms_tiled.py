"""Parity tests: the tiled exact greedy NMS (`ops/nms_tiled.py`) must select
the same boxes, in the same order, as the loop NMS (`ops/nms.py`) and the
numpy oracle — across tile boundaries, ties, masks, and degenerate inputs."""

import numpy as np
import pytest
import jax.numpy as jnp

from replication_faster_rcnn_tpu.ops.nms import nms_fixed
from replication_faster_rcnn_tpu.ops.nms_tiled import nms_fixed_tiled
from tests import oracles
from tests.test_boxes import rand_boxes


def _both(boxes, scores, thresh, max_out, mask=None, tile=64):
    m = None if mask is None else jnp.array(mask)
    a_idx, a_val = nms_fixed(jnp.array(boxes), jnp.array(scores), thresh, max_out, mask=m)
    b_idx, b_val = nms_fixed_tiled(
        jnp.array(boxes), jnp.array(scores), thresh, max_out, mask=m, tile=tile
    )
    a = list(np.asarray(a_idx)[np.asarray(a_val)])
    b = list(np.asarray(b_idx)[np.asarray(b_val)])
    assert a == b, f"tiled {b} != loop {a}"
    # validity is a prefix and invalid slots are zeroed
    bv = np.asarray(b_val)
    if not bv.all():
        first = int(np.argmin(bv))
        assert not bv[first:].any()
        assert (np.asarray(b_idx)[~bv] == 0).all()
    return a


def test_tiled_matches_loop_random():
    rng = np.random.default_rng(7)
    for n in [1, 9, 63, 64, 65, 200, 700]:
        boxes = rand_boxes(n, rng, size=60.0)
        scores = rng.uniform(0, 1, n).astype(np.float32)
        for thresh in [0.3, 0.5, 0.7]:
            for tile in [32, 64, 512]:
                _both(boxes, scores, thresh, max_out=50, tile=tile)


def test_tiled_matches_oracle():
    rng = np.random.default_rng(8)
    boxes = rand_boxes(300, rng, size=40.0)  # small extent: dense overlaps
    scores = rng.uniform(0, 1, 300).astype(np.float32)
    got = _both(boxes, scores, 0.5, max_out=300, tile=64)
    want = oracles.nms_np(boxes, scores, 0.5)[:300]
    assert got == want


def test_tiled_score_ties_break_on_index():
    rng = np.random.default_rng(9)
    boxes = rand_boxes(120, rng, size=30.0)
    # quantize scores to force many exact ties
    scores = (rng.integers(0, 4, 120) / 4.0).astype(np.float32)
    _both(boxes, scores, 0.5, max_out=60, tile=32)


def test_tiled_suppression_chains_across_tiles():
    # a chain of half-overlapping boxes A>B>C>... spanning tile boundaries:
    # greedy keeps every other link; the in-tile fixpoint and cross-tile
    # buffer must agree with the loop
    n = 100
    boxes = np.stack(
        [
            np.arange(n, dtype=np.float32) * 5.0,
            np.zeros(n, np.float32),
            np.arange(n, dtype=np.float32) * 5.0 + 10.0,
            np.full(n, 10.0, np.float32),
        ],
        axis=1,
    )
    scores = np.linspace(1.0, 0.5, n).astype(np.float32)
    _both(boxes, scores, 0.3, max_out=100, tile=16)


def test_tiled_mask_and_nonfinite():
    rng = np.random.default_rng(10)
    boxes = rand_boxes(50, rng)
    scores = rng.uniform(0, 1, 50).astype(np.float32)
    scores[7] = np.nan
    scores[13] = np.inf  # nms_fixed treats non-finite as invalid
    mask = np.ones(50, bool)
    mask[20:30] = False
    _both(boxes, scores, 0.5, max_out=30, mask=mask, tile=16)


def test_tiled_all_invalid_and_empty_budget():
    rng = np.random.default_rng(11)
    boxes = rand_boxes(10, rng)
    scores = np.full(10, -np.inf, np.float32)
    idx, valid = nms_fixed_tiled(jnp.array(boxes), jnp.array(scores), 0.5, 5)
    assert not np.asarray(valid).any()
    assert (np.asarray(idx) == 0).all()


def test_tiled_max_out_exceeds_n():
    rng = np.random.default_rng(12)
    boxes = rand_boxes(6, rng, size=500.0)  # spread out: nothing suppressed
    scores = rng.uniform(0, 1, 6).astype(np.float32)
    idx, valid = nms_fixed_tiled(jnp.array(boxes), jnp.array(scores), 0.5, 20)
    assert int(np.asarray(valid).sum()) == 6


def test_assume_sorted_bit_identical():
    # pre-sorting candidates and passing assume_sorted=True must select
    # exactly the same boxes in the same order as the internal sort
    rng = np.random.default_rng(11)
    for n in [1, 9, 65, 400]:
        boxes = rand_boxes(n, rng, size=60.0)
        scores = rng.uniform(0, 1, n).astype(np.float32)
        # inject score ties to exercise the tie-break path
        if n >= 9:
            scores[2] = scores[7] = scores[5]
        order = np.argsort(-scores, kind="stable")
        bi, bv = nms_fixed_tiled(
            jnp.array(boxes), jnp.array(scores), 0.5, 50, tile=64
        )
        si, sv = nms_fixed_tiled(
            jnp.array(boxes[order]), jnp.array(scores[order]), 0.5, 50,
            tile=64, assume_sorted=True,
        )
        np.testing.assert_array_equal(np.asarray(bv), np.asarray(sv))
        # map sorted-space indices back to original ids
        remapped = order[np.asarray(si)[np.asarray(sv)]]
        np.testing.assert_array_equal(
            np.asarray(bi)[np.asarray(bv)], remapped
        )


def test_select_proposals_single_sort_matches_topk_pipeline():
    # models/rpn.py sorts once (one stable sort that carries the boxes, a
    # slice, assume_sorted NMS); this pins bit-identity against the oldest
    # pipeline, top_k -> unsorted NMS
    import jax

    from replication_faster_rcnn_tpu.config import ProposalConfig
    from replication_faster_rcnn_tpu.models.rpn import select_proposals
    from replication_faster_rcnn_tpu.ops import boxes as box_ops

    rng = np.random.default_rng(3)
    A = 333
    anchors = rand_boxes(A, rng, size=80.0).astype(np.float32)
    deltas = rng.normal(0, 0.1, (A, 4)).astype(np.float32)
    fg = rng.uniform(0, 1, A).astype(np.float32)
    fg[10] = fg[20] = fg[30]  # ties
    cfg = ProposalConfig()
    rois, valid = select_proposals(
        jnp.array(anchors), jnp.array(fg), jnp.array(deltas),
        96.0, 96.0, cfg, train=True,
    )

    # the old pipeline, inline
    from replication_faster_rcnn_tpu.ops.nms_tiled import nms_fixed_tiled

    pre_nms = min(cfg.pre_nms(True), A)
    props = box_ops.clip(
        box_ops.decode(jnp.array(anchors), jnp.array(deltas)), 96.0, 96.0
    )
    hs = props[:, 2] - props[:, 0]
    ws = props[:, 3] - props[:, 1]
    keep = (hs >= cfg.min_size) & (ws >= cfg.min_size)
    scores = jnp.where(keep, jnp.array(fg), -jnp.inf)
    top_scores, top_idx = jax.lax.top_k(scores, pre_nms)
    top_boxes = props[top_idx]
    idx, val = nms_fixed_tiled(
        top_boxes, top_scores, cfg.nms_thresh, cfg.post_nms(True),
        mask=jnp.isfinite(top_scores),
    )
    old_rois = top_boxes[idx] * val[:, None]
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(val))
    np.testing.assert_array_equal(np.asarray(rois), np.asarray(old_rois))


def _proposal_inputs(a=333, seed=5):
    """Anchors, scores with ties, and deltas that shrink some rows under
    the min-size filter (their scores become -inf, tied with each other)."""
    rng = np.random.default_rng(seed)
    anchors = rand_boxes(a, rng, size=80.0).astype(np.float32)
    deltas = rng.normal(0, 0.1, (a, 4)).astype(np.float32)
    deltas[::7, 2:] = -3.0  # exp(-3) of the anchor's extent: under min_size
    fg = rng.uniform(0, 1, a).astype(np.float32)
    fg[10] = fg[20] = fg[30]
    fg[200:204] = fg[100]
    return jnp.array(anchors), jnp.array(fg), jnp.array(deltas)


@pytest.mark.parametrize(
    "kw,train",
    [
        # pre_nms >= A: every row kept, the -inf rows last in index order
        (dict(), True),
        # pre_nms < A, the inference shape: the cut falls inside the list
        (dict(pre_nms_test=100, post_nms_test=30), False),
    ],
    ids=["train_keeps_all", "test_keeps_few"],
)
def test_select_proposals_equals_the_gather_writing_to_the_bit(kw, train):
    from replication_faster_rcnn_tpu.config import ProposalConfig
    from replication_faster_rcnn_tpu.models.rpn import select_proposals

    cfg = ProposalConfig(**kw)
    anchors, fg, deltas = _proposal_inputs()
    rois, valid = select_proposals(anchors, fg, deltas, 96.0, 96.0, cfg, train)
    want_rois, want_valid = oracles.select_proposals_gather(
        anchors, fg, deltas, 96.0, 96.0, cfg, train
    )
    assert 0 < int(want_valid.sum()) <= cfg.post_nms(train)
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(want_valid))
    np.testing.assert_array_equal(np.asarray(rois), np.asarray(want_rois))


def test_select_proposals_picks_nothing_by_index_before_the_nms():
    # the engagement check: in the lowered text no gather takes more
    # indices than post_nms (the `top_boxes[idx]` after the NMS stays)
    import jax

    from replication_faster_rcnn_tpu.config import ProposalConfig
    from replication_faster_rcnn_tpu.models.rpn import select_proposals

    cfg = ProposalConfig(pre_nms_train=200, post_nms_train=40)
    anchors, fg, deltas = _proposal_inputs()
    text = jax.jit(
        lambda a, s, d: select_proposals(a, s, d, 96.0, 96.0, cfg, True)
    ).lower(anchors, fg, deltas).as_text()
    assert oracles.largest_gather(text) <= cfg.post_nms(True)
    # the oracle is what the check would catch
    old = jax.jit(
        lambda a, s, d: oracles.select_proposals_gather(a, s, d, 96.0, 96.0, cfg, True)
    ).lower(anchors, fg, deltas).as_text()
    assert oracles.largest_gather(old) == cfg.pre_nms(True)
