"""Pallas NMS kernel (`ops/pallas/nms_kernel.py`, ISSUE 13): selections
must be BIT-IDENTICAL to the tiled XLA backend (`ops/nms_tiled.py`) — the
same tile/fixpoint recurrence, so parity is exact equality of the
(idx, valid) outputs, not a tolerance. All tests run the kernel in
interpret mode (pure JAX): the numerics tier-1 gates here are exactly
what Mosaic compiles on a TPU, minus the codegen — which is why the
wrapper pins strict-IEEE float behavior (runtime-zero products + an
optimization_barrier on the kernel inputs; see `_iou_grid`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.ops.nms_tiled import nms_fixed_tiled
from replication_faster_rcnn_tpu.ops.pallas import nms_fixed_pallas
from tests import oracles
from tests.test_boxes import rand_boxes

pytestmark = pytest.mark.pallas_interpret


def _pair(boxes, scores, thresh, max_out, mask=None, tile=64, sorted_=False):
    """(idx, valid) from both backends; asserts bitwise equality."""
    m = None if mask is None else jnp.asarray(mask)
    b, s = jnp.asarray(boxes), jnp.asarray(scores)
    t_idx, t_val = nms_fixed_tiled(
        b, s, thresh, max_out, mask=m, tile=tile, assume_sorted=sorted_
    )
    p_idx, p_val = nms_fixed_pallas(
        b, s, thresh, max_out, mask=m, tile=tile, assume_sorted=sorted_,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(p_idx), np.asarray(t_idx))
    np.testing.assert_array_equal(np.asarray(p_val), np.asarray(t_val))
    return np.asarray(p_idx), np.asarray(p_val)


def test_bit_identical_across_sizes_and_tiles():
    rng = np.random.default_rng(3)
    for n in [1, 63, 65, 200, 700]:
        boxes = rand_boxes(n, rng, size=60.0)
        scores = rng.uniform(0, 1, n).astype(np.float32)
        for tile in [33, 512]:
            _pair(boxes, scores, 0.5, 50, tile=tile)


def test_matches_numpy_oracle_dense_overlaps():
    rng = np.random.default_rng(4)
    boxes = rand_boxes(300, rng, size=40.0)
    scores = rng.uniform(0, 1, 300).astype(np.float32)
    idx, val = _pair(boxes, scores, 0.5, 300, tile=64)
    assert list(idx[val]) == oracles.nms_np(boxes, scores, 0.5)[:300]


def test_score_ties_break_on_index():
    rng = np.random.default_rng(5)
    boxes = rand_boxes(160, rng, size=30.0)
    scores = (rng.integers(0, 4, 160) / 4.0).astype(np.float32)
    _pair(boxes, scores, 0.5, 80, tile=32)


def test_mask_and_nonfinite_scores():
    # the proposal path masks -inf (min-size-filtered) candidates; NaN
    # scores must also stay suppressed through both backends identically
    rng = np.random.default_rng(6)
    n = 120
    boxes = rand_boxes(n, rng, size=50.0)
    scores = rng.uniform(0, 1, n).astype(np.float32)
    scores[::7] = -np.inf
    scores[::11] = np.nan
    _pair(boxes, scores, 0.5, 60, mask=np.isfinite(scores), tile=48)


def test_assume_sorted_and_max_out_exceeding_n():
    rng = np.random.default_rng(7)
    n = 90
    boxes = rand_boxes(n, rng, size=45.0)
    scores = np.sort(rng.uniform(0, 1, n).astype(np.float32))[::-1].copy()
    idx, val = _pair(boxes, scores, 0.6, n + 7, tile=32, sorted_=True)
    # validity is a prefix; invalid slots are zeroed
    if not val.all():
        first = int(np.argmin(val))
        assert not val[first:].any()
        assert (idx[~val] == 0).all()


def test_vmap_matches_per_image():
    rng = np.random.default_rng(8)
    batch, n, out = 3, 150, 40
    boxes = np.stack([rand_boxes(n, rng, size=50.0) for _ in range(batch)])
    scores = rng.uniform(0, 1, (batch, n)).astype(np.float32)

    fn = jax.jit(
        jax.vmap(
            lambda b, s: nms_fixed_pallas(b, s, 0.5, out, interpret=True)
        )
    )
    v_idx, v_val = fn(jnp.asarray(boxes), jnp.asarray(scores))
    for i in range(batch):
        e_idx, e_val = _pair(boxes[i], scores[i], 0.5, out, tile=512)
        np.testing.assert_array_equal(np.asarray(v_idx[i]), e_idx)
        np.testing.assert_array_equal(np.asarray(v_val[i]), e_val)
