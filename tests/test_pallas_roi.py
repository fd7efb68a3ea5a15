"""Pallas ROIAlign (`ops/pallas/roi_kernel.py`, ISSUE 13): three-way
parity einsum / gather / pallas-interpret, edge cases included.

Unlike the NMS kernel (bit-identical by construction), the fused forward
reassociates the separable bilinear contraction relative to both XLA
formulations, so parity is tolerance-gated: ATOL = 1e-5 absolute against
the gather oracle (observed interpret-mode max |diff| ~2.4e-7 on
detection-scale features; the documented contract lives in PARITY.md).
The backward is the einsum formulation's VJP verbatim (custom_vjp), so
gradients are compared exactly against `method="einsum"` grads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.ops import roi_ops
from replication_faster_rcnn_tpu.ops.pallas import roi_align_pallas

pytestmark = pytest.mark.pallas_interpret

ATOL = 1e-5


def _feat(h=12, w=10, c=5, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((h, w, c)).astype(np.float32))


def _three_way(feat, rois, out_size=7, sampling_ratio=2, spatial_scale=1.0):
    ein = roi_ops.roi_align(
        feat, rois, out_size, sampling_ratio, spatial_scale, method="einsum"
    )
    gat = roi_ops.roi_align(
        feat, rois, out_size, sampling_ratio, spatial_scale, method="gather"
    )
    pal = roi_align_pallas(
        feat, rois, out_size, sampling_ratio, spatial_scale, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(pal), np.asarray(gat), atol=ATOL, rtol=0
    )
    np.testing.assert_allclose(
        np.asarray(pal), np.asarray(ein), atol=ATOL, rtol=0
    )
    return pal


def test_random_rois_all_methods_agree():
    rng = np.random.default_rng(1)
    feat = _feat()
    tl = rng.uniform(0, 8, (6, 2)).astype(np.float32)
    wh = rng.uniform(0.5, 4, (6, 2)).astype(np.float32)
    rois = jnp.asarray(np.concatenate([tl, tl + wh], axis=1))
    _three_way(feat, rois)


def test_border_rois_minus_one_to_extent():
    # sample points fall in the [-1, H] tent-weight border region: rois
    # flush against (and slightly past) the feature-map edges
    feat = _feat()
    rois = jnp.asarray(
        np.array(
            [
                [-0.6, -0.6, 2.0, 2.0],  # past the top-left corner
                [9.5, 7.5, 12.0, 10.0],  # past the bottom-right corner
                [0.0, 0.0, 11.0, 9.0],  # exactly the full map
            ],
            np.float32,
        )
    )
    _three_way(feat, rois)


def test_zero_area_rois():
    # degenerate rois (x1==x2, y1==y2): the extent clamps to 1px minimum
    # in every method — outputs must still agree, and be finite
    feat = _feat()
    rois = jnp.asarray(
        np.array([[3.0, 4.0, 3.0, 4.0], [0.0, 0.0, 0.0, 0.0]], np.float32)
    )
    out = _three_way(feat, rois)
    assert np.isfinite(np.asarray(out)).all()


def test_sampling_ratio_one_and_two():
    rng = np.random.default_rng(2)
    feat = _feat()
    tl = rng.uniform(0, 7, (4, 2)).astype(np.float32)
    wh = rng.uniform(1, 3, (4, 2)).astype(np.float32)
    rois = jnp.asarray(np.concatenate([tl, tl + wh], axis=1))
    for s in (1, 2):
        _three_way(feat, rois, sampling_ratio=s)


def test_spatial_scale_applied_inside_kernel():
    # the pallas wrapper applies spatial_scale itself (roi_ops.roi_align
    # delegates BEFORE its own pre-scaling) — 1/16 image-coord rois must
    # land on the same bins as pre-scaled feature-coord rois
    feat = _feat()
    rois_img = jnp.asarray(
        np.array([[16.0, 32.0, 80.0, 96.0]], np.float32)
    )
    a = roi_align_pallas(feat, rois_img, spatial_scale=1.0 / 16, interpret=True)
    b = roi_align_pallas(feat, rois_img / 16.0, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gradients_match_einsum_vjp_exactly(dtype):
    """bfloat16 is the presets' compute dtype: the kernel must return what
    the XLA twin returns for it (float32 — the rois promote), or the
    custom_vjp's cotangent does not fit the einsum VJP and the first
    backward raises (found on the chip in PR 21; float32-only tests had
    hidden it)."""
    rng = np.random.default_rng(3)
    feat = _feat(8, 8, 3).astype(dtype)
    tl = rng.uniform(0, 5, (3, 2)).astype(np.float32)
    wh = rng.uniform(1, 2, (3, 2)).astype(np.float32)
    rois = jnp.asarray(np.concatenate([tl, tl + wh], axis=1))
    cot = jnp.asarray(
        rng.standard_normal((3, 7, 7, 3)).astype(np.float32)
    )

    def loss_pallas(f):
        return jnp.vdot(roi_align_pallas(f, rois, interpret=True), cot)

    def loss_einsum(f):
        return jnp.vdot(roi_ops.roi_align(f, rois, method="einsum"), cot)

    assert (
        roi_align_pallas(feat, rois, interpret=True).dtype
        == roi_ops.roi_align(feat, rois, method="einsum").dtype
    )
    g_pal = jax.grad(loss_pallas)(feat)
    g_ein = jax.grad(loss_einsum)(feat)
    assert g_pal.dtype == g_ein.dtype == dtype
    # custom_vjp replays the einsum formulation for the backward: exact
    np.testing.assert_array_equal(
        np.asarray(g_pal, np.float32), np.asarray(g_ein, np.float32)
    )


def test_vmap_over_batch():
    rng = np.random.default_rng(4)
    batch = 2
    feats = jnp.asarray(
        rng.standard_normal((batch, 9, 9, 4)).astype(np.float32)
    )
    tl = rng.uniform(0, 6, (batch, 5, 2)).astype(np.float32)
    wh = rng.uniform(1, 2, (batch, 5, 2)).astype(np.float32)
    rois = jnp.asarray(np.concatenate([tl, tl + wh], axis=2))
    out = jax.vmap(
        lambda f, r: roi_align_pallas(f, r, interpret=True)
    )(feats, rois)
    for i in range(batch):
        ref = roi_ops.roi_align(feats[i], rois[i], method="gather")
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(ref), atol=ATOL, rtol=0
        )
