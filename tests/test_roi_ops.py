import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from replication_faster_rcnn_tpu.ops import roi_ops
from tests import oracles

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # `perf/` is imported from the checkout's root, as tests/perf_yardstick does
    sys.path.insert(0, ROOT)


def _rand_feat_rois(rng, h=12, w=14, c=5, n=6):
    feat = rng.normal(0, 1, (h, w, c)).astype(np.float32)
    p1 = rng.uniform(0, h - 2, (n, 1)), rng.uniform(0, w - 2, (n, 1))
    hh = rng.uniform(1, h / 2, (n, 1))
    ww = rng.uniform(1, w / 2, (n, 1))
    rois = np.concatenate([p1[0], p1[1], p1[0] + hh, p1[1] + ww], axis=1).astype(
        np.float32
    )
    return feat, rois


def test_roi_pool_matches_oracle():
    rng = np.random.default_rng(0)
    feat, rois = _rand_feat_rois(rng)
    got = np.asarray(roi_ops.roi_pool(jnp.array(feat), jnp.array(rois), 7))
    want = oracles.roi_pool_np(feat, rois, 7)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_roi_pool_tiny_roi_nonempty():
    feat = np.arange(36, dtype=np.float32).reshape(6, 6, 1)
    rois = np.array([[2.2, 2.2, 2.4, 2.4]], np.float32)  # sub-pixel roi
    out = np.asarray(roi_ops.roi_pool(jnp.array(feat), jnp.array(rois), 7))
    want = oracles.roi_pool_np(feat, rois, 7)
    np.testing.assert_allclose(out, want, rtol=1e-6)
    assert np.isfinite(out).all()


def test_roi_align_matches_oracle():
    rng = np.random.default_rng(1)
    feat, rois = _rand_feat_rois(rng)
    got = np.asarray(
        roi_ops.roi_align(jnp.array(feat), jnp.array(rois), 7, sampling_ratio=2)
    )
    want = oracles.roi_align_np(feat, rois, 7, sampling=2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_roi_align_border_rois():
    """Rois touching / slightly crossing the border must stay finite and
    match the oracle's zero-outside rule."""
    rng = np.random.default_rng(2)
    feat = rng.normal(0, 1, (8, 8, 3)).astype(np.float32)
    rois = np.array(
        [[-0.5, -0.5, 4.0, 4.0], [0, 0, 8, 8], [6.5, 6.5, 9.0, 9.0]], np.float32
    )
    got = np.asarray(roi_ops.roi_align(jnp.array(feat), jnp.array(rois), 4))
    want = oracles.roi_align_np(feat, rois, 4)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_roi_align_einsum_matches_gather():
    """The MXU formulation (separable tent-weight matmuls) must reproduce
    the direct 4-corner-gather implementation exactly, including rois
    crossing the border and degenerate (sub-pixel) rois."""
    rng = np.random.default_rng(3)
    feat, rois = _rand_feat_rois(rng, h=11, w=9, c=4, n=8)
    rois = np.concatenate(
        [
            rois,
            np.array(
                [[-0.9, -0.9, 3.0, 3.0], [8.0, 6.0, 12.0, 10.0], [2.2, 2.2, 2.3, 2.3]],
                np.float32,
            ),
        ]
    )
    a = np.asarray(
        roi_ops.roi_align(jnp.array(feat), jnp.array(rois), 7, 2, method="einsum")
    )
    b = np.asarray(
        roi_ops.roi_align(jnp.array(feat), jnp.array(rois), 7, 2, method="gather")
    )
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_roi_align_einsum_grads_match_gather():
    rng = np.random.default_rng(4)
    feat, rois = _rand_feat_rois(rng, h=10, w=10, c=3, n=5)

    def loss(f, method):
        return (
            roi_ops.roi_align(f, jnp.array(rois), 5, 2, method=method) ** 2
        ).sum()

    ga = jax.grad(lambda f: loss(f, "einsum"))(jnp.array(feat))
    gb = jax.grad(lambda f: loss(f, "gather"))(jnp.array(feat))
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), rtol=1e-4, atol=1e-5)


def test_roi_ops_vmap_over_batch():
    rng = np.random.default_rng(3)
    feats = np.stack([_rand_feat_rois(rng)[0] for _ in range(3)])
    rois = np.stack([_rand_feat_rois(rng)[1] for _ in range(3)])
    out = jax.vmap(lambda f, r: roi_ops.roi_align(f, r, 7))(
        jnp.array(feats), jnp.array(rois)
    )
    assert out.shape == (3, rois.shape[1], 7, 7, feats.shape[-1])


def test_roi_align_grad_flows_to_features():
    rng = np.random.default_rng(4)
    feat, rois = _rand_feat_rois(rng, h=8, w=8, c=2, n=3)

    def loss(f):
        return roi_ops.roi_align(f, jnp.array(rois), 4).sum()

    g = jax.grad(loss)(jnp.array(feat))
    assert np.abs(np.asarray(g)).sum() > 0


def test_roi_pool_grad_flows_to_features():
    rng = np.random.default_rng(5)
    feat, rois = _rand_feat_rois(rng, h=8, w=8, c=2, n=3)

    def loss(f):
        return roi_ops.roi_pool(f, jnp.array(rois), 4).sum()

    g = jax.grad(loss)(jnp.array(feat))
    assert np.abs(np.asarray(g)).sum() > 0


# ------------------------------------------------------------- ROIPool
# (range-max tables + selection matmul + hand-written backward, PR 25)

MAPS = {"8x8": (8, 8, 3), "38x38": (38, 38, 6), "50x84": (50, 84, 4)}


def _edges_np(rois, out, h, w, reciprocal):
    """The oracle's bin edges [R, 4, out]; with `reciprocal` the bin size is
    extent * (1 / out), the way a compiler may write the division."""
    r1, c1, r2, c2 = np.round(rois).T
    p = np.arange(out, dtype=np.float32)
    res = []
    for first, last, extent in ((r1, r2, h), (c1, c2, w)):
        size = np.maximum(last - first + 1, 1).astype(np.float32)
        size = size * np.float32(1.0 / out) if reciprocal else size / np.float32(out)
        res.append(np.clip(np.floor(p[None] * size[:, None]) + first[:, None], 0, extent))
        res.append(np.clip(np.ceil((p[None] + 1) * size[:, None]) + first[:, None], 0, extent))
    return np.stack(res, axis=1).astype(np.int32)


def _pool_rois(rng, h, w, n=48, out=7):
    """Whole-map, border-clipped, fully-outside, sub-pixel and one-row ROIs
    and `n` random ones. A bin row's last edge is ceil(out * (extent / out)),
    which one ulp of the division can move by a whole cell (PERF.md section
    6): only ROIs whose edges survive both ways of dividing are kept, so the
    oracle's answer is the answer."""
    fixed = np.array(
        [
            [0, 0, h, w], [0, 0, h - 1, w - 1],  # whole map
            [-0.5, -0.5, 4, 4], [h - 3.4, w - 2.6, h + 2, w + 2], [-2, 1, 2, w + 1],  # across the border
            [h + 3, w + 3, h + 9, w + 9], [-6, -6, -2.2, -2.2],  # outside
            [2.2, 2.2, 2.4, 2.4], [h - 1.3, 0.2, h - 1.1, 0.4],  # sub-pixel
            [3, 0, 3.2, w], [h - 1, 2, h - 1, 9],  # one row
        ],
        np.float32,
    )
    p = np.concatenate([rng.uniform(-2, h, (n, 1)), rng.uniform(-2, w, (n, 1))], axis=1)
    size = np.concatenate([rng.uniform(0.1, h, (n, 1)), rng.uniform(0.1, w, (n, 1))], axis=1)
    rois = np.concatenate([fixed, np.concatenate([p, np.minimum(p + size, [h, w])], axis=1)]).astype(np.float32)
    keep = (_edges_np(rois, out, h, w, False) == _edges_np(rois, out, h, w, True)).all(axis=(1, 2))
    assert keep[: len(fixed)].sum() >= len(fixed) - 1 and keep.sum() >= len(fixed) + n // 2
    return rois[keep]


def _pool_feat(rng, shape, dtype, ties):
    """Tie-free normal features, or post-ReLU ones on a coarse grid: many
    exact ties, zeros among them."""
    feat = rng.normal(0, 1, shape).astype(np.float32)
    if ties:
        feat = np.maximum(np.round(feat * 2) / 2, 0)
    return np.asarray(jnp.asarray(feat, dtype).astype(jnp.float32))  # the dtype's own values


@pytest.mark.parametrize("ties", [False, True], ids=["tie_free", "relu_ties"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("size", list(MAPS))
def test_roi_pool_forward_bitwise_against_oracle(size, dtype, ties):
    h, w, c = MAPS[size]
    rng = np.random.default_rng(h * w)
    feat = _pool_feat(rng, (h, w, c), dtype, ties)
    rois = _pool_rois(rng, h, w)
    got = roi_ops.roi_pool(jnp.asarray(feat, dtype), jnp.asarray(rois), 7)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), oracles.roi_pool_np(feat, rois, 7))


FRAMES = dict(MAPS, **{"6x13": (6, 13, 3), "37x37": (37, 37, 2)})  # (W + 1) % 7 == 0; a floored 600 / 16


def _survive(rois, h, w, out=7):
    return rois[(_edges_np(rois, out, h, w, False) == _edges_np(rois, out, h, w, True)).all(axis=(1, 2))]


@pytest.mark.parametrize("size", list(FRAMES))
def test_roi_pool_takes_every_roi_as_wide_as_the_maps_frame(size):
    """The op's domain ends at a rounded extent of W + 2 columns (clipped
    boxes on a trunk that floors its map: [0, W + 1]; on one that rounds up:
    [-1, W]). Every ROI of that extent and of W + 1, at every offset from
    wholly left of the map to wholly right of it, is the oracle's bitwise."""
    h, w, c = FRAMES[size]
    rng = np.random.default_rng(w)
    feat = _pool_feat(rng, (h, w, c), jnp.float32, ties=True)
    rois = []
    for extent in (w + 1, w + 2):
        for c1 in range(-extent - 1, w + 2):
            r1 = rng.uniform(-1, h - 1)
            rois.append([r1, c1 + rng.uniform(-0.4, 0.4), r1 + rng.uniform(0, h), c1 + extent - 1 + rng.uniform(-0.4, 0.4)])
    rois = _survive(np.asarray(rois, np.float32), h, w)
    assert len(rois) > w  # most offsets survive both ways of dividing
    assert (np.round(rois[:, 3]) - np.round(rois[:, 1]) + 1).max() == w + 2
    got = roi_ops.roi_pool(jnp.asarray(feat), jnp.asarray(rois), 7)
    np.testing.assert_array_equal(np.asarray(got), oracles.roi_pool_np(feat, rois, 7))


@pytest.mark.parametrize("size", ["6x13", "38x38"])
def test_roi_pool_beyond_the_frame_is_pinned(size):
    """Past that extent the op is outside its domain, and what it does there
    is stated, not left to chance: a bin no wider than the widest table is
    still exact, a wider one is pooled over its first `_widest_bin` columns."""
    h, w, c = FRAMES[size]
    out = 7
    widest = roi_ops._widest_bin(w, out)
    rng = np.random.default_rng(h)
    feat = _pool_feat(rng, (h, w, c), jnp.float32, ties=False)
    rois = []
    for extent in (w + 3, w + 8, 2 * w, 3 * w + 1):
        for c1 in range(-extent, w, 3):
            rois.append([-0.3, c1, h - 0.8, c1 + extent - 1])
    rois = _survive(np.asarray(rois, np.float32), h, w, out)
    edges = _edges_np(rois, out, h, w, False)
    want = np.zeros((len(rois), out, out, c), np.float32)
    too_wide = 0
    for r, (hs, he, ws, we) in enumerate(edges):
        for i in range(out):
            for j in range(out):
                too_wide += we[j] - ws[j] > widest
                if he[i] > hs[i] and we[j] > ws[j]:
                    want[r, i, j] = feat[hs[i] : he[i], ws[j] : min(we[j], ws[j] + widest)].max(axis=(0, 1))
    exact = (edges[:, 3] - edges[:, 2] <= widest).all(axis=1)
    assert too_wide > 0 and exact.sum() > 3
    got = np.asarray(roi_ops.roi_pool(jnp.asarray(feat), jnp.asarray(rois), out))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got[exact], oracles.roi_pool_np(feat, rois[exact], out))


def _dense_pool(feat, rois, out):
    """The dense per-bin maximum, differentiated by JAX: the benchmark's
    float32 reference (jitted, so that both sides divide the same way)."""
    from perf.references.frcnn import _roi_pool_one

    return jax.jit(_roi_pool_one, static_argnums=2)(feat, rois, out)


@pytest.mark.parametrize("size", list(MAPS))
def test_roi_pool_gradient_matches_the_dense_reference_without_ties(size):
    h, w, c = MAPS[size]
    rng = np.random.default_rng(h + w)
    feat = jnp.asarray(_pool_feat(rng, (h, w, c), jnp.float32, ties=False))
    rois = jnp.asarray(_pool_rois(rng, h, w))
    wgt = jnp.asarray(rng.normal(0, 1, (rois.shape[0], 7, 7, c)), jnp.float32)
    got = jax.grad(lambda f: (roi_ops.roi_pool(f, rois, 7) * wgt).sum())(feat)
    want = jax.grad(lambda f: (_dense_pool(f, rois, 7) * wgt).sum())(feat)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6 * scale)


def _bin_probes(feat, rois, out):
    """The VJP of every (roi, bin, channel) cotangent basis vector:
    [R, out, out, C, H, W, C]."""
    pooled, vjp = jax.vjp(lambda f: roi_ops.roi_pool(f, rois, out), feat)
    eye = jnp.eye(pooled.size, dtype=pooled.dtype).reshape((pooled.size,) + pooled.shape)
    (grads,) = jax.vmap(vjp)(eye)
    return np.asarray(pooled.astype(jnp.float32)), np.asarray(grads.astype(jnp.float32)).reshape(pooled.shape + feat.shape)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_roi_pool_tied_cotangent_goes_whole_to_the_first_maximum(dtype):
    """On tied features each bin sends its cotangent to ONE element: the
    first maximal one in row-major order (Caffe's argmax). So the support
    lies on maximal elements, each bin's mass is conserved, an empty bin
    sends nothing and nothing is NaN."""
    h, w, c, out = 8, 8, 2, 7
    rng = np.random.default_rng(11)
    feat = _pool_feat(rng, (h, w, c), dtype, ties=True)
    rois = _pool_rois(rng, h, w, n=6)
    pooled, grads = _bin_probes(jnp.asarray(feat, dtype), jnp.asarray(rois), out)
    assert np.isfinite(grads).all()
    edges = _edges_np(rois, out, h, w, False)
    empties = 0
    for r in range(len(rois)):
        hs, he, ws, we = edges[r]
        for i in range(out):
            for j in range(out):
                for ch in range(c):
                    want = np.zeros((h, w, c), np.float32)
                    if he[i] > hs[i] and we[j] > ws[j]:
                        window = feat[hs[i] : he[i], ws[j] : we[j], ch]
                        first = np.flatnonzero(window.ravel() == window.max())[0]  # row-major
                        want[hs[i] + first // window.shape[1], ws[j] + first % window.shape[1], ch] = 1.0
                        assert pooled[r, i, j, ch] == window.max()
                    else:
                        empties += 1
                        assert pooled[r, i, j, ch] == 0.0
                    np.testing.assert_array_equal(grads[r, i, j, ch], want)
    assert empties > 0


def test_roi_pool_under_vmap_jit_value_and_grad():
    """The head's use: a batch of images through `vmap`, inside `jit`, under
    `value_and_grad`; each image's gradient is its own."""
    h, w, c = 38, 38, 4
    rng = np.random.default_rng(5)
    feats = jnp.asarray(np.stack([_pool_feat(rng, (h, w, c), jnp.float32, ties=t) for t in (False, True)]))
    rois = jnp.asarray(np.stack([_pool_rois(rng, h, w, n=32)[:24] for _ in range(2)]))
    wgt = jnp.asarray(rng.normal(0, 1, (2, 24, 7, 7, c)), jnp.float32)

    def loss(f):
        crops = jax.vmap(lambda a, b: roi_ops.extract_roi_features(a, b, op="pool", out_size=7))(f, rois)
        return (crops * wgt).sum()

    value, grad = jax.jit(jax.value_and_grad(loss))(feats)
    assert np.isfinite(float(value)) and np.isfinite(np.asarray(grad)).all()
    for n in range(2):
        one = jax.grad(lambda f: (roi_ops.roi_pool(f, rois[n], 7) * wgt[n]).sum())(feats[n])
        np.testing.assert_allclose(np.asarray(grad[n]), np.asarray(one), rtol=1e-6, atol=1e-6)
    # every non-empty bin's weight arrives somewhere: the mass is conserved
    pooled = jax.vmap(lambda a, b: roi_ops.roi_pool(a, b, 7))(feats, rois)
    edges = np.stack([_edges_np(np.asarray(rois[n]), 7, h, w, False) for n in range(2)])  # [N, R, 4, out]
    filled = (edges[:, :, 1] > edges[:, :, 0])[..., :, None] & (edges[:, :, 3] > edges[:, :, 2])[..., None, :]
    np.testing.assert_allclose(float(grad.sum()), float((np.asarray(wgt) * filled[..., None]).sum()), rtol=1e-4)
    assert (np.asarray(pooled)[~filled] == 0).all()


def _all_avals(jaxpr):
    """Every value of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _all_avals(inner)


def test_roi_pool_never_sweeps_the_map_per_roi():
    """No intermediate of the forward or of the VJP has R*H*W*C elements or
    more: what is per ROI is [out, H, C] or [out, out, H, C], what is per
    map is shared by the ROIs. A return to masked sweeps of the whole map
    for every ROI fails here and not only on the benchmark."""
    r, h, w, c = 32, 50, 84, 8
    feat = jax.ShapeDtypeStruct((h, w, c), jnp.float32)
    rois = jax.ShapeDtypeStruct((r, 4), jnp.float32)
    g = jax.ShapeDtypeStruct((r, 7, 7, c), jnp.float32)
    forward = jax.make_jaxpr(lambda f, b: roi_ops.roi_pool(f, b, 7))(feat, rois)
    backward = jax.make_jaxpr(lambda f, b, ct: jax.vjp(lambda x: roi_ops.roi_pool(x, b, 7), f)[1](ct))(feat, rois, g)
    for name, closed in (("forward", forward), ("vjp", backward)):
        sizes = [int(np.prod(a.shape)) for a in _all_avals(closed.jaxpr) if hasattr(a, "shape")]
        assert len(sizes) > 20, name  # the walk went inside the jit and the custom_vjp
        assert max(sizes) < r * h * w * c, (name, max(sizes), r * h * w * c)
