"""Feature Pyramid Network — BASELINE.json config #3 ("FPN neck over
ResNet50 + multi-scale anchors").

No reference implementation exists (the reference is single-scale C4;
its `utils/anchors.py` multi-scale anchors are scale-multiples at one
stride). This follows the FPN paper (Lin et al., arXiv:1612.03144) with the
standard Faster-R-CNN-FPN wiring, built fixed-shape for XLA:

  * backbone exposes C2..C5 (strides 4/8/16/32);
  * 1x1 lateral convs + nearest top-down upsample + 3x3 smoothing -> P2..P5,
    plus P6 = stride-2 subsample of P5 (RPN-only level);
  * the RPN head is ONE set of convs shared across levels;
  * anchors use one scale per level (AnchorConfig.scales=(8,)) over
    per-level strides (4, 8, 16, 32, 64);
  * ROIs are assigned to levels by the paper's k = k0 + log2(sqrt(area)/224)
    rule. On TPU the pyramid is flattened into one [N, sum(Hl*Wl), C]
    buffer and each roi does a single 4-corner gather at level-offset flat
    indices — fully static shapes, no sorting/regrouping, one backward
    scatter (see multilevel_roi_align).

All spatial tensors are NHWC; levels are a list ordered fine -> coarse.
"""

from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from replication_faster_rcnn_tpu.models.resnet import _WIDTHS, _conv, _spec, _stage, _stem_pool
from replication_faster_rcnn_tpu.ops import roi_ops

Array = jnp.ndarray

FPN_STRIDES: Tuple[int, ...] = (4, 8, 16, 32, 64)  # P2..P6


class ResNetFeatures(nn.Module):
    """ResNet trunk exposing every stage: [C2, C3, C4, C5]
    (strides 4/8/16/32; channels x1 for BasicBlock, x4 for Bottleneck).

    Same parameter naming/layout as ResNetTrunk+ResNetTail so pretrained
    torch checkpoints convert identically (layer4 lives here, not in the
    head, when FPN is on)."""

    arch: str = "resnet50"
    dtype: Any = jnp.bfloat16
    bn_axis: Any = None
    remat: bool = False  # jax.checkpoint each residual block
    frozen_bn: bool = False  # see ResNetTrunk.frozen_bn
    norm: str = "batch"  # see ResNetTrunk.norm

    @nn.compact
    def __call__(self, x: Array, train: bool = False) -> List[Array]:
        depths = _spec(self.arch)[1]
        train = train and not self.frozen_bn  # `train` only gates BN here
        ax, rm, nm = self.bn_axis, self.remat, self.norm
        x = x.astype(self.dtype)
        x = _conv(64, 7, 2, 3, self.dtype, "conv1")(x)
        x = _stem_pool(x, self.dtype, train, ax, nm)
        c2 = _stage(self.arch, x, _WIDTHS[0], depths[0], 1, self.dtype, train, "layer1", ax, rm, nm)
        c3 = _stage(self.arch, c2, _WIDTHS[1], depths[1], 2, self.dtype, train, "layer2", ax, rm, nm)
        c4 = _stage(self.arch, c3, _WIDTHS[2], depths[2], 2, self.dtype, train, "layer3", ax, rm, nm)
        c5 = _stage(self.arch, c4, _WIDTHS[3], depths[3], 2, self.dtype, train, "layer4", ax, rm, nm)
        return [c2, c3, c4, c5]


def _upsample_nearest(x: Array, target_hw: Tuple[int, int]) -> Array:
    """2x nearest upsample cropped to the (possibly odd) finer shape."""
    n, h, w, c = x.shape
    y = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return y[:, : target_hw[0], : target_hw[1], :]


class FPNNeck(nn.Module):
    """[C2..C5] -> [P2..P6], all ``channels`` wide."""

    channels: int = 256
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, feats: Sequence[Array]) -> List[Array]:
        c2, c3, c4, c5 = feats
        laterals = [
            _conv(self.channels, 1, 1, 0, self.dtype, f"lateral{i}")(c)
            for i, c in enumerate((c2, c3, c4, c5))
        ]
        # top-down pathway
        tds = [laterals[3]]
        for i in (2, 1, 0):
            finer = laterals[i]
            tds.insert(
                0, finer + _upsample_nearest(tds[0], finer.shape[1:3])
            )
        outs = [
            _conv(self.channels, 3, 1, 1, self.dtype, f"smooth{i}")(t)
            for i, t in enumerate(tds)
        ]
        # P6: stride-2 subsample of P5 (maxpool k=1 s=2, Detectron convention)
        p6 = outs[3][:, ::2, ::2, :]
        return outs + [p6]


def roi_levels(rois: Array, k0: int = 4, canonical: float = 224.0) -> Array:
    """FPN paper level assignment: [..., 4] rois -> int level index 0..3
    (P2..P5; P6 is RPN-only). k = k0 + log2(sqrt(area)/canonical)."""
    h = jnp.maximum(rois[..., 2] - rois[..., 0], 1e-6)
    w = jnp.maximum(rois[..., 3] - rois[..., 1], 1e-6)
    k = jnp.floor(k0 + jnp.log2(jnp.sqrt(h * w) / canonical))
    return jnp.clip(k, 2, 5).astype(jnp.int32) - 2


def multilevel_roi_align(
    feats: Sequence[Array],
    rois: Array,
    img_h: float,
    img_w: float,
    out_size: int = 7,
    sampling_ratio: int = 2,
    method: str = "flat",
) -> Array:
    """ROIAlign across P2..P5 with level assignment, fixed-shape.

    feats: 4 arrays [N, Hl, Wl, C]; rois: [N, R, 4] image coords.
    Returns [N, R, out, out, C].

    ``method="flat"`` (default): all four levels are flattened into ONE
    [N, sum(Hl*Wl), C] buffer and every roi does a single 4-corner
    bilinear gather with level-offset flat indices (index = level_offset +
    r * Wl + c, computed from the roi's assigned level). One gather pass
    and one backward scatter for the whole pyramid.

    ``method="blend"``: the original formulation — every roi is aligned on
    EVERY level (gather roi_align per level) and the results combined with
    a one-hot level mask. 4x the gathers and a 4x backward scatter; kept
    as the oracle for the flat path's parity test. The two are the same
    math (the blended sum adds exact zeros) but not bitwise: the sample
    coordinate r1 + pts*bin feeds floor(), and XLA's FMA choice can shift
    the fractional part (the bilinear weight) by ~eps(coordinate).

    The einsum (MXU) roi_align formulation is deliberately not used here:
    its dense [R, P, H] weight matmul is a win on the stride-16
    single-scale map but scales with H*W, which at P2 (stride 4, 150x150
    for 600 input) costs ~10x the whole backbone.
    """
    levels = roi_levels(rois)  # [N, R]
    if method == "blend":
        out = None
        for li, feat in enumerate(feats[:4]):
            scale_r = feat.shape[1] / img_h
            scale_c = feat.shape[2] / img_w
            scale = jnp.asarray([scale_r, scale_c, scale_r, scale_c], rois.dtype)

            def align_one(f: Array, rb: Array) -> Array:
                return roi_ops.roi_align(
                    f,
                    rb * scale,
                    out_size=out_size,
                    sampling_ratio=sampling_ratio,
                    method="gather",
                )

            crops = jax.vmap(align_one)(feat, rois)  # [N, R, s, s, C]
            mask = (levels == li).astype(crops.dtype)[..., None, None, None]
            out = crops * mask if out is None else out + crops * mask
        return out
    if method != "flat":
        raise ValueError(f"unknown multilevel_roi_align method {method!r}")

    import numpy as np

    n, r_cnt = rois.shape[0], rois.shape[1]
    c_dim = feats[0].shape[-1]
    hs = [int(f.shape[1]) for f in feats[:4]]
    ws = [int(f.shape[2]) for f in feats[:4]]
    offs = np.concatenate([[0], np.cumsum([h * w for h, w in zip(hs, ws)])[:-1]])
    flat = jnp.concatenate([f.reshape(n, -1, c_dim) for f in feats[:4]], axis=1)

    dt = rois.dtype
    h_l = jnp.asarray(hs, dt)[levels]  # [N, R] assigned-level extents
    w_l = jnp.asarray(ws, dt)[levels]
    w_li = jnp.asarray(ws, jnp.int32)[levels]
    off_l = jnp.asarray(offs, jnp.int32)[levels]

    # roi coords scaled into assigned-level units (blend path: rb * scale)
    sr = h_l / img_h
    sc = w_l / img_w
    r1, c1 = rois[..., 0] * sr, rois[..., 1] * sc
    r2, c2 = rois[..., 2] * sr, rois[..., 3] * sc

    # sample grid (roi_ops._sample_grid semantics: 1px minimum extent,
    # sample centers at (p + .5)/s bin units)
    s = sampling_ratio
    bin_h = jnp.maximum(r2 - r1, 1.0) / out_size  # [N, R]
    bin_w = jnp.maximum(c2 - c1, 1.0) / out_size
    pts = (jnp.arange(out_size * s, dtype=dt) + 0.5) / s  # [S]
    rr = r1[..., None] + pts * bin_h[..., None]  # [N, R, S]
    cc = c1[..., None] + pts * bin_w[..., None]

    # 4-corner bilinear on the [N, R, S, S] grid, extents per assigned
    # level (roi_ops._bilinear_gather border rule: outside [-1, H]x[-1, W]
    # contributes zero; in-range clamps to the valid window)
    rg = rr[..., :, None] * jnp.ones_like(cc)[..., None, :]
    cg = cc[..., None, :] * jnp.ones_like(rr)[..., :, None]
    hb = h_l[..., None, None]
    wb = w_l[..., None, None]
    in_range = (rg >= -1.0) & (rg <= hb) & (cg >= -1.0) & (cg <= wb)
    rg = jnp.clip(rg, 0.0, hb - 1.0)
    cg = jnp.clip(cg, 0.0, wb - 1.0)
    r0 = jnp.floor(rg)
    c0 = jnp.floor(cg)
    r0i = r0.astype(jnp.int32)
    c0i = c0.astype(jnp.int32)
    r1i = jnp.minimum(r0i + 1, hb.astype(jnp.int32) - 1)
    c1i = jnp.minimum(c0i + 1, wb.astype(jnp.int32) - 1)
    ar = rg - r0
    ac = cg - c0

    base = off_l[..., None, None]
    wrow = w_li[..., None, None]

    def corner(ri: Array, ci: Array) -> Array:
        idx = (base + ri * wrow + ci).reshape(n, -1)  # [N, R*S*S]
        return jnp.take_along_axis(flat, idx[..., None], axis=1)  # [N, K, C]

    def w3(w: Array) -> Array:
        return w.reshape(n, -1, 1)

    sampled = (
        corner(r0i, c0i) * w3((1 - ar) * (1 - ac))
        + corner(r0i, c1i) * w3((1 - ar) * ac)
        + corner(r1i, c0i) * w3(ar * (1 - ac))
        + corner(r1i, c1i) * w3(ar * ac)
    )
    sampled = sampled * w3(in_range.astype(sampled.dtype))
    sampled = sampled.reshape(n, r_cnt, out_size, s, out_size, s, c_dim)
    return sampled.mean(axis=(3, 5))
