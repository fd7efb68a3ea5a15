"""Plain float32 reference of one Faster R-CNN training step.

The whole iteration the benchmark times, written straight from the papers
(arXiv:1506.01497, arXiv:1612.03144, arXiv:1512.03385) in `jax.numpy`:
ResNet trunk (and FPN neck), RPN heads, proposal decode + greedy NMS, both
target assigners, ROIPool / multilevel ROIAlign, the box head, the four
losses, the gradients, and Adam with L2 weight decay. No kernels, no
tiling, no mixed precision: float32 everywhere, matrix products at
`Precision.HIGHEST`.

It imports nothing of the program. It is a configuration's `reference`
(perf/harness.py lists what such a module states; the last of it ends this
file). It reads its sizes from the configuration's JSON file (`sizes`, keyed by the program's dotted config
names so that the harness can hold the program to the same numbers) and
makes its own weights from a seed (`init_params`).

Departures from the published description, all of them so that the numbers
can be compared with the program's at all:

* the two samplers draw with the program's key schedule
  (`fold_in(rng, step)` -> 3 keys -> `fold_in(key, image position)` ->
  uniform priorities); a different schedule samples other anchors and ROIs;
* outputs are fixed-size and masked (600 proposal slots, 128 ROI slots);
* BatchNorm keeps no running statistics: a training step never reads them;
* each residual block is recomputed in the backward pass
  (`jax.checkpoint`) so that float32 activations of the timed batch fit
  beside nothing else on a 16 GB chip. It changes no value.

`precision` selects what the control of `correct` needs: "float32" is the
reference; "bfloat16" and "float8" round the operands of every convolution
and matrix product to that type's precision (float8: e4m3 with a per-tensor
scale, the usual fp8 recipe) and leave the rest in float32.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
Params = Dict[str, jnp.ndarray]

_DEPTHS = {"resnet18": ("basic", (2, 2, 2, 2)), "resnet50": ("bottleneck", (3, 4, 6, 3))}
_WIDTHS = (64, 128, 256, 512)
FPN_STRIDES = (4, 8, 16, 32, 64)


# --------------------------------------------------------------- sizes


class Sizes:
    """The configuration's sizes, read from its JSON `sizes` block."""

    def __init__(self, sizes: Dict[str, Any], batch: int) -> None:
        g = sizes.__getitem__
        self.batch = int(batch)
        self.backbone = g("model.backbone")
        self.fpn = bool(g("model.fpn"))
        self.roi_op = g("model.roi_op")
        self.num_classes = int(g("model.num_classes"))
        self.rpn_mid = int(g("model.fpn_channels") if self.fpn else g("model.rpn_mid_channels"))
        self.fpn_channels = int(g("model.fpn_channels"))
        self.roi_size = int(g("model.roi_size"))
        self.sampling_ratio = int(g("model.roi_sampling_ratio"))
        self.image_hw = tuple(int(v) for v in g("data.image_size"))
        self.ratios = tuple(float(v) for v in g("anchors.ratios"))
        self.scales = tuple(float(v) for v in g("anchors.scales"))
        self.base_size = int(g("anchors.base_size"))
        self.feat_stride = int(g("anchors.feat_stride"))
        self.pre_nms = int(g("proposals.pre_nms_train"))
        self.post_nms = int(g("proposals.post_nms_train"))
        self.nms_thresh = float(g("proposals.nms_thresh"))
        self.min_size = float(g("proposals.min_size"))
        self.rpn_n_sample = int(g("rpn_targets.n_sample"))
        self.rpn_pos_iou = float(g("rpn_targets.pos_iou_thresh"))
        self.rpn_neg_iou = float(g("rpn_targets.neg_iou_thresh"))
        self.rpn_pos_ratio = float(g("rpn_targets.pos_ratio"))
        self.roi_n_sample = int(g("roi_targets.n_sample"))
        self.roi_pos_ratio = float(g("roi_targets.pos_ratio"))
        self.roi_pos_iou = float(g("roi_targets.pos_iou_thresh"))
        self.roi_neg_hi = float(g("roi_targets.neg_iou_thresh_high"))
        self.roi_neg_lo = float(g("roi_targets.neg_iou_thresh_low"))
        self.reg_mean = tuple(float(v) for v in g("roi_targets.reg_mean"))
        self.reg_std = tuple(float(v) for v in g("roi_targets.reg_std"))
        self.lr = float(g("train.lr"))
        self.weight_decay = float(g("train.weight_decay"))
        self.sigma = float(g("train.smooth_l1_sigma"))
        self.loss_weights = tuple(float(v) for v in g("train.loss_weights"))
        self.k = len(self.ratios) * len(self.scales)


# ----------------------------------------------------------- precision


def _straight_through(x, qx):
    return x + lax.stop_gradient(qx - x)


def make_rounding(precision: str):
    """Operand rounding for convolutions and matrix products. Done with
    `lax.reduce_precision`: the TPU compiler removes a plain
    `astype(narrow).astype(float32)` round trip (measured on the v5e, PR 23:
    the round trip through bfloat16 or float8 came back bit-identical)."""
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: _straight_through(x, lax.reduce_precision(x, 8, 7))
    if precision == "float8":

        def q(x):
            # e4m3 (4 exponent bits, 3 of mantissa) with a per-tensor scale that
            # puts the largest magnitude at the format's largest normal, 240
            amax = jnp.maximum(jnp.max(jnp.abs(lax.stop_gradient(x))), 1e-30)
            scale = amax / 240.0
            return _straight_through(x, lax.reduce_precision(x / scale, 4, 3) * scale)

        return q
    raise ValueError(f"unknown precision {precision!r}")


# ------------------------------------------------------------- weights


def _param_shapes(sz: Sizes) -> List[Tuple[str, Tuple[int, ...], float]]:
    """(name, shape, std) of every parameter; std < 0 marks a constant
    (-1: ones, -2: zeros). Names follow the torch module names the papers'
    public implementations use, '/'-joined."""
    out: List[Tuple[str, Tuple[int, ...], float]] = []

    def conv(name, k, cin, cout, std=None, bias=False):
        s = math.sqrt(1.0 / (k * k * cin)) if std is None else std
        out.append((f"{name}/kernel", (k, k, cin, cout), s))
        if bias:
            out.append((f"{name}/bias", (cout,), -2.0))

    def bn(name, c):
        out.append((f"{name}/scale", (c,), -1.0))
        out.append((f"{name}/bias", (c,), -2.0))

    def dense(name, cin, cout, std=None):
        s = math.sqrt(1.0 / cin) if std is None else std
        out.append((f"{name}/kernel", (cin, cout), s))
        out.append((f"{name}/bias", (cout,), -2.0))

    kind, depths = _DEPTHS[sz.backbone]
    exp = 4 if kind == "bottleneck" else 1

    def stage(prefix, li, cin):
        feats = _WIDTHS[li]
        cout = feats * exp
        for b in range(depths[li]):
            stride = (1 if li == 0 else 2) if b == 0 else 1
            name = f"{prefix}/layer{li + 1}.{b}"
            if kind == "basic":
                conv(f"{name}/conv1", 3, cin, feats)
                bn(f"{name}/bn1", feats)
                conv(f"{name}/conv2", 3, feats, feats)
                bn(f"{name}/bn2", feats)
            else:
                conv(f"{name}/conv1", 1, cin, feats)
                bn(f"{name}/bn1", feats)
                conv(f"{name}/conv2", 3, feats, feats)
                bn(f"{name}/bn2", feats)
                conv(f"{name}/conv3", 1, feats, cout)
                bn(f"{name}/bn3", cout)
            if stride != 1 or cin != cout:
                conv(f"{name}/downsample_conv", 1, cin, cout)
                bn(f"{name}/downsample_bn", cout)
            cin = cout
        return cin

    conv("trunk/conv1", 7, 3, 64)
    bn("trunk/bn1", 64)
    c = 64
    chans = []
    for li in range(4 if sz.fpn else 3):
        c = stage("trunk", li, c)
        chans.append(c)
    if sz.fpn:
        for i, ci in enumerate(chans):
            conv(f"neck/lateral{i}", 1, ci, sz.fpn_channels)
        for i in range(4):
            conv(f"neck/smooth{i}", 3, sz.fpn_channels, sz.fpn_channels)
        rpn_in = sz.fpn_channels
    else:
        rpn_in = c
    conv("rpn/conv1", 3, rpn_in, sz.rpn_mid, std=0.01, bias=True)
    conv("rpn/cls", 1, sz.rpn_mid, sz.k * 2, std=0.01, bias=True)
    conv("rpn/reg", 1, sz.rpn_mid, sz.k * 4, std=0.01, bias=True)
    if sz.fpn:
        dense("head/fc6", sz.roi_size * sz.roi_size * sz.fpn_channels, 1024)
        dense("head/fc7", 1024, 1024)
        emb = 1024
    else:
        emb = stage("head/tail", 3, c)
    dense("head/cls", emb, sz.num_classes, std=0.01)
    dense("head/reg", emb, sz.num_classes * 4, std=0.001)
    return out


def init_params(sz: Sizes, key) -> Params:
    """Every weight from one key: normal(0, sqrt(1/fan_in)) for trunk, neck
    and fc6/fc7 (LeCun), normal(0, 0.01) for the RPN and the class scores,
    normal(0, 0.001) for the box deltas (the paper's values), BatchNorm at
    scale 1 and bias 0, biases 0. Jit the call: one program makes them all."""
    shapes = _param_shapes(sz)
    keys = jax.random.split(key, len(shapes))
    params: Params = {}
    for (name, shape, std), k in zip(shapes, keys):
        if std == -1.0:
            params[name] = jnp.ones(shape, jnp.float32)
        elif std == -2.0:
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            params[name] = std * jax.random.normal(k, shape, jnp.float32)
    return params


def init_adam(params: Params) -> Dict[str, Params]:
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"mu": zeros, "nu": dict(zeros)}


# -------------------------------------------------------------- layers


def _conv(x, w, stride, pad, q, b=None):
    y = lax.conv_general_dilated(
        q(x), q(w), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
    )
    return y if b is None else y + b


def _dense(x, w, b, q):
    return jnp.dot(q(x), q(w), precision=HI) + b


def _bn(x, p: Params, name: str):
    """Training-mode BatchNorm: statistics of this batch, eps 1e-5."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + 1e-5) * p[f"{name}/scale"] + p[f"{name}/bias"]


def _maxpool_3x3_s2(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)),
    )


def _block(x, p: Params, name: str, kind: str, stride: int, q):
    def k(n):
        return p[f"{name}/{n}/kernel"]

    if kind == "basic":
        y = jax.nn.relu(_bn(_conv(x, k("conv1"), stride, 1, q), p, f"{name}/bn1"))
        y = _bn(_conv(y, k("conv2"), 1, 1, q), p, f"{name}/bn2")
    else:
        y = jax.nn.relu(_bn(_conv(x, k("conv1"), 1, 0, q), p, f"{name}/bn1"))
        y = jax.nn.relu(_bn(_conv(y, k("conv2"), stride, 1, q), p, f"{name}/bn2"))
        y = _bn(_conv(y, k("conv3"), 1, 0, q), p, f"{name}/bn3")
    if f"{name}/downsample_conv/kernel" in p:
        x = _bn(_conv(x, k("downsample_conv"), stride, 0, q), p, f"{name}/downsample_bn")
    return jax.nn.relu(y + x)


def _stage(x, p: Params, prefix: str, li: int, sz: Sizes, q):
    kind, depths = _DEPTHS[sz.backbone]
    for b in range(depths[li]):
        stride = (1 if li == 0 else 2) if b == 0 else 1
        name = f"{prefix}/layer{li + 1}.{b}"
        sub = {n: v for n, v in p.items() if n.startswith(name + "/")}
        x = jax.checkpoint(
            lambda xx, pp, name=name, stride=stride: _block(xx, pp, name, kind, stride, q)
        )(x, sub)
    return x


def _features(images, p: Params, sz: Sizes, q):
    x = _conv(images, p["trunk/conv1/kernel"], 2, 3, q)
    x = _maxpool_3x3_s2(jax.nn.relu(_bn(x, p, "trunk/bn1")))
    cs = []
    for li in range(4 if sz.fpn else 3):
        x = _stage(x, p, "trunk", li, sz, q)
        cs.append(x)
    if not sz.fpn:
        return x
    lat = [_conv(c, p[f"neck/lateral{i}/kernel"], 1, 0, q) for i, c in enumerate(cs)]
    td = [lat[3]]
    for i in (2, 1, 0):
        up = jnp.repeat(jnp.repeat(td[0], 2, axis=1), 2, axis=2)
        td.insert(0, lat[i] + up[:, : lat[i].shape[1], : lat[i].shape[2], :])
    outs = [_conv(t, p[f"neck/smooth{i}/kernel"], 1, 1, q) for i, t in enumerate(td)]
    return outs + [outs[3][:, ::2, ::2, :]]


def _rpn(feat, p: Params, q):
    n = feat.shape[0]
    x = jax.nn.relu(_conv(feat, p["rpn/conv1/kernel"], 1, 1, q, p["rpn/conv1/bias"]))
    logits = _conv(x, p["rpn/cls/kernel"], 1, 0, q, p["rpn/cls/bias"])
    deltas = _conv(x, p["rpn/reg/kernel"], 1, 0, q, p["rpn/reg/bias"])
    return logits.reshape(n, -1, 2), deltas.reshape(n, -1, 4)


# --------------------------------------------------------------- boxes


def _anchor_grid(base_size, ratios, scales, stride, fh, fw) -> np.ndarray:
    """[fh*fw*K, 4] anchors [r1, c1, r2, c2]; index (r*fw + c)*K + k, the K
    base anchors ratio-major, h = base*scale*sqrt(ratio), w = base*scale/sqrt(ratio)."""
    ratios = np.asarray(ratios, np.float32)
    scales = np.asarray(scales, np.float32)
    h = (base_size * scales[None, :] * np.sqrt(ratios)[:, None]).reshape(-1)
    w = (base_size * scales[None, :] * np.sqrt(1.0 / ratios)[:, None]).reshape(-1)
    base = np.stack([-h / 2, -w / 2, h / 2, w / 2], axis=1).astype(np.float32)
    rr, cc = np.meshgrid(
        np.arange(fh, dtype=np.float32) * stride,
        np.arange(fw, dtype=np.float32) * stride, indexing="ij",
    )
    shifts = np.stack([rr.ravel(), cc.ravel(), rr.ravel(), cc.ravel()], axis=1)
    return (shifts[:, None, :] + base[None, :, :]).reshape(-1, 4).astype(np.float32)


def _decode(anchors, d):
    h = anchors[..., 2] - anchors[..., 0]
    w = anchors[..., 3] - anchors[..., 1]
    cr = (anchors[..., 0] + anchors[..., 2]) * 0.5
    cc = (anchors[..., 1] + anchors[..., 3]) * 0.5
    r = d[..., 0] * h + cr
    c = d[..., 1] * w + cc
    nh = jnp.exp(jnp.minimum(d[..., 2], 12.0)) * h
    nw = jnp.exp(jnp.minimum(d[..., 3], 12.0)) * w
    return jnp.stack([r - nh * 0.5, c - nw * 0.5, r + nh * 0.5, c + nw * 0.5], axis=-1)


def _encode(src, dst, eps=1e-8):
    sh = jnp.maximum(src[..., 2] - src[..., 0], eps)
    sw = jnp.maximum(src[..., 3] - src[..., 1], eps)
    scr = (src[..., 0] + src[..., 2]) * 0.5
    scc = (src[..., 1] + src[..., 3]) * 0.5
    dh = jnp.maximum(dst[..., 2] - dst[..., 0], eps)
    dw = jnp.maximum(dst[..., 3] - dst[..., 1], eps)
    dcr = (dst[..., 0] + dst[..., 2]) * 0.5
    dcc = (dst[..., 1] + dst[..., 3]) * 0.5
    return jnp.stack(
        [(dcr - scr) / sh, (dcc - scc) / sw, jnp.log(dh / sh), jnp.log(dw / sw)], axis=-1
    )


def _iou(a, b):
    """a [Na, 4], b [Nb, 4] -> [Na, Nb]; 0 where the union is empty."""
    tl = jnp.maximum(a[:, None, :2], b[None, :, :2])
    br = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = br - tl
    inter = jnp.where(jnp.all(wh > 0, axis=-1), wh[..., 0] * wh[..., 1], 0.0)
    area_a = (a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1])
    area_b = (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
    union = area_a[:, None] + area_b[None, :] - inter
    return jnp.where(union > 0, inter / jnp.where(union > 0, union, 1.0), 0.0)


def _greedy_nms(boxes, scores, thresh, max_out):
    """Textbook greedy NMS into `max_out` slots: take the best live box,
    drop everything that overlaps it by more than `thresh`, repeat."""
    n = boxes.shape[0]
    live0 = jnp.where(jnp.isfinite(scores), scores, -jnp.inf)

    def body(i, carry):
        live, idx, valid = carry
        best = jnp.argmax(live)
        ok = live[best] > -jnp.inf
        ious = _iou(boxes[best][None, :], boxes)[0]
        drop = (ious > thresh) | (jnp.arange(n) == best)
        return (
            jnp.where(ok & drop, -jnp.inf, live),
            idx.at[i].set(jnp.where(ok, best, 0).astype(jnp.int32)),
            valid.at[i].set(ok),
        )

    _, idx, valid = lax.fori_loop(
        0, max_out, body,
        (live0, jnp.zeros((max_out,), jnp.int32), jnp.zeros((max_out,), bool)),
    )
    return idx, valid


def _propose_one(anchors, fg, deltas, img_h, img_w, sz: Sizes):
    boxes = _decode(anchors, deltas)
    boxes = jnp.stack(
        [
            jnp.clip(boxes[:, 0], 0.0, img_h), jnp.clip(boxes[:, 1], 0.0, img_w),
            jnp.clip(boxes[:, 2], 0.0, img_h), jnp.clip(boxes[:, 3], 0.0, img_w),
        ],
        axis=-1,
    )
    big = ((boxes[:, 2] - boxes[:, 0]) >= sz.min_size) & ((boxes[:, 3] - boxes[:, 1]) >= sz.min_size)
    scores = jnp.where(big, fg, -jnp.inf)
    pre = min(sz.pre_nms, anchors.shape[0])
    order = jnp.argsort(-scores)[:pre]
    top_boxes, top_scores = boxes[order], scores[order]
    idx, valid = _greedy_nms(top_boxes, top_scores, sz.nms_thresh, sz.post_nms)
    return top_boxes[idx] * valid[:, None], valid


# ------------------------------------------------------------- targets


def _random_subset(key, member, k):
    """Uniformly keep min(k, member.sum()) members: a uniform priority per
    element, the k largest among members stay."""
    r = jax.random.uniform(key, member.shape)
    score = jnp.where(member, r, -jnp.inf)
    kk = jnp.minimum(jnp.asarray(k, jnp.int32), jnp.sum(member).astype(jnp.int32))
    ranked = jnp.sort(score)[::-1]
    cut = ranked[jnp.maximum(kk - 1, 0)]
    return member & (score >= cut) & (kk > 0)


def _anchor_targets_one(key, gt_boxes, gt_mask, anchors, sz: Sizes):
    a = anchors.shape[0]
    has_gt = jnp.any(gt_mask)
    ious = jnp.where(gt_mask[None, :], _iou(anchors, gt_boxes), -1.0)
    match = jnp.argmax(ious, axis=1)
    max_iou = jnp.max(jnp.maximum(ious, 0.0), axis=1)
    # every ground-truth box claims its best anchor
    best_anchor = jnp.where(gt_mask, jnp.argmax(ious, axis=0), a)
    match = match.at[best_anchor].set(jnp.arange(gt_boxes.shape[0], dtype=match.dtype), mode="drop")
    claimed = jnp.zeros((a,), bool).at[best_anchor].set(True, mode="drop")
    labels = jnp.full((a,), -1, jnp.int32)
    labels = jnp.where(max_iou < sz.rpn_neg_iou, 0, labels)
    labels = jnp.where(max_iou >= sz.rpn_pos_iou, 1, labels)
    labels = jnp.where(claimed & has_gt, 1, labels)
    n_pos = int(sz.rpn_pos_ratio * sz.rpn_n_sample)
    k_pos, k_neg = jax.random.split(key)
    keep_pos = _random_subset(k_pos, labels == 1, n_pos)
    labels = jnp.where((labels == 1) & ~keep_pos, -1, labels)
    keep_neg = _random_subset(k_neg, labels == 0, sz.rpn_n_sample - jnp.sum(labels == 1))
    labels = jnp.where((labels == 0) & ~keep_neg, -1, labels)
    reg = jnp.where(has_gt, _encode(anchors, gt_boxes[match]), 0.0)
    labels = jnp.where(has_gt, labels, jnp.where(labels == 1, -1, labels))
    return reg, labels


def _proposal_targets_one(key, rois, roi_valid, gt_boxes, gt_labels, gt_mask, sz: Sizes):
    n = sz.roi_n_sample
    cand = jnp.concatenate([rois, gt_boxes], axis=0)
    cand_valid = jnp.concatenate([roi_valid, gt_mask], axis=0)
    ious = jnp.where(gt_mask[None, :], _iou(cand, gt_boxes), -1.0)
    match = jnp.argmax(ious, axis=1)
    max_iou = jnp.where(cand_valid, jnp.max(jnp.maximum(ious, 0.0), axis=1), -1.0)
    is_pos = cand_valid & (max_iou >= sz.roi_pos_iou)
    is_neg = cand_valid & (max_iou < sz.roi_neg_hi) & (max_iou >= sz.roi_neg_lo)
    k_pos, k_neg, k_pack = jax.random.split(key, 3)
    n_pos_max = int(round(n * sz.roi_pos_ratio))
    keep_pos = _random_subset(k_pos, is_pos, n_pos_max)
    keep_neg = _random_subset(k_neg, is_neg, n - jnp.sum(keep_pos))
    # positives first, then negatives, then empty slots, random within each
    rank = jnp.where(keep_pos, 0, jnp.where(keep_neg, 1, 2)).astype(jnp.float32)
    idx = jnp.argsort(rank + jax.random.uniform(k_pack, rank.shape))[:n]
    slot_pos, slot_neg = keep_pos[idx], keep_neg[idx]
    sample = cand[idx] * (slot_pos | slot_neg)[:, None]
    reg = _encode(sample, gt_boxes[match[idx]])
    reg = (reg - jnp.asarray(sz.reg_mean, jnp.float32)) / jnp.asarray(sz.reg_std, jnp.float32)
    reg = jnp.where(slot_pos[:, None], reg, 0.0)
    labels = jnp.where(slot_pos, gt_labels[match[idx]].astype(jnp.int32), jnp.where(slot_neg, 0, -1))
    return sample, reg, labels


# ------------------------------------------------------------ roi ops


def _roi_pool_one(feat, rois, out):
    """Quantized max pooling of `rois` (feature coordinates) over feat
    [H, W, C] -> [R, out, out, C] (Caffe ROIPool: rounded corners, +1
    extents, floor/ceil bin edges, empty bins 0)."""
    h, w = feat.shape[0], feat.shape[1]
    r1, c1, r2, c2 = (jnp.round(rois[:, i]) for i in range(4))
    bin_h = jnp.maximum(r2 - r1 + 1.0, 1.0) / out
    bin_w = jnp.maximum(c2 - c1 + 1.0, 1.0) / out
    p = jnp.arange(out, dtype=jnp.float32)
    h0 = jnp.clip(jnp.floor(p[None] * bin_h[:, None]) + r1[:, None], 0, h)
    h1 = jnp.clip(jnp.ceil((p[None] + 1) * bin_h[:, None]) + r1[:, None], 0, h)
    w0 = jnp.clip(jnp.floor(p[None] * bin_w[:, None]) + c1[:, None], 0, w)
    w1 = jnp.clip(jnp.ceil((p[None] + 1) * bin_w[:, None]) + c1[:, None], 0, w)
    rows = jnp.arange(h, dtype=jnp.float32)
    cols = jnp.arange(w, dtype=jnp.float32)
    in_row = (rows[None, None, :] >= h0[:, :, None]) & (rows[None, None, :] < h1[:, :, None])
    in_col = (cols[None, None, :] >= w0[:, :, None]) & (cols[None, None, :] < w1[:, :, None])
    bins = []
    for i in range(out):
        row = []
        for j in range(out):
            m = in_row[:, i, :, None] & in_col[:, j, None, :]  # [R, H, W]
            row.append(jnp.max(jnp.where(m[..., None], feat[None], -jnp.inf), axis=(1, 2)))
        bins.append(jnp.stack(row, axis=1))
    pooled = jnp.stack(bins, axis=1)
    return jnp.where(jnp.isfinite(pooled), pooled, 0.0)


def _bilinear(feat, r, c):
    """feat [H, W, C] at points r, c [...]: zero outside [-1, H] x [-1, W],
    clamped to the map inside (torchvision's border rule)."""
    h, w = feat.shape[0], feat.shape[1]
    inside = (r >= -1.0) & (r <= h) & (c >= -1.0) & (c <= w)
    r = jnp.clip(r, 0.0, h - 1.0)
    c = jnp.clip(c, 0.0, w - 1.0)
    r0 = jnp.floor(r).astype(jnp.int32)
    c0 = jnp.floor(c).astype(jnp.int32)
    r1 = jnp.minimum(r0 + 1, h - 1)
    c1 = jnp.minimum(c0 + 1, w - 1)
    ar = (r - r0)[..., None]
    ac = (c - c0)[..., None]
    v = (
        feat[r0, c0] * (1 - ar) * (1 - ac) + feat[r0, c1] * (1 - ar) * ac
        + feat[r1, c0] * ar * (1 - ac) + feat[r1, c1] * ar * ac
    )
    return v * inside[..., None]


def _roi_align_one(feat, rois, out, s):
    """ROIAlign (aligned=False): `s` x `s` bilinear samples a bin, averaged."""
    r1, c1, r2, c2 = (rois[:, i] for i in range(4))
    bin_h = jnp.maximum(r2 - r1, 1.0) / out
    bin_w = jnp.maximum(c2 - c1, 1.0) / out
    pts = (jnp.arange(out * s, dtype=jnp.float32) + 0.5) / s
    rr = r1[:, None] + pts[None, :] * bin_h[:, None]
    cc = c1[:, None] + pts[None, :] * bin_w[:, None]
    grid_r = jnp.broadcast_to(rr[:, :, None], rr.shape + (out * s,))
    grid_c = jnp.broadcast_to(cc[:, None, :], (cc.shape[0], out * s, cc.shape[1]))
    v = _bilinear(feat, grid_r, grid_c)
    return v.reshape(v.shape[0], out, s, out, s, v.shape[-1]).mean(axis=(2, 4))


def _roi_features(feat, rois, img_h, img_w, sz: Sizes):
    """rois [N, R, 4] in image coordinates -> crops [N, R, s, s, C]."""
    if not sz.fpn:
        fh, fw = feat.shape[1], feat.shape[2]
        scaled = rois * jnp.asarray([fh / img_h, fw / img_w, fh / img_h, fw / img_w], jnp.float32)
        if sz.roi_op == "pool":
            return jax.vmap(lambda f, r: _roi_pool_one(f, r, sz.roi_size))(feat, scaled)
        return jax.vmap(lambda f, r: _roi_align_one(f, r, sz.roi_size, sz.sampling_ratio))(feat, scaled)
    # FPN: a ROI of area wh goes to level floor(4 + log2(sqrt(wh) / 224)) of P2..P5
    rh = jnp.maximum(rois[..., 2] - rois[..., 0], 1e-6)
    rw = jnp.maximum(rois[..., 3] - rois[..., 1], 1e-6)
    level = jnp.clip(jnp.floor(4 + jnp.log2(jnp.sqrt(rh * rw) / 224.0)), 2, 5).astype(jnp.int32) - 2
    crops = 0.0
    for li, f in enumerate(feat[:4]):
        sr, sc = f.shape[1] / img_h, f.shape[2] / img_w
        scaled = rois * jnp.asarray([sr, sc, sr, sc], jnp.float32)
        one = jax.vmap(lambda ff, r: _roi_align_one(ff, r, sz.roi_size, sz.sampling_ratio))(f, scaled)
        crops = crops + one * (level == li)[..., None, None, None]
    return crops


# -------------------------------------------------------------- losses


def _smooth_l1(pred, target, sigma):
    s2 = sigma * sigma
    d = jnp.abs(pred - target)
    return jnp.where(d < 1.0 / s2, 0.5 * s2 * d * d, d - 0.5 / s2)


def _loc_loss(pred, target, labels, sigma):
    pos = (labels > 0).astype(jnp.float32)
    return (_smooth_l1(pred, target, sigma).sum(-1) * pos).sum() / jnp.maximum(pos.sum(), 1.0)


def _cross_entropy(logits, labels):
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.where(valid, picked, 0.0).sum() / jnp.maximum(valid.sum(), 1)


# ---------------------------------------------------------------- step


def loss_fn(p: Params, batch, step_key, sz: Sizes, q):
    images = batch["image"].astype(jnp.float32)
    gt_boxes, gt_labels, gt_mask = batch["boxes"], batch["labels"], batch["mask"]
    n = images.shape[0]
    img_h, img_w = float(images.shape[1]), float(images.shape[2])
    k_anchor, k_roi, _ = jax.random.split(step_key, 3)
    positions = jnp.arange(n, dtype=jnp.int32)

    feat = _features(images, p, sz, q)
    if sz.fpn:
        outs = [_rpn(f, p, q) for f in feat]
        logits = jnp.concatenate([o[0] for o in outs], axis=1)
        deltas = jnp.concatenate([o[1] for o in outs], axis=1)
        anchors = np.concatenate(
            [
                _anchor_grid(stride, sz.ratios, sz.scales, stride, f.shape[1], f.shape[2])
                for f, stride in zip(feat, FPN_STRIDES)
            ],
            axis=0,
        )
    else:
        logits, deltas = _rpn(feat, p, q)
        anchors = _anchor_grid(
            sz.base_size, sz.ratios, sz.scales, sz.feat_stride, feat.shape[1], feat.shape[2]
        )
    anchors = jnp.asarray(anchors)

    keys = jax.vmap(lambda i: jax.random.fold_in(k_anchor, i))(positions)
    reg_t, lab_t = jax.vmap(lambda k, b, m: _anchor_targets_one(k, b, m, anchors, sz))(
        keys, gt_boxes, gt_mask
    )
    rpn_reg = _loc_loss(deltas, reg_t, lab_t, sz.sigma)
    rpn_cls = _cross_entropy(logits, lab_t)

    fg = lax.stop_gradient(jax.nn.softmax(logits, axis=-1)[..., 1])
    rois, roi_valid = jax.vmap(
        lambda s, d: _propose_one(anchors, s, d, img_h, img_w, sz)
    )(fg, lax.stop_gradient(deltas))
    keys = jax.vmap(lambda i: jax.random.fold_in(k_roi, i))(positions)
    sample, reg_t2, lab_t2 = jax.vmap(
        lambda k, r, v, b, lbl, m: _proposal_targets_one(k, r, v, b, lbl, m, sz)
    )(keys, rois, roi_valid, gt_boxes, gt_labels, gt_mask)

    crops = _roi_features(feat, sample, img_h, img_w, sz)
    crops = crops.reshape((n * sz.roi_n_sample,) + crops.shape[2:])
    if sz.fpn:
        x = crops.reshape(crops.shape[0], -1)
        x = jax.nn.relu(_dense(x, p["head/fc6/kernel"], p["head/fc6/bias"], q))
        emb = jax.nn.relu(_dense(x, p["head/fc7/kernel"], p["head/fc7/bias"], q))
    else:
        emb = jnp.mean(_stage(crops, p, "head/tail", 3, sz, q), axis=(1, 2))
    cls = _dense(emb, p["head/cls/kernel"], p["head/cls/bias"], q).reshape(n, sz.roi_n_sample, -1)
    reg = _dense(emb, p["head/reg/kernel"], p["head/reg/bias"], q)
    reg = reg.reshape(n, sz.roi_n_sample, sz.num_classes, 4)
    pick = jnp.clip(lab_t2, 0, sz.num_classes - 1)[..., None, None]
    reg_sel = jnp.take_along_axis(reg, jnp.broadcast_to(pick, pick.shape[:-1] + (4,)), axis=2)[:, :, 0]
    head_reg = _loc_loss(reg_sel, reg_t2, lab_t2, sz.sigma)
    head_cls = _cross_entropy(cls, lab_t2)
    w = sz.loss_weights
    total = w[0] * rpn_cls + w[1] * rpn_reg + w[2] * head_cls + w[3] * head_reg
    parts = {
        "rpn_cls_loss": rpn_cls, "rpn_reg_loss": rpn_reg,
        "head_cls_loss": head_cls, "head_reg_loss": head_reg,
        "n_pos_rpn": (lab_t == 1).sum(), "n_pos_head": (lab_t2 > 0).sum(),
    }
    return total, parts


def train_step(params: Params, adam, batch, rng, step, sz: Sizes, precision: str = "float32"):
    """One step: (params, adam, losses, grad) after the update. `losses` has
    the total under "loss" beside its four parts; `grad` is the gradient as
    Adam gets it, the L2 term added (torch's weight_decay)."""
    q = make_rounding(precision)
    step_key = jax.random.fold_in(rng, step)
    (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch, step_key, sz, q)
    b1, b2, eps = 0.9, 0.999, 1e-8
    t = (step + 1).astype(jnp.float32)
    new_p, mu, nu, seen = {}, {}, {}, {}
    for name, p in params.items():
        g = grads[name] + sz.weight_decay * p
        m = b1 * adam["mu"][name] + (1 - b1) * g
        v = b2 * adam["nu"][name] + (1 - b2) * g * g
        update = (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + eps)
        # the schedule is a cosine over epochs: constant lr inside epoch 0
        new_p[name] = p - sz.lr * update
        mu[name], nu[name], seen[name] = m, v, g
    return new_p, {"mu": mu, "nu": nu}, dict(parts, loss=loss), seen


def leaf_norms(tree: Params) -> Dict[str, jnp.ndarray]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


# ------------------------------------------- what else the harness reads

BATCH_KEYS = ("image", "boxes", "labels", "mask")  # of a batch, what `train_step` takes
LOSS_PARTS = ("rpn_cls_loss", "rpn_reg_loss", "head_cls_loss", "head_reg_loss")  # compared at step 1
# The model's own numbers: the worst leaf gap of the first gradient's norms
# under these prefixes. The RPN heads' gradient comes from the two RPN
# losses alone, upstream of every proposal, so no flipped selection reaches
# it; the objectness kernel's is the steadiest (256 sampled anchors an image).
LEAF_NUMBERS = {"rpn_grad_norm_gap": ("rpn/cls/", "rpn/reg/"), "rpn_cls_grad_gap": ("rpn/cls/kernel",)}
SCOPE_PREFIX = "frcnn."  # of the step program's stage scopes (`telemetry/stages.py`)
# the readers' `ctx["flops"]` is this module: the counter is perf/flops.py
from perf.flops import conv_roofline_seconds, train_flops_per_image  # noqa: E402,F401
