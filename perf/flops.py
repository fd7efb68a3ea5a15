"""Operations and bytes a training step NEEDS, from the configuration's
shapes alone (never from the compiler's count of what the program happens
to run: a recompute or a fused-away op must not move the numerator).

Counted: every convolution and matrix product of trunk, neck, RPN heads
and box head, 2 FLOPs a multiply-add. A training step is the forward pass,
the gradient for the input and the gradient for the weights of each layer:
forward x 3, less the stem's input gradient, which nothing needs. Not
counted: BatchNorm, activations, NMS, IoU matching, ROI pooling, losses,
the optimizer (vector work, under a percent of the FLOPs) and anything the
program recomputes.

Bytes of a convolution pass are its operands and result at 2 bytes an
element (the configurations compute in bfloat16), each touched once.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

_DEPTHS = {"resnet18": ("basic", (2, 2, 2, 2)), "resnet50": ("bottleneck", (3, 4, 6, 3))}
_WIDTHS = (64, 128, 256, 512)
FPN_STRIDES = (4, 8, 16, 32, 64)


def _half(n: int) -> int:
    return math.ceil(n / 2)


def layers(sizes: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Every matrix-product layer of one image's forward pass:
    {name, kind, k, cin, cout, in_hw, out_hw, count, input_grad}. `count`
    is how many times the layer runs for one image (ROIs per image for the
    box head), `out_hw` its output positions per run."""
    backbone = sizes["model.backbone"]
    fpn = bool(sizes["model.fpn"])
    kind, depths = _DEPTHS[backbone]
    exp = 4 if kind == "bottleneck" else 1
    h, w = (int(v) for v in sizes["data.image_size"])
    n_roi = int(sizes["roi_targets.n_sample"])
    k_anchor = len(sizes["anchors.ratios"]) * len(sizes["anchors.scales"])
    out: List[Dict[str, Any]] = []

    def conv(name, k, cin, cout, ih, iw, stride, count=1, input_grad=True):
        oh, ow = (_half(ih), _half(iw)) if stride == 2 else (ih, iw)
        out.append(
            dict(name=name, kind="conv", k=k, cin=cin, cout=cout, in_hw=(ih, iw),
                 out_hw=(oh, ow), count=count, input_grad=input_grad)
        )
        return oh, ow

    def stage(prefix, li, cin, ih, iw, count=1):
        feats = _WIDTHS[li]
        cout = feats * exp
        for b in range(depths[li]):
            stride = (1 if li == 0 else 2) if b == 0 else 1
            name = f"{prefix}/layer{li + 1}.{b}"
            if kind == "basic":
                oh, ow = conv(f"{name}/conv1", 3, cin, feats, ih, iw, stride, count)
                conv(f"{name}/conv2", 3, feats, feats, oh, ow, 1, count)
            else:
                conv(f"{name}/conv1", 1, cin, feats, ih, iw, 1, count)
                oh, ow = conv(f"{name}/conv2", 3, feats, feats, ih, iw, stride, count)
                conv(f"{name}/conv3", 1, feats, cout, oh, ow, 1, count)
            if stride != 1 or cin != cout:
                conv(f"{name}/downsample_conv", 1, cin, cout, ih, iw, stride, count)
            cin, ih, iw = cout, oh, ow
        return cin, ih, iw

    ih, iw = conv("trunk/conv1", 7, 3, 64, h, w, 2, input_grad=False)
    ih, iw = _half(ih), _half(iw)  # 3x3/s2 max pool
    c = 64
    levels = []
    for li in range(4 if fpn else 3):
        c, ih, iw = stage("trunk", li, c, ih, iw)
        levels.append((c, ih, iw))
    if fpn:
        ch = int(sizes["model.fpn_channels"])
        for i, (ci, lh, lw) in enumerate(levels):
            conv(f"neck/lateral{i}", 1, ci, ch, lh, lw, 1)
            conv(f"neck/smooth{i}", 3, ch, ch, lh, lw, 1)
        p5 = levels[3]
        rpn_levels = [(lh, lw) for _, lh, lw in levels] + [(_half(p5[1]), _half(p5[2]))]
        for i, (lh, lw) in enumerate(rpn_levels):
            conv(f"rpn/conv1@P{i + 2}", 3, ch, ch, lh, lw, 1)
            conv(f"rpn/cls@P{i + 2}", 1, ch, k_anchor * 2, lh, lw, 1)
            conv(f"rpn/reg@P{i + 2}", 1, ch, k_anchor * 4, lh, lw, 1)
        s = int(sizes["model.roi_size"])
        out.append(dict(name="head/fc6", kind="dense", k=1, cin=s * s * ch, cout=1024,
                        in_hw=(1, 1), out_hw=(1, 1), count=n_roi, input_grad=True))
        out.append(dict(name="head/fc7", kind="dense", k=1, cin=1024, cout=1024,
                        in_hw=(1, 1), out_hw=(1, 1), count=n_roi, input_grad=True))
        emb = 1024
    else:
        mid = int(sizes["model.rpn_mid_channels"])
        conv("rpn/conv1", 3, c, mid, ih, iw, 1)
        conv("rpn/cls", 1, mid, k_anchor * 2, ih, iw, 1)
        conv("rpn/reg", 1, mid, k_anchor * 4, ih, iw, 1)
        s = int(sizes["model.roi_size"])
        emb, _, _ = stage("head/tail", 3, c, s, s, count=n_roi)
    ncls = int(sizes["model.num_classes"])
    for name, cout in (("head/cls", ncls), ("head/reg", ncls * 4)):
        out.append(dict(name=name, kind="dense", k=1, cin=emb, cout=cout,
                        in_hw=(1, 1), out_hw=(1, 1), count=n_roi, input_grad=True))
    return out


def forward_flops(layer: Dict[str, Any]) -> float:
    oh, ow = layer["out_hw"]
    return 2.0 * oh * ow * layer["k"] ** 2 * layer["cin"] * layer["cout"] * layer["count"]


def train_flops_per_image(sizes: Dict[str, Any], kinds=("conv", "dense")) -> float:
    total = 0.0
    for layer in layers(sizes):
        if layer["kind"] in kinds:
            total += forward_flops(layer) * (3.0 if layer["input_grad"] else 2.0)
    return total


def conv_roofline_seconds(
    sizes: Dict[str, Any], images: int, flops_per_s: float, bytes_per_s: float
) -> Dict[str, float]:
    """The least time one chip could take for the convolutions of a step
    over `images` images: for each pass of each convolution the larger of
    FLOPs over peak and bytes over bandwidth, summed. Also the two sums
    alone, to say which bound binds."""
    least = by_flops = by_bytes = 0.0
    for layer in layers(sizes):
        if layer["kind"] != "conv":
            continue
        n = images * layer["count"]
        ih, iw = layer["in_hw"]
        oh, ow = layer["out_hw"]
        x = 2.0 * n * ih * iw * layer["cin"]
        y = 2.0 * n * oh * ow * layer["cout"]
        wts = 2.0 * layer["k"] ** 2 * layer["cin"] * layer["cout"]
        f = forward_flops(layer) * images
        passes = [x + wts + y, x + y + wts] + ([y + wts + x] if layer["input_grad"] else [])
        for b in passes:
            tf, tb = f / flops_per_s, b / bytes_per_s
            least += max(tf, tb)
            by_flops += tf
            by_bytes += tb
    return {"least_s": least, "flops_s": by_flops, "bytes_s": by_bytes}
