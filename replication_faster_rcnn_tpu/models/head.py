"""Second-stage detection head — ROI feature extraction + ResNet tail + fc.

Capability parity with reference `nets/heads.py:7-59` (``ResnetHead``),
redesigned fixed-shape:

  * ROIs arrive batched [N, R, 4] in image coordinates with a validity mask
    (instead of the reference's flat [N*R, 4] + batch-index column,
    `nets/heads.py:47`); extraction vmaps the ROIAlign/ROIPool op over the
    batch.
  * ROIs are scaled image->feature by dividing by the image size and
    multiplying by the feature size, exactly the reference's arithmetic
    (`nets/heads.py:42-44` — equivalent to 1/feat_stride).
  * The pooled crops run through the backbone tail (layer4 + avgpool — the
    reference's `classifier`, `nets/heads.py:51-52`) then two Linear heads:
    reg -> num_classes*4, cls -> num_classes (`nets/heads.py:21-22`), with
    in-features derived from the tail (fixing the hard-coded 512 that broke
    resnet50 in the reference, SURVEY.md §2.1 #11).
  * Invalid (padded) rois produce outputs as normal; callers mask the loss.
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from replication_faster_rcnn_tpu.models.resnet import ResNetTail
from replication_faster_rcnn_tpu.ops import roi_ops
from replication_faster_rcnn_tpu.telemetry import stages

Array = jnp.ndarray


class QuantDense(nn.Module):
    """int8 twin of the cls/reg Dense: same param names/shapes ("kernel"
    int8 [in, out], "bias" f32), computed as a true int8 GEMM through
    `ops/quant_ops.py::quant_dense` with the calibrated activation scale.
    Only ever instantiated when the serve path supplies a ``"quant"``
    collection entry — the f32/bf16 trace never reaches this class, so
    the fingerprint-banked programs are untouched."""

    features: int

    @nn.compact
    def __call__(self, x: Array, qinfo) -> Array:
        from replication_faster_rcnn_tpu.ops import quant_ops

        kernel = self.param(
            "kernel",
            lambda rng, shape: jnp.zeros(shape, jnp.int8),
            (x.shape[-1], self.features),
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.features,), jnp.float32
        )
        return quant_ops.quant_dense(
            x, kernel, qinfo["w_scale"], qinfo["x_scale"], bias
        )


def _head_dense(mod: nn.Module, x: Array, features: int, stddev: float, name: str) -> Array:
    """cls/reg projection: the banked nn.Dense, or its QuantDense twin
    when the caller passed quantization info for this layer."""
    if mod.has_variable("quant", name):
        return QuantDense(features, name=name)(x, mod.get_variable("quant", name))
    return nn.Dense(
        features,
        kernel_init=nn.initializers.normal(stddev=stddev),
        param_dtype=jnp.float32,
        name=name,
    )(x)


class DetectionHead(nn.Module):
    """ROI extract + tail + cls/reg Linear heads.

    __call__(feat [N, H, W, C], rois [N, R, 4], img_h, img_w, train)
      -> (cls_logits [N, R, num_classes], reg [N, R, num_classes*4]) float32.
    """

    arch: str = "resnet18"
    num_classes: int = 21
    roi_size: int = 7
    roi_op: str = "align"  # "align" | "pool"
    sampling_ratio: int = 2
    dtype: Any = jnp.bfloat16
    bn_axis: Any = None  # sync-BN axis for the ResNet tail under shard_map
    frozen_bn: bool = False  # see ResNetTrunk.frozen_bn (applies to the tail)
    norm: str = "batch"  # see ResNetTrunk.norm (applies to the tail)

    @nn.compact
    def __call__(
        self,
        feat: Array,
        rois: Array,
        img_h: float,
        img_w: float,
        train: bool = False,
    ) -> Tuple[Array, Array]:
        n, r = rois.shape[0], rois.shape[1]
        fh, fw = feat.shape[1], feat.shape[2]

        def extract(f: Array, rb: Array) -> Array:
            return roi_ops.extract_roi_features(
                f,
                rb,
                op=self.roi_op,
                out_size=self.roi_size,
                sampling_ratio=self.sampling_ratio,
            )

        with jax.named_scope(stages.ROI_POOL):
            # image -> feature coordinates (reference `nets/heads.py:42-44`)
            scale = jnp.array(
                [fh / img_h, fw / img_w, fh / img_h, fw / img_w], rois.dtype
            )
            feat_rois = rois * scale
            crops = jax.vmap(extract)(feat, feat_rois)  # [N, R, s, s, C]
            crops = crops.reshape((n * r,) + crops.shape[2:])

        # Backbone tail: layer4+avgpool for ResNets (the reference's
        # `classifier`, `nets/heads.py:51-52`); fc6/fc7 for the
        # prototxt-documented VGG16 (models/vgg.py).
        if self.arch == "vgg16":
            from replication_faster_rcnn_tpu.models.vgg import VGG16Tail

            embed = VGG16Tail(self.dtype, name="tail")(crops, train)
        else:
            embed = ResNetTail(
                self.arch, self.dtype, bn_axis=self.bn_axis,
                frozen_bn=self.frozen_bn, norm=self.norm, name="tail"
            )(crops, train)
        embed = embed.astype(jnp.float32)  # [N*R, C_tail]

        # Paper-standard inits the reference leaves at torch defaults:
        # cls N(0, 0.01), reg N(0, 0.001).
        cls = _head_dense(self, embed, self.num_classes, 0.01, "cls")
        reg = _head_dense(self, embed, self.num_classes * 4, 0.001, "reg")
        return cls.reshape(n, r, -1), reg.reshape(n, r, -1)


class FPNDetectionHead(nn.Module):
    """FPN variant of the detection head: multilevel ROIAlign + the paper's
    two-fc (1024-1024) box head instead of the ResNet layer4 tail (which the
    FPN backbone consumes as C5).

    __call__(feats [P2..P6 list], rois [N, R, 4], img_h, img_w, train)
      -> (cls_logits [N, R, num_classes], reg [N, R, num_classes*4]).
    """

    num_classes: int = 21
    roi_size: int = 7
    sampling_ratio: int = 2
    mlp_dim: int = 1024
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(
        self,
        feats,
        rois: Array,
        img_h: float,
        img_w: float,
        train: bool = False,
    ) -> Tuple[Array, Array]:
        from replication_faster_rcnn_tpu.models.fpn import multilevel_roi_align

        n, r = rois.shape[0], rois.shape[1]
        with jax.named_scope(stages.ROI_POOL):
            crops = multilevel_roi_align(
                feats, rois, img_h, img_w, self.roi_size, self.sampling_ratio
            )  # [N, R, s, s, C]
        x = crops.reshape(n * r, -1).astype(self.dtype)
        # dtype=self.dtype keeps the two big matmuls on the MXU in bf16
        # (param_dtype stays f32; flax would otherwise promote to f32).
        x = nn.relu(
            nn.Dense(self.mlp_dim, dtype=self.dtype, param_dtype=jnp.float32, name="fc6")(x)
        )
        x = nn.relu(
            nn.Dense(self.mlp_dim, dtype=self.dtype, param_dtype=jnp.float32, name="fc7")(x)
        )
        x = x.astype(jnp.float32)  # cls/reg logits in f32
        cls = _head_dense(self, x, self.num_classes, 0.01, "cls")
        reg = _head_dense(self, x, self.num_classes * 4, 0.001, "reg")
        return cls.reshape(n, r, -1), reg.reshape(n, r, -1)


def select_class_deltas(reg: Array, labels: Array) -> Array:
    """Pick each roi's box deltas for a given class id.

    reg: [..., R, num_classes*4]; labels: [..., R] int -> [..., R, 4].
    The reference does this with gather over computed flat indices
    label*4 + {0..3} (`train.py:112-117`); here it is a take_along_axis
    over the class axis.
    """
    shape = reg.shape[:-1] + (-1, 4)
    per_class = reg.reshape(shape)  # [..., R, C, 4]
    idx = labels[..., None, None].astype(jnp.int32)
    idx = jnp.clip(idx, 0, per_class.shape[-2] - 1)
    return jnp.take_along_axis(per_class, jnp.broadcast_to(idx, shape[:-2] + (1, 4)), axis=-2)[
        ..., 0, :
    ]
