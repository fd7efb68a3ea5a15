"""Faster R-CNN assembly — trunk + RPN + detection head as one flax module.

Capability parity with reference `nets/faster_rcnn.py:7-34` (``FasterRCNN``)
— and a working version of its combined forward, which in the reference is
broken (calls the head without its required img_h/img_w args,
`nets/faster_rcnn.py:31` vs `nets/heads.py:27`; SURVEY.md §3.2).

The trainer needs to run target assignment between the RPN and the head
(reference `train.py:63-110` bypasses the combined forward for exactly this
reason). Rather than bypassing the module, the stages are exposed as flax
methods — ``extract_features`` / ``rpn_forward`` / ``head_forward`` — which
`apply(..., method=...)` can call separately inside the one jitted train
step; ``__call__`` composes them for inference.

Anchors are a compile-time constant: the feature map shape is static under
jit, so the full [H*W*K, 4] grid is baked into the XLA program instead of
being regenerated from numpy on every forward (reference `nets/rpn.py:126-127`,
a host-device boundary in the reference's hot loop).
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from replication_faster_rcnn_tpu.config import FasterRCNNConfig
from replication_faster_rcnn_tpu.models.head import DetectionHead
from replication_faster_rcnn_tpu.models.resnet import ResNetTrunk
from replication_faster_rcnn_tpu.models.rpn import RPNHead, batched_proposals
from replication_faster_rcnn_tpu.ops import anchors as anchor_ops
from replication_faster_rcnn_tpu.telemetry import stages

Array = jnp.ndarray


class FasterRCNN(nn.Module):
    """The full two-stage detector.

    Submodule layout (names matter for checkpoint conversion):
      trunk — ResNetTrunk (conv1..layer3)
      rpn   — RPNHead
      head  — DetectionHead (contains the layer4 tail)
    """

    config: FasterRCNNConfig

    def setup(self) -> None:
        cfg = self.config
        dtype = jnp.dtype(cfg.model.compute_dtype)
        if cfg.model.fpn:
            from replication_faster_rcnn_tpu.models.fpn import FPNNeck, ResNetFeatures
            from replication_faster_rcnn_tpu.models.head import FPNDetectionHead

            self.trunk = ResNetFeatures(
                cfg.model.backbone, dtype, bn_axis=cfg.model.bn_axis,
                remat=cfg.model.remat, frozen_bn=cfg.model.frozen_bn,
                norm=cfg.model.norm,
            )
            self.neck = FPNNeck(cfg.model.fpn_channels, dtype)
            self.rpn = RPNHead(
                num_anchors=cfg.anchors.num_base_anchors,
                mid_channels=cfg.model.fpn_channels,
                dtype=dtype,
            )
            self.head = FPNDetectionHead(
                num_classes=cfg.model.num_classes,
                roi_size=cfg.model.roi_size,
                sampling_ratio=cfg.model.roi_sampling_ratio,
                dtype=dtype,
            )
        else:
            if cfg.model.backbone == "vgg16":
                from replication_faster_rcnn_tpu.models.vgg import VGG16Trunk

                self.trunk = VGG16Trunk(dtype, remat=cfg.model.remat)
            else:
                self.trunk = ResNetTrunk(
                    cfg.model.backbone, dtype, bn_axis=cfg.model.bn_axis,
                    remat=cfg.model.remat, frozen_bn=cfg.model.frozen_bn,
                    norm=cfg.model.norm,
                )
            # the head dispatches internally on arch (VGG16 fc6/fc7 tail
            # vs ResNet layer4 tail)
            self.rpn = RPNHead(
                num_anchors=cfg.anchors.num_base_anchors,
                mid_channels=cfg.model.rpn_mid_channels,
                dtype=dtype,
            )
            self.head = DetectionHead(
                arch=cfg.model.backbone,
                num_classes=cfg.model.num_classes,
                roi_size=cfg.model.roi_size,
                roi_op=cfg.model.roi_op,
                sampling_ratio=cfg.model.roi_sampling_ratio,
                dtype=dtype,
                bn_axis=cfg.model.bn_axis,
                frozen_bn=cfg.model.frozen_bn,
                norm=cfg.model.norm,
            )

    # --- stage methods (used individually by the trainer) ---

    def preprocess(self, images: Array) -> Array:
        """uint8 NHWC -> normalized float32, on device.

        With ``data.device_normalize`` the host ships raw bytes (a quarter
        of the f32 host-to-device volume) and this affine runs on-chip,
        where XLA fuses it into the first conv's input. float32 input
        passes through untouched (the host already normalized it)."""
        if images.dtype == jnp.uint8:
            mean = jnp.asarray(self.config.data.pixel_mean, jnp.float32)
            std = jnp.asarray(self.config.data.pixel_std, jnp.float32)
            images = (images.astype(jnp.float32) / 255.0 - mean) / std
        return images

    def extract_features(self, images: Array, train: bool = False):
        """images NHWC [N, H, W, 3] -> shared features.

        Single-scale: one [N, H/16, W/16, C] map. FPN: list [P2..P6]."""
        with jax.named_scope(stages.INPUT):
            images = self.preprocess(images)
        if self.config.model.fpn:
            return self.neck(self.trunk(images, train))
        return self.trunk(images, train)

    def rpn_forward(self, feat) -> Tuple[Array, Array, Array]:
        """features -> (logits [N, A, 2], deltas [N, A, 4], anchors [A, 4]).

        FPN: the SAME RPN head runs on every level (FPN paper: shared
        heads); per-level outputs and anchors concatenate fine->coarse, so
        downstream proposal/target code is level-agnostic.
        """
        if self.config.model.fpn:
            from replication_faster_rcnn_tpu.models.fpn import FPN_STRIDES

            logits_l, deltas_l, anchors_l = [], [], []
            for level, stride in zip(feat, FPN_STRIDES):
                lg, dl = self.rpn(level)
                logits_l.append(lg)
                deltas_l.append(dl)
                base = anchor_ops.anchor_base(
                    stride, self.config.anchors.ratios, self.config.anchors.scales
                )
                anchors_l.append(
                    anchor_ops.grid_anchors(
                        base, stride, level.shape[1], level.shape[2]
                    )
                )
            import numpy as np

            return (
                jnp.concatenate(logits_l, axis=1),
                jnp.concatenate(deltas_l, axis=1),
                jnp.asarray(
                    np.concatenate(anchors_l, axis=0), dtype=jnp.float32
                ),
            )
        logits, deltas = self.rpn(feat)
        anchors = jnp.asarray(
            anchor_ops.make_anchors(
                self.config.anchors, (feat.shape[1], feat.shape[2])
            ),
            dtype=jnp.float32,
        )
        return logits, deltas, anchors

    def propose(
        self,
        logits: Array,
        deltas: Array,
        anchors: Array,
        img_h: float,
        img_w: float,
        train: bool,
    ) -> Tuple[Array, Array]:
        """(rois [N, post_nms, 4], valid [N, post_nms]) — fixed shape."""
        return batched_proposals(
            anchors, logits, deltas, img_h, img_w, self.config.proposals, train
        )

    def head_forward(
        self,
        feat,
        rois: Array,
        img_h: float,
        img_w: float,
        train: bool = False,
    ) -> Tuple[Array, Array]:
        """(cls [N, R, num_classes], reg [N, R, num_classes*4])."""
        return self.head(feat, rois, img_h, img_w, train)

    # --- combined forward (inference path) ---

    def __call__(
        self, images: Array, train: bool = False
    ) -> Tuple[Array, Array, Array, Array, Array, Array, Array]:
        """Full forward (reference `nets/faster_rcnn.py:24-34`, fixed).

        Returns (rpn_logits, rpn_deltas, rois, roi_valid, cls, reg, anchors).
        """
        img_h, img_w = float(images.shape[1]), float(images.shape[2])
        feat = self.extract_features(images, train)
        logits, deltas, anchors = self.rpn_forward(feat)
        rois, valid = self.propose(logits, deltas, anchors, img_h, img_w, train)
        cls, reg = self.head_forward(feat, rois, img_h, img_w, train)
        return logits, deltas, rois, valid, cls, reg, anchors


def create(config: FasterRCNNConfig) -> FasterRCNN:
    return FasterRCNN(config)


def init_variables(config: FasterRCNNConfig, rng: Any, batch_size: int = 1):
    """Initialize parameters/batch stats with a dummy batch."""
    model = FasterRCNN(config)
    h, w = config.data.image_size
    dummy = jnp.zeros((batch_size, h, w, 3), jnp.float32)
    return model, model.init({"params": rng}, dummy, train=False)
