"""Region Proposal Network — flax head + fixed-shape proposal selection.

Capability parity with reference `nets/rpn.py:82-138` (RPN module) and
`nets/rpn.py:20-79` (`region_proposal` layer), redesigned for XLA:

  * The head is a 3x3 conv + ReLU and two 1x1 convs (cls: K*2 channels,
    reg: K*4 channels), all gaussian-init sigma 0.01 (reference
    `nets/rpn.py:93-100`). NHWC; outputs are reshaped to [N, H*W*K, .]
    position-major, matching the anchor grid ordering in
    `ops/anchors.grid_anchors`.
  * Proposal selection — decode, clip, min-size filter, top-pre_nms by
    score, NMS, keep post_nms (reference `nets/rpn.py:47-78`) — is a pure
    fixed-shape function vmapped over the batch instead of a per-image
    Python loop (`nets/rpn.py:131-136`). The reference's data-dependent
    output length (SURVEY.md §2.1 #10) becomes a padded [post_nms] roi
    array plus a validity mask.
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from replication_faster_rcnn_tpu.config import ProposalConfig
from replication_faster_rcnn_tpu.ops import boxes as box_ops

Array = jnp.ndarray


def _gaussian_conv(
    features: int, kernel: int, padding: int, dtype: Any, name: str
) -> nn.Conv:
    """Conv with N(0, 0.01) weight init and zero bias (reference
    `nets/rpn.py:11-17` ``normal_init`` with stddev=0.01, truncated=False)."""
    return nn.Conv(
        features=features,
        kernel_size=(kernel, kernel),
        strides=(1, 1),
        padding=((padding, padding), (padding, padding)),
        kernel_init=nn.initializers.normal(stddev=0.01),
        bias_init=nn.initializers.zeros,
        dtype=dtype,
        param_dtype=jnp.float32,
        name=name,
    )


class RPNHead(nn.Module):
    """Conv heads producing per-anchor objectness logits and box deltas.

    Input: trunk features NHWC [N, H, W, C].
    Output: (logits [N, H*W*K, 2], deltas [N, H*W*K, 4]) in float32,
    position-major to align with the [H*W*K, 4] anchor grid.
    """

    num_anchors: int  # K
    mid_channels: int = 256
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, feat: Array) -> Tuple[Array, Array]:
        n = feat.shape[0]
        x = _gaussian_conv(self.mid_channels, 3, 1, self.dtype, "conv1")(feat)
        x = nn.relu(x)
        logits = _gaussian_conv(self.num_anchors * 2, 1, 0, self.dtype, "cls")(x)
        deltas = _gaussian_conv(self.num_anchors * 4, 1, 0, self.dtype, "reg")(x)
        # [N, H, W, K*d] -> [N, H*W*K, d]: position-major flatten matches
        # the reference's permute(0,2,3,1).view(N,-1,d) (`nets/rpn.py:117-124`)
        # and ops.anchors' flat index = (r*W + c)*K + k.
        logits = logits.reshape(n, -1, 2).astype(jnp.float32)
        deltas = deltas.reshape(n, -1, 4).astype(jnp.float32)
        return logits, deltas


def select_proposals(
    anchors: Array,
    fg_scores: Array,
    deltas: Array,
    img_h: float,
    img_w: float,
    cfg: ProposalConfig,
    train: bool,
) -> Tuple[Array, Array]:
    """Per-image proposal selection (reference `nets/rpn.py:47-78`), fixed-shape.

    Args:
      anchors: [A, 4]; fg_scores: [A] foreground softmax scores;
      deltas: [A, 4] predicted regression.
    Returns:
      (rois [post_nms, 4], valid [post_nms] bool). Invalid slots are zeros.
    """
    pre_nms = min(cfg.pre_nms(train), anchors.shape[0])
    post_nms = cfg.post_nms(train)

    props = box_ops.decode(anchors, deltas)
    props = box_ops.clip(props, img_h, img_w)

    # min-size filter as a mask (reference `nets/rpn.py:65-68` drops rows)
    hs = props[:, 2] - props[:, 0]
    ws = props[:, 3] - props[:, 1]
    keep = (hs >= cfg.min_size) & (ws >= cfg.min_size)
    scores = jnp.where(keep, fg_scores, -jnp.inf)

    # top-pre_nms by score (reference sorts then truncates, `nets/rpn.py:70-72`).
    # ONE stable sort carries the boxes along with the key: what comes out is
    # already the descending candidate list the NMS wants (assume_sorted
    # below), and no value is picked by index afterwards. The order is that
    # of a stable argsort(-scores) (ties and -inf rows: lowest original index
    # first, as lax.top_k breaks them), the scores come back by an exact
    # negation, so this is bit-identical to argsort + `scores[idx]`,
    # `props[idx]` (tests/oracles.py::select_proposals_gather). Those two
    # gathers cost 4.91 + 3.34 ms of the 91.0 ms step at 32 x 12,000 indices:
    # the chip serves an XLA gather at 9-13 ns an index (PERF.md, PR 28).
    neg, *columns = jax.lax.sort(
        (-scores, *(props[:, i] for i in range(4))), num_keys=1, is_stable=True
    )
    top_scores = -neg[:pre_nms]
    top_boxes = jnp.stack(columns, axis=-1)[:pre_nms]

    # tiled exact NMS by default; ops.backend=pallas (or FRCNN_NMS=pallas)
    # swaps in the bit-identical ops/pallas kernel, FRCNN_NMS=loop the
    # serial selection loop — see nms_fixed_auto
    from replication_faster_rcnn_tpu.ops.nms import nms_fixed_auto

    idx, valid = nms_fixed_auto(
        top_boxes,
        top_scores,
        cfg.nms_thresh,
        post_nms,
        mask=jnp.isfinite(top_scores),
        assume_sorted=True,
    )
    rois = top_boxes[idx] * valid[:, None]
    return rois, valid


def batched_proposals(
    anchors: Array,
    logits: Array,
    deltas: Array,
    img_h: float,
    img_w: float,
    cfg: ProposalConfig,
    train: bool,
) -> Tuple[Array, Array]:
    """Batch proposal selection: logits [N, A, 2], deltas [N, A, 4] ->
    (rois [N, post_nms, 4], valid [N, post_nms]).

    The foreground score is softmax(logits)[..., 1] (reference
    `nets/rpn.py:119-121`). rois carry no gradient — the reference detaches
    them before head sampling (`train.py:94`); here the stop_gradient makes
    that contract explicit at the source.
    """
    fg = jax.nn.softmax(logits, axis=-1)[..., 1]
    fg = jax.lax.stop_gradient(fg)
    deltas = jax.lax.stop_gradient(deltas)
    return jax.vmap(
        lambda s, d: select_proposals(anchors, s, d, img_h, img_w, cfg, train)
    )(fg, deltas)
