"""Every `ops/pallas/` kernel, and the stem's two of `ops/pool_ops.py`, must
lower for the TPU at the shapes the `voc_resnet18` step and the 600x600 serve
program use; the sequence model's attention and grouped product
(`ops/attention.py`, `ops/grouped_mm.py`) at the shapes of `trinity_mini_ep8`.

The Pallas -> Mosaic lowering is Python and runs on any host
(``lowering_platforms=("tpu",)``), so whatever it refuses — an
unimplemented primitive, a misaligned block, a float iota — is caught
here on the CPU and never costs chip time again. What it cannot say is
whether libtpu's Mosaic compiler then takes the kernel: that verdict
comes from `benchmarks/pallas_on_chip.py` on a chip.
"""

import jax
import jax.numpy as jnp
import pytest

from replication_faster_rcnn_tpu.ops import attention as attention_ops
from replication_faster_rcnn_tpu.ops import grouped_mm, pool_ops
from replication_faster_rcnn_tpu.ops.pallas import (
    dequantize_pallas,
    iou_matrix_pallas,
    match_boxes_pallas,
    nms_fixed_pallas,
    quant_matmul_pallas,
    roi_align_pallas,
)

S = jax.ShapeDtypeStruct
F32, I8, BOOL = jnp.float32, jnp.int8, jnp.bool_
B = 16  # the preset's per-chip batch: the step vmaps the per-image ops


def _nms(max_out, masked):
    def f(b, s, *m):
        return nms_fixed_pallas(
            b, s, 0.7, max_out, mask=m[0] if masked else None,
            assume_sorted=not masked, interpret=False,
        )

    return f


def _match(a, g, m):
    return match_boxes_pallas(a, g, m, interpret=False)


def _iou(a, g, m):
    return iou_matrix_pallas(a, g, m, interpret=False)


def _roi(f, r):
    return roi_align_pallas(f, r, 7, 2, 1 / 16.0, interpret=False)


def _stem(train):
    """conv1's map at 600x600 through the stem's norm + ReLU + max-pool:
    the forward alone (serving), and with its hand-written backward."""
    pool = lambda y, *terms: pool_ops.norm_relu_max_pool(y, *terms, jnp.bfloat16)
    if not train:
        return pool
    return jax.grad(lambda *a: jnp.sum(pool(*a).astype(F32)), argnums=(0, 1, 2, 3))


def _stem_args(batch, per_sample=False):
    term = S((batch if per_sample else 1, 1, 1, 64), F32)
    return (S((batch, 300, 300, 64), jnp.bfloat16), term, term, term)


def _attention(window):
    """Two rows of 8,192 tokens, 32 query heads on 4 KV heads of 128, forward
    and the hand-written backward's two kernels."""
    loss = lambda q, k, v: jnp.sum(attention_ops.attention(q, k, v, window).astype(F32))
    return jax.grad(loss, argnums=(0, 1, 2))


_ATTENTION_ARGS = (S((2, 8192, 32, 128), jnp.bfloat16),) + (S((2, 8192, 4, 128), jnp.bfloat16),) * 2


def _experts(rows, weights, sizes):
    """The held experts' first product over the usual buffer of 32,768 rows."""
    loss = lambda r, w: jnp.sum(grouped_mm.grouped_matmul(r, w, sizes).astype(F32))
    return jax.grad(loss, argnums=(0, 1))(rows, weights)


CASES = {
    "attention_windowed_train": (_attention(2048), _ATTENTION_ARGS),
    "attention_full_train": (_attention(None), _ATTENTION_ARGS),
    "grouped_matmul_train": (
        _experts, (S((32768, 2048), jnp.bfloat16), S((16, 2048, 1024), F32), S((16,), jnp.int32))
    ),
    "stem_pool_train": (_stem(True), _stem_args(32)),
    "stem_pool_train_group_norm": (_stem(True), _stem_args(B, per_sample=True)),
    "stem_pool_serve": (_stem(False), _stem_args(8)),
    # proposal NMS, train (12000 -> 600, sorted) alone and under the vmap
    "nms_train": (_nms(600, False), (S((12000, 4), F32), S((12000,), F32))),
    "nms_train_vmap": (
        jax.vmap(_nms(600, False)),
        (S((B, 12000, 4), F32), S((B, 12000), F32)),
    ),
    # serve: proposals 3000 -> 300, then per-class detections (masked)
    "nms_serve": (_nms(300, False), (S((3000, 4), F32), S((3000,), F32))),
    "nms_detect": (
        _nms(100, True),
        (S((6300, 4), F32), S((6300,), F32), S((6300,), BOOL)),
    ),
    # RPN matching: 12,996 anchors (shared) x 32 gt slots
    "anchor_match": (
        _match, (S((12996, 4), F32), S((32, 4), F32), S((32,), BOOL))
    ),
    "anchor_match_vmap": (
        jax.vmap(_match, in_axes=(None, 0, 0)),
        (S((12996, 4), F32), S((B, 32, 4), F32), S((B, 32), BOOL)),
    ),
    # head matching: 600 proposals + 32 gt candidates
    "proposal_match": (
        _iou, (S((632, 4), F32), S((32, 4), F32), S((32,), BOOL))
    ),
    "proposal_match_vmap": (
        jax.vmap(_iou),
        (S((B, 632, 4), F32), S((B, 32, 4), F32), S((B, 32), BOOL)),
    ),
    # ROIAlign on the stride-16 map, 128 sampled rois, both compute dtypes
    "roi_align_f32": (_roi, (S((38, 38, 256), F32), S((128, 4), F32))),
    "roi_align_bf16": (
        _roi, (S((38, 38, 256), jnp.bfloat16), S((128, 4), F32))
    ),
    "roi_align_vmap": (
        jax.vmap(_roi), (S((B, 38, 38, 256), F32), S((B, 128, 4), F32))
    ),
    # the int8 head GEMM and the weight dequantize at the VGG head's shape
    "int8_matmul": (
        lambda x, w: quant_matmul_pallas(x, w, interpret=False),
        (S((128, 25088), I8), S((25088, 4096), I8)),
    ),
    "dequantize": (
        lambda w, s: dequantize_pallas(w, s, interpret=False),
        (S((25088, 4096), I8), S((4096,), F32)),
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_lowers_for_tpu_at_step_shapes(name, monkeypatch):
    # the stem's kernels ask the backend, which is the CPU here
    monkeypatch.setattr(pool_ops, "_interpret", lambda: False)
    monkeypatch.setattr(attention_ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(grouped_mm, "interpret_mode", lambda: False)
    fn, args = CASES[name]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    # a Mosaic kernel, not an interpreted loop nest
    assert "tpu_custom_call" in lowered.as_text()
