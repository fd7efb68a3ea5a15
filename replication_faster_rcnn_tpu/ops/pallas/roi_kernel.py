"""Pallas ROIAlign forward — bilinear sampling fused in VMEM.

One grid step per roi: the kernel builds the separable tent-weight matrices
(`ops/roi_ops.py::_tent_weights` semantics — torchvision aligned=False,
points outside [-1, extent] contribute zero, in-range points clamp to the
border tap) and contracts them against the VMEM-resident feature map on the
MXU, then bin-averages — the einsum formulation of `roi_ops.roi_align` with
the sampling, both contractions, and the pooling mean fused into one kernel
so no [R, P, W, C] intermediate ever touches HBM.

The forward is tolerance-gated against the gather oracle (not bit-identical:
contraction order differs from the XLA einsum schedule; tier-1 pins
atol=2e-5 / rtol=1e-5 in float32 — tests/test_pallas_roi.py). The backward
is a custom_vjp that replays the einsum formulation under `jax.vjp`, so
gradients are exactly the well-tested XLA path — Pallas only owns the
inference/forward hot loop.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray


def _tent_rows(coords: Array, extent: int) -> Array:
    """coords [P] -> [P, extent] bilinear tent weights (border rule of
    `roi_ops._tent_weights`)."""
    p = coords.shape[0]
    in_range = (coords >= -1.0) & (coords <= extent)
    x = jnp.clip(coords, 0.0, extent - 1.0)
    grid = jax.lax.broadcasted_iota(jnp.int32, (p, extent), 1).astype(
        jnp.float32
    )
    w = jnp.maximum(0.0, 1.0 - jnp.abs(x[:, None] - grid))
    return w * in_range[:, None]


def _roi_kernel(roi_ref, feat_ref, out_ref, *, out_size: int, s: int):
    h, w, c = feat_ref.shape
    p = out_size * s
    # rois ride in SMEM (scalar prefetch), flattened [R * 4]
    base = 4 * pl.program_id(0)
    r1 = roi_ref[base]
    c1 = roi_ref[base + 1]
    r2 = roi_ref[base + 2]
    c2 = roi_ref[base + 3]
    # aligned=False semantics: roi extent clamps to a 1px minimum
    bin_h = jnp.maximum(r2 - r1, 1.0) / out_size
    bin_w = jnp.maximum(c2 - c1, 1.0) / out_size
    pts = (
        jax.lax.broadcasted_iota(jnp.int32, (p, 1), 0)[:, 0].astype(jnp.float32)
        + 0.5
    ) / s
    rr = r1 + pts * bin_h  # [P]
    cc = c1 + pts * bin_w

    wr = _tent_rows(rr, h)  # [P, H]
    wc = _tent_rows(cc, w)  # [P, W]
    feat = feat_ref[...].astype(jnp.float32)

    # sampled[p, q, ch] = sum_{i,j} wr[p, i] * feat[i, j, ch] * wc[q, j]
    # full float32 contractions: at the MXU's default (bfloat16-pass)
    # precision the chip lands 2e-2 from the gather oracle
    rows = jnp.dot(
        wr,
        feat.reshape(h, w * c),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(p, w, c)
    sampled = jax.lax.dot_general(
        rows,
        wc,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [P, C, Q]
    sampled = sampled.transpose(0, 2, 1)  # [P, Q, C]
    pooled = sampled.reshape(out_size, s, out_size, s, c).mean(axis=(1, 3))
    out_ref[...] = pooled[None].astype(out_ref.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _roi_align_p(feat, rois, out_size, sampling_ratio, interpret):
    r = rois.shape[0]
    h, w, c = feat.shape
    return pl.pallas_call(
        partial(_roi_kernel, out_size=out_size, s=sampling_ratio),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r,),
            in_specs=[pl.BlockSpec((h, w, c), lambda i, rois: (0, 0, 0))],
            out_specs=pl.BlockSpec(
                (1, out_size, out_size, c), lambda i, rois: (i, 0, 0, 0)
            ),
        ),
        # float32 out whatever the feature dtype: the rois are float32, so
        # that is what the XLA formulations promote to — and what the
        # einsum VJP below expects its cotangent in
        out_shape=jax.ShapeDtypeStruct(
            (r, out_size, out_size, c), jnp.float32
        ),
        interpret=interpret,
    )(rois.astype(jnp.float32).reshape(-1), feat)


def _roi_align_p_fwd(feat, rois, out_size, sampling_ratio, interpret):
    return _roi_align_p(feat, rois, out_size, sampling_ratio, interpret), (
        feat,
        rois,
    )


def _roi_align_p_bwd(out_size, sampling_ratio, interpret, res, g):
    # backward = the einsum formulation's VJP: exactly the XLA path the
    # rest of training uses, so gradients carry no kernel-specific risk
    from replication_faster_rcnn_tpu.ops import roi_ops

    feat, rois = res
    _, vjp = jax.vjp(
        lambda f, r: roi_ops.roi_align(
            f, r, out_size, sampling_ratio, 1.0, method="einsum"
        ),
        feat,
        rois,
    )
    return vjp(g)


_roi_align_p.defvjp(_roi_align_p_fwd, _roi_align_p_bwd)


@partial(
    jax.jit, static_argnames=("out_size", "sampling_ratio", "interpret")
)
def _roi_align_pallas(feat, rois, out_size, sampling_ratio, spatial_scale, interpret):
    rois = rois * spatial_scale
    return _roi_align_p(feat, rois, out_size, sampling_ratio, interpret)


def roi_align_pallas(
    feat: Array,
    rois: Array,
    out_size: int = 7,
    sampling_ratio: int = 2,
    spatial_scale: float = 1.0,
    interpret: bool | None = None,
) -> Array:
    """Drop-in replacement for :func:`ops.roi_ops.roi_align`:
    feat [H, W, C], rois [R, 4] -> [R, out, out, C]."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _roi_align_pallas(
        feat, rois, out_size, sampling_ratio, spatial_scale, bool(interpret)
    )
