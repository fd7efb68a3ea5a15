"""ROI feature extraction — TPU-native replacements for
``torchvision.ops.roi_pool`` / ``roi_align`` (reference `nets/heads.py:48`;
SURVEY.md §2.3).

Both ops are fixed-shape and differentiable w.r.t. the feature map, so the
detection-head gradient flows into the backbone exactly as it does through
torchvision's C++ kernels in the reference.

* :func:`roi_align` — bilinear sampling on a fixed ``sampling_ratio^2`` grid
  per output bin, averaged (torchvision ROIAlign, aligned=False semantics).
  Two implementations with identical numerics:
    - ``method="einsum"`` (default): bilinear interpolation is separable,
      so sampling IS a pair of batched matmuls — per-roi tent-weight
      matrices ``WR [R, P, H]`` / ``WC [R, Q, W]`` contract the feature map
      on the MXU. No gathers touch HBM: the TPU-native formulation.
    - ``method="gather"``: 4-corner gathers + weighted sum (the direct
      translation of the sampling definition); kept as the oracle and for
      very large feature maps where the dense weight matrices would not pay.
* :func:`roi_pool` — legacy quantized max pooling (round coords, +1 extents,
  floor/ceil bin edges, empty bins -> 0), matching the Caffe/torchvision
  ROIPool the reference uses. One implementation, three steps:
    1. range-max tables, once per image and not per ROI: for every bin width
       n the shapes allow, ``T_n[w] = max(feat[:, w : w + n])``, each one
       shifted maximum of the one before;
    2. a bin ``[ws, we)`` is the entry ``T_{we-ws}[ws]``, and picking the
       ``out`` entries of every ROI of an image is one dense matmul with a
       one-hot matrix (``[R*out, n*W] @ [n*W, H*C]``, exact: ``1.0 * x``
       accumulated in float32 is ``x``) — the same "dense matmul beats
       random HBM access" as ``roi_align``'s einsum;
    3. one masked max over the map's rows.
  The backward is written by hand (``jax.custom_vjp``) and walks the same
  steps in reverse; the selection's transpose is again one matmul an image,
  which sums the ROIs' cotangents on the MXU. No tie counts are formed: a
  bin's cotangent goes whole to its first maximal element in row-major
  order, as in Caffe's and torchvision's kernels.

Features are NHWC ([H, W, C] per image here; callers vmap over the batch).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Array = jnp.ndarray


def _bilinear_gather(feat: Array, r: Array, c: Array) -> Array:
    """Bilinear-interpolate feat [H, W, C] at continuous (r, c) points.

    r, c: [...] coordinates in pixel units (centers at integers). Points
    outside [-1, H] x [-1, W] contribute zero (torchvision border rule);
    in-range points clamp to the valid gather window.
    """
    h, w = feat.shape[0], feat.shape[1]
    in_range = (r >= -1.0) & (r <= h) & (c >= -1.0) & (c <= w)
    r = jnp.clip(r, 0.0, h - 1.0)
    c = jnp.clip(c, 0.0, w - 1.0)
    r0 = jnp.floor(r)
    c0 = jnp.floor(c)
    r0i = r0.astype(jnp.int32)
    c0i = c0.astype(jnp.int32)
    r1i = jnp.minimum(r0i + 1, h - 1)
    c1i = jnp.minimum(c0i + 1, w - 1)
    ar = r - r0
    ac = c - c0
    w00 = (1 - ar) * (1 - ac)
    w01 = (1 - ar) * ac
    w10 = ar * (1 - ac)
    w11 = ar * ac
    gathered = (
        feat[r0i, c0i] * w00[..., None]
        + feat[r0i, c1i] * w01[..., None]
        + feat[r1i, c0i] * w10[..., None]
        + feat[r1i, c1i] * w11[..., None]
    )
    return gathered * in_range[..., None]


def _sample_grid(rois: Array, out_size: int, s: int, dtype) -> tuple:
    """Continuous sample coordinates per roi: (rr [R, out*s], cc [R, out*s])."""
    r1, c1, r2, c2 = rois[:, 0], rois[:, 1], rois[:, 2], rois[:, 3]
    # aligned=False semantics: roi extent clamps to a 1px minimum.
    roi_h = jnp.maximum(r2 - r1, 1.0)
    roi_w = jnp.maximum(c2 - c1, 1.0)
    bin_h = roi_h / out_size  # [R]
    bin_w = roi_w / out_size
    # Sample offsets within a roi, in bin units: (p + (i + .5)/s) for output
    # bin p and sample i — shape [out*s].
    pts = (jnp.arange(out_size * s, dtype=dtype) + 0.5) / s
    rr = r1[:, None] + pts[None, :] * bin_h[:, None]  # [R, out*s]
    cc = c1[:, None] + pts[None, :] * bin_w[:, None]
    return rr, cc


def _tent_weights(coords: Array, extent: int) -> Array:
    """Per-point bilinear weight rows: coords [R, P] -> [R, P, extent].

    Row p holds the two-tap interpolation weights of sample p against the
    integer grid 0..extent-1 (a tent max(0, 1-|x-i|) after the gather
    path's clamping), zeroed for points outside [-1, extent] (torchvision
    border rule). Matches `_bilinear_gather` exactly: clamping to
    [0, extent-1] collapses the tent to weight 1 at the border tap.
    """
    in_range = (coords >= -1.0) & (coords <= extent)
    x = jnp.clip(coords, 0.0, extent - 1.0)
    grid = jnp.arange(extent, dtype=coords.dtype)
    w = jnp.maximum(0.0, 1.0 - jnp.abs(x[..., None] - grid))  # [R, P, extent]
    return w * in_range[..., None]


@partial(jax.jit, static_argnames=("out_size", "sampling_ratio", "method"))
def roi_align(
    feat: Array,
    rois: Array,
    out_size: int = 7,
    sampling_ratio: int = 2,
    spatial_scale: float = 1.0,
    method: str = "einsum",
) -> Array:
    """ROIAlign: feat [H, W, C], rois [R, 4] -> [R, out, out, C].

    Rois are in feature-map coordinates after multiplying by
    ``spatial_scale`` (the reference pre-scales rois itself and passes
    spatial_scale=1, `nets/heads.py:42-48`).

    ``method="einsum"``: bilinear sampling is separable, so the whole op is
    sampled[r,p,q,:] = WR[r,p,:] @ feat @ WC[r,q,:]^T — two batched
    matmuls on the MXU, no gathers (each weight row has <= 2 nonzeros, but
    dense-matmul beats random HBM access on TPU for detection-sized maps).
    ``method="gather"``: the direct 4-corner gather implementation.
    ``method="pallas"``: the fused `ops/pallas/roi_kernel.py` forward
    (same einsum formulation inside one kernel; tolerance-gated parity —
    see tests/test_pallas_roi.py), einsum VJP for the backward.
    """
    if method == "pallas":
        from replication_faster_rcnn_tpu import ops as ops_pkg
        from replication_faster_rcnn_tpu.ops.pallas import roi_align_pallas

        # the kernel wrapper applies spatial_scale itself — delegate before
        # the shared pre-scaling below
        return roi_align_pallas(
            feat, rois, out_size, sampling_ratio, spatial_scale,
            interpret=ops_pkg.interpret_mode(),
        )
    rois = rois * spatial_scale
    s = sampling_ratio
    rr, cc = _sample_grid(rois, out_size, s, feat.dtype)

    if method == "einsum":
        h, w = feat.shape[0], feat.shape[1]
        wr = _tent_weights(rr, h)  # [R, P, H]
        wc = _tent_weights(cc, w)  # [R, Q, W]
        # [R, P, H] x [H, W, C] -> [R, P, W, C]; then contract W with WC.
        rows = jnp.einsum("rph,hwc->rpwc", wr, feat)
        sampled = jnp.einsum("rpwc,rqw->rpqc", rows, wc)
    elif method == "gather":
        rg = rr[:, :, None] * jnp.ones_like(cc)[:, None, :]  # [R, out*s, out*s]
        cg = cc[:, None, :] * jnp.ones_like(rr)[:, :, None]
        sampled = _bilinear_gather(feat, rg, cg)  # [R, out*s, out*s, C]
    else:
        raise ValueError(f"unknown roi_align method {method!r}")

    r_, c_ = sampled.shape[0], sampled.shape[-1]
    sampled = sampled.reshape(r_, out_size, s, out_size, s, c_)
    return sampled.mean(axis=(2, 4))


def _pool_edges(rois: Array, out_size: int, h: int, w: int) -> tuple:
    """Integer bin edges (hs, he, ws, we), each [R, out]: Caffe's quantisation
    (rounded corners, +1 extents, floor/ceil of the fractional bin size,
    clamped to the map). A bin is empty where its end is not past its start."""
    r1, c1, r2, c2 = (jnp.round(rois[:, i]) for i in range(4))
    bin_h = jnp.maximum(r2 - r1 + 1.0, 1.0) / out_size  # [R]
    bin_w = jnp.maximum(c2 - c1 + 1.0, 1.0) / out_size
    p = jnp.arange(out_size, dtype=rois.dtype)

    def edges(first, size, extent):
        start = jnp.clip(jnp.floor(p[None, :] * size[:, None]) + first[:, None], 0, extent)
        end = jnp.clip(jnp.ceil((p[None, :] + 1) * size[:, None]) + first[:, None], 0, extent)
        return start.astype(jnp.int32), end.astype(jnp.int32)

    return edges(r1, bin_h, h) + edges(c1, bin_w, w)


def _widest_bin(extent: int, out_size: int) -> int:
    """Columns in the widest bin of a ROI no wider than the map's frame: a
    rounded extent of at most extent + 2 (a trunk that floors its map gives
    that: 600 / 16 rounds to 38 on 37 columns), wherever it lies. A bin spans
    under its fractional size + 1 columns, one more where the division rounds
    up, so ceil((extent + 2) / out) + 1 (7 on a 38-wide map). From the static
    shapes alone, never from a setting."""
    return min(extent, -(-(extent + 2) // out_size) + 1)


def _shift_left(x: Array, s: int) -> Array:
    """x[w + s] along axis 0; the last s entries repeat the final one. They
    stand for windows past the map's edge, which no bin selects, but must be
    finite: the selection multiplies them by zero."""
    return jnp.concatenate([x[s:], jnp.broadcast_to(x[-1:], (s,) + x.shape[1:])])


def _window_max_tables(feat: Array, widest: int) -> list:
    """feat [H, W, C] -> for each width n = 1..widest the maxima of the n
    columns from w on, as [W, H, C]: the table before, and one more column
    of the map shifted under it."""
    base = jnp.swapaxes(feat, 0, 1)
    tabs = [base]
    for n in range(2, widest + 1):
        tabs.append(jnp.maximum(tabs[-1], _shift_left(base, n - 1)))
    return tabs


def _bin_selector(ws: Array, we: Array, widest: int, w: int, dtype) -> Array:
    """One-hot rows [R, out, widest*W] that pick bin [ws, we)'s table entry:
    width we - ws, column ws. An empty bin's row is all zero."""
    width = we - ws
    entry = jnp.where(width > 0, (jnp.minimum(width, widest) - 1) * w + ws, -1)
    return jax.nn.one_hot(entry, widest * w, dtype=dtype)


def _dot_precision(dtype):
    # a selection must return its operand: bfloat16 products accumulate in
    # float32 as they are; float32 operands need the MXU's six passes
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _empty_bins(hs: Array, he: Array, ws: Array, we: Array) -> Array:
    """[R, out_i, out_j, 1]: the bins with no row or no column."""
    return ((he <= hs)[:, :, None] | (we <= ws)[:, None, :])[..., None]


def _pool_forward(feat: Array, rois: Array, out_size: int, with_rows: bool):
    """pooled [R, out, out, C]; with_rows also gives, per bin and channel,
    the first map row that holds the maximum (what the backward needs)."""
    h, w = feat.shape[0], feat.shape[1]
    hs, he, ws, we = _pool_edges(rois, out_size, h, w)
    widest = _widest_bin(w, out_size)
    table = jnp.concatenate(_window_max_tables(feat, widest))  # [widest*W, H, C]
    onehot = _bin_selector(ws, we, widest, w, feat.dtype)
    # the table is shared by the image's ROIs: [R*out, widest*W] @ [widest*W, H*C]
    col_pooled = jnp.einsum("rjk,khc->rjhc", onehot, table, precision=_dot_precision(feat.dtype))

    rows = jnp.arange(h, dtype=jnp.int32)
    in_bin = (rows >= hs[:, :, None]) & (rows < he[:, :, None])  # [R, out_i, H]
    neg = jnp.asarray(-jnp.inf, feat.dtype)
    masked = jnp.where(in_bin[:, :, None, :, None], col_pooled[:, None], neg)  # [R, i, j, H, C]
    # empty bins are 0 by their integer edges: no -inf has to reach a test
    empty = _empty_bins(hs, he, ws, we)
    zero = jnp.zeros((), feat.dtype)
    if not with_rows:
        # not the pair-reduce below with its rows dropped: the compiler keeps
        # the dead half, and evaluation and serving would pay a fifth more
        # (5.15 against 4.23 ms at b32 x 128 on the v5e, PERF.md section 6)
        return jnp.where(empty, zero, jnp.max(masked, axis=3))

    def first_max(a, b):
        (va, ra), (vb, rb) = a, b
        keep = (va > vb) | ((va == vb) & (ra < rb))
        return jnp.where(keep, va, vb), jnp.where(keep, ra, rb)

    pooled, first_row = jax.lax.reduce(
        (masked, jnp.broadcast_to(rows[:, None], masked.shape)), (neg, jnp.int32(h)), first_max, (3,)
    )
    return jnp.where(empty, zero, pooled), first_row


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _roi_pool(feat: Array, rois: Array, out_size: int) -> Array:
    return _pool_forward(feat, rois, out_size, False)


def _roi_pool_fwd(feat, rois, out_size):
    pooled, first_row = _pool_forward(feat, rois, out_size, True)
    return pooled, (feat, rois, first_row)


def _roi_pool_bwd(out_size, res, g):
    """The forward's three steps in reverse. Each bin's cotangent goes whole
    to one element, its first maximum in row-major order: the first row that
    holds it, then the leftmost column of that row."""
    feat, rois, first_row = res
    h, w = feat.shape[0], feat.shape[1]
    hs, he, ws, we = _pool_edges(rois, out_size, h, w)
    widest = _widest_bin(w, out_size)
    # rows: an empty bin sends nothing; bins that share a row (fractional
    # bin heights overlap) add up there
    g = jnp.where(_empty_bins(hs, he, ws, we), jnp.zeros((), g.dtype), g)
    rows = jnp.arange(h, dtype=jnp.int32)
    hit = first_row[:, :, :, None, :] == rows[:, None]  # [R, i, j, H, C]
    g_col = jnp.sum(
        jnp.where(hit, g[:, :, :, None, :], jnp.zeros((), g.dtype)), axis=1, dtype=jnp.float32
    ).astype(feat.dtype)  # [R, j, H, C]
    # the selection's transpose: the ROIs' sum, on the MXU, in float32
    onehot = _bin_selector(ws, we, widest, w, feat.dtype)
    d_table = jnp.einsum(
        "rjk,rjhc->khc", onehot, g_col,
        precision=_dot_precision(feat.dtype), preferred_element_type=jnp.float32,
    ).reshape((widest, w) + g_col.shape[2:])  # [widest, W, H, C]
    # the tables, widest first: a window's cotangent stays with the narrower
    # window on its left unless its last column is strictly larger
    tabs = _window_max_tables(feat, widest)
    d_base = d_table[0]
    d = jnp.zeros_like(d_base)
    for n in range(widest, 1, -1):
        d = d + d_table[n - 1]
        to_left = tabs[n - 2] >= _shift_left(tabs[0], n - 1)
        d_base = d_base + jnp.pad(jnp.where(to_left, 0.0, d), ((n - 1, 0), (0, 0), (0, 0)))[:w]
        d = jnp.where(to_left, d, 0.0)
    return jnp.swapaxes(d_base + d, 0, 1).astype(feat.dtype), jnp.zeros_like(rois)


_roi_pool.defvjp(_roi_pool_fwd, _roi_pool_bwd)


@partial(jax.jit, static_argnames=("out_size",))
def roi_pool(
    feat: Array,
    rois: Array,
    out_size: int = 7,
    spatial_scale: float = 1.0,
) -> Array:
    """Legacy ROIPool: feat [H, W, C], rois [R, 4] -> [R, out, out, C].

    Quantization follows the Caffe/torchvision kernel: scaled coords are
    rounded; roi extent gets +1; bin edges are floor/ceil of the fractional
    bin size; bins clamp to the map; empty bins output 0.

    Gradient: a bin's cotangent goes whole to one maximal element of the
    bin, the first in row-major order (Caffe's and torchvision's argmax, which
    the reference repository runs); nothing is split among ties.

    Domain: ROIs no wider than the map's frame, a rounded extent of at most
    W + 2, as proposals and ground-truth boxes clipped to the image are
    whether the trunk rounds its map up or down (where they lie, inside,
    across the border or outside, is free, and so is their height). The
    tables hold every bin width such a ROI can have (`_widest_bin`). A wider
    ROI is outside the domain: those of its bins that are wider than the
    widest table are pooled over their first `_widest_bin` columns only
    (tests/test_roi_ops.py pins both sides of that boundary).

    The selection is a matmul, so a non-finite feature value spreads along
    its map row instead of staying in its bins;
    `train/fault.py::guarded_update` skips such a step either way.
    """
    return _roi_pool(feat, rois * spatial_scale, out_size)


def extract_roi_features(
    feat: Array,
    rois: Array,
    op: str = "align",
    out_size: int = 7,
    sampling_ratio: int = 2,
    spatial_scale: float = 1.0,
) -> Array:
    """Dispatch between ROIAlign and ROIPool by config string.

    ROIAlign additionally honors the `ops.backend` axis: backend=pallas
    routes to the fused kernel forward (XLA einsum VJP for the backward),
    backend=xla (default) keeps the einsum formulation byte-identical to
    the committed fingerprints.
    """
    if op == "align":
        from replication_faster_rcnn_tpu import ops as ops_pkg

        method = "pallas" if ops_pkg.want_pallas("roi_align") else "einsum"
        return roi_align(
            feat, rois, out_size, sampling_ratio, spatial_scale, method=method
        )
    if op == "pool":
        return roi_pool(feat, rois, out_size, spatial_scale)
    raise ValueError(f"unknown roi op {op!r}")
