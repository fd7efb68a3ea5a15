"""Pallas tiled exact greedy NMS — bit-identical to `ops/nms_tiled.py`.

Same algorithm, same recurrence, same arithmetic: candidates are processed
in descending-score order one TILE per sequential grid step; within a tile
the greedy keep vector is solved by fixpoint sweeps of
``g = m0 & ~any(suppress & g[:, None], axis=0)``; selected boxes accumulate
into compact VMEM buffers that suppress later tiles in one matrix op. The
in-kernel IoU replicates `ops/boxes.py::iou` op-for-op
(maximum/minimum/subtract/multiply/where/divide in the same order), so every
comparison against ``iou_thresh`` sees bitwise the same float as the XLA
tiling and the selections are exactly identical — tier-1 pins this
(tests/test_pallas_nms.py).

The grid is static (``n_tiles`` steps) where the XLA tiling uses a
while_loop that exits once the buffer fills; a ``count < max_out`` predicate
skips the per-tile work instead, which appends nothing either way, so
results match exactly.

Written for the Mosaic compiler as it is installed: every value in the
kernel is 2-D — a row ``[1, N]`` (index on lanes) or a column ``[N, 1]``
(index on sublanes) — and a reduction keeps its dims, so nothing asks the
compiler to move a vector between the two layouts. The tile's boxes and
scores arrive in both layouts from the wrapper; the one in-kernel
conversion (the keep vector, row -> column, once per sweep) is a masked
lane reduction against the identity. Boolean and integer reductions go
through float32 (exact: counts and indices stay far below 2**24), and the
in-tile prefix count is a masked reduction against the triangle instead of
``cumsum``, which the TPU lowering does not implement.

Interpret mode (the default off-TPU) runs the same kernel as a pure JAX
interpretation on any backend.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray

_NEG = -jnp.inf


def _iou_grid(a, b, zero: Array) -> Array:
    """`ops/boxes.py::iou` on split coordinates: ``a`` is four columns
    ``[Na, 1]``, ``b`` four rows ``[1, Nb]`` (r1, c1, r2, c2 each) ->
    ``[Na, Nb]``. The elementwise op sequence is identical to the
    row-major original, so results are bitwise equal — with one subtlety:
    ``zero`` is a RUNTIME +0.0 scalar added to each product. The
    interpreter inlines the kernel jaxpr into the caller's XLA module,
    where LLVM codegen FMA-contracts a product into a following
    add/subtract in some fusion contexts (a 1-ulp drift off strict IEEE;
    HLO-level bitcast roundtrips are optimized away before codegen, so
    they can't pin it). Routing each product through ``+ zero`` is
    bit-exact on every codegen path: left alone it adds +0.0 (identity on
    the areas/intersection, which are never -0.0 here), and if contracted
    it becomes ``fma(x, y, 0)`` = ``round(x*y)`` — the strict product —
    while the remaining add/subtract chain has no multiply left to
    contract.

    Together with the producer `optimization_barrier` in the wrappers
    (which keeps pad/transpose producers from fusing into the kernel loop
    and re-triggering the contraction on the division), this makes the
    kernels strict-IEEE in every context tested — including ones where
    XLA:CPU's own compilation of `ops/boxes.py::iou` drifts 1 ulp from
    strict under heavy producer fusion (tests pin the kernels against a
    strict numpy oracle as well as the XLA reference)."""
    tl_r = jnp.maximum(a[0], b[0])
    tl_c = jnp.maximum(a[1], b[1])
    br_r = jnp.minimum(a[2], b[2])
    br_c = jnp.minimum(a[3], b[3])
    wh_r = br_r - tl_r
    wh_c = br_c - tl_c
    valid = (wh_r > 0) & (wh_c > 0)
    inter = jnp.where(valid, wh_r * wh_c, 0.0) + zero
    area_a = (a[2] - a[0]) * (a[3] - a[1]) + zero
    area_b = (b[2] - b[0]) * (b[3] - b[1]) + zero
    union = area_a + area_b - inter
    return jnp.where(union > 0, inter / jnp.where(union > 0, union, 1.0), 0.0)


def _cols(ref):
    """A ``[N, 4]`` box block as four ``[N, 1]`` columns."""
    return [ref[:, c : c + 1] for c in range(4)]


def _rows(ref):
    """A ``[4, N]`` box block as four ``[1, N]`` rows."""
    return [ref[c : c + 1, :] for c in range(4)]


def _count(mask: Array, axis: int) -> Array:
    """Number of set entries along ``axis`` (dims kept), as float32."""
    return jnp.sum(jnp.where(mask, 1.0, 0.0), axis=axis, keepdims=True)


def _nms_kernel(
    thresh_ref,
    zero_ref,
    coords_ref,
    boxes_ref,
    scores_ref,
    order_ref,
    idx_ref,
    valid_ref,
    sel_r1,
    sel_c1,
    sel_r2,
    sel_c2,
    count_ref,
    *,
    tile: int,
    max_out: int,
):
    i = pl.program_id(0)
    n_tiles = pl.num_programs(0)
    sel_refs = (sel_r1, sel_c1, sel_r2, sel_c2)
    slots = idx_ref.shape[0]  # max_out rounded up to the sublane tile

    @pl.when(i == 0)
    def _init():
        count_ref[0] = 0
        idx_ref[...] = jnp.zeros_like(idx_ref)
        valid_ref[...] = jnp.zeros_like(valid_ref)
        for ref in sel_refs:
            ref[...] = jnp.zeros_like(ref)

    count = count_ref[0]

    @pl.when(count < max_out)
    def _tile_step():
        thresh = thresh_ref[0, 0]
        zero = zero_ref[0, 0]
        t_rows = _rows(coords_ref)  # 4 x [1, tile]
        t_cols = _cols(boxes_ref)  # 4 x [tile, 1]
        tv = scores_ref[...] > _NEG  # [1, tile]
        ti = order_ref[...].astype(jnp.float32)  # [1, tile] original indices
        sel = [ref[...] for ref in sel_refs]  # 4 x [slots, 1]

        # cross-tile: suppressed by any already-selected box (one matrix op)
        cross = _iou_grid(sel, t_rows, zero) > thresh  # [slots, tile]
        slot_id = jax.lax.broadcasted_iota(jnp.int32, (slots, tile), 0)
        m0 = tv & (_count(cross & (slot_id < count), 0) == 0.0)  # [1, tile]

        # in-tile greedy via fixpoint sweeps (exact; see nms_tiled docstring)
        ia = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
        ib = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
        suppress = (_iou_grid(t_cols, t_rows, zero) > thresh) & (ia < ib)
        eye = ia == ib

        def to_col(row):  # [1, tile] 0/1 float -> [tile, 1]
            return jnp.max(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

        def sweep_cond(gs):
            return gs[2] == 0

        def sweep_body(gs):
            g_row, g_col, _ = gs
            hit = _count(suppress & (g_col > 0.0), 0) > 0.0  # [1, tile]
            g2 = jnp.where(m0 & ~hit, 1.0, 0.0)
            stable = jnp.sum(jnp.abs(g2 - g_row)) == 0.0
            return g2, to_col(g2), stable.astype(jnp.int32)

        g0 = jnp.where(m0, 1.0, 0.0)
        g_row, g_col, _ = jax.lax.while_loop(
            sweep_cond, sweep_body, (g0, to_col(g0), jnp.int32(0))
        )
        g = g_row > 0.0

        # append this tile's selections in order; the scatter of the XLA
        # tiling (`at[slot].set(mode="drop")`) becomes a one-hot
        # gather-free write: each output slot takes at most one candidate
        pos = (
            count + _count((ia <= ib) & (g_col > 0.0), 0).astype(jnp.int32) - 1
        )  # [1, tile] target slot per kept box
        onehot = g & (slot_id == pos) & (pos < max_out)  # [slots, tile]
        taken = _count(onehot, 1) > 0.0  # [slots, 1]
        for ref, old, row in zip(sel_refs, sel, t_rows):
            new = jnp.sum(jnp.where(onehot, row, 0.0), axis=1, keepdims=True)
            ref[...] = jnp.where(taken, new, old)
        new_idx = jnp.sum(jnp.where(onehot, ti, 0.0), axis=1, keepdims=True)
        idx_ref[...] = jnp.where(taken, new_idx.astype(jnp.int32), idx_ref[...])
        kept = jnp.sum(g_row).astype(jnp.int32)
        count_ref[0] = jnp.minimum(count + kept, max_out)

    @pl.when(i == n_tiles - 1)
    def _finalize():
        final = count_ref[0]
        valid_ref[...] = (
            jax.lax.broadcasted_iota(jnp.int32, valid_ref.shape, 0) < final
        ).astype(jnp.int32)


@partial(
    jax.jit,
    static_argnames=("max_out", "tile", "assume_sorted", "interpret"),
)
def _nms_fixed_pallas(
    boxes: Array,
    scores: Array,
    iou_thresh: Array,
    max_out: int,
    mask: Array | None,
    tile: int,
    assume_sorted: bool,
    interpret: bool,
) -> tuple[Array, Array]:
    # ---- prep: identical to nms_fixed_tiled ----
    n = boxes.shape[0]
    tile = min(tile, max(n, 1))
    s = scores.astype(jnp.float32)
    s = jnp.where(jnp.isfinite(s), s, _NEG)
    if mask is not None:
        s = jnp.where(mask, s, _NEG)

    n_tiles = -(-n // tile)
    n_pad = n_tiles * tile
    pad = n_pad - n
    if assume_sorted:
        order_p = jnp.pad(jnp.arange(n, dtype=jnp.int32), (0, pad))
        s_sorted = jnp.pad(s, (0, pad), constant_values=_NEG)
        b_sorted = jnp.pad(boxes.astype(jnp.float32), ((0, pad), (0, 0)))
    else:
        order = jnp.argsort(-s)
        order_p = jnp.pad(order, (0, pad)).astype(jnp.int32)
        s_sorted = jnp.pad(s[order], (0, pad), constant_values=_NEG)
        b_sorted = jnp.pad(boxes.astype(jnp.float32)[order], ((0, pad), (0, 0)))

    thresh = jnp.full((1, 1), iou_thresh, jnp.float32)
    zero = jnp.zeros((1, 1), jnp.float32)  # runtime +0.0, see _iou_grid
    coords = b_sorted.T  # [4, n_pad] — the tile as rows (index on lanes)
    s_row = s_sorted[None, :]
    o_row = order_p[None, :]
    # producer barrier: keep the sort/pad/transpose prep from fusing into
    # the inlined kernel body on CPU, where it perturbs LLVM vectorization
    # of the IoU arithmetic (see _iou_grid docstring)
    thresh, zero, coords, b_sorted, s_row, o_row = jax.lax.optimization_barrier(
        (thresh, zero, coords, b_sorted, s_row, o_row)
    )

    slots = -(-max_out // 8) * 8  # column buffers: whole sublane tiles
    idx_col, valid_col = pl.pallas_call(
        partial(_nms_kernel, tile=tile, max_out=max_out),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((4, tile), lambda i: (0, i)),
            pl.BlockSpec((tile, 4), lambda i: (i, 0)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((slots, 1), lambda i: (0, 0)),
            pl.BlockSpec((slots, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((slots, 1), jnp.int32),
            jax.ShapeDtypeStruct((slots, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((slots, 1), jnp.float32)] * 4
        + [pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(thresh, zero, coords, b_sorted, s_row, o_row)

    valid = valid_col[:max_out, 0].astype(bool)
    return jnp.where(valid, idx_col[:max_out, 0], 0), valid


def nms_fixed_pallas(
    boxes: Array,
    scores: Array,
    iou_thresh: float,
    max_out: int,
    mask: Array | None = None,
    tile: int = 512,
    assume_sorted: bool = False,
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """Drop-in replacement for :func:`ops.nms_tiled.nms_fixed_tiled`
    (same contract, bit-identical selections).

    ``interpret=None`` resolves to interpret mode unless the default JAX
    backend is a real TPU — the CPU tier-1 path always interprets.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _nms_fixed_pallas(
        boxes,
        scores,
        jnp.asarray(iou_thresh, jnp.float32),
        max_out,
        mask,
        tile,
        assume_sorted,
        bool(interpret),
    )
