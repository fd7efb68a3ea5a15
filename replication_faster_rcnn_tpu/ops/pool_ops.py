"""The ResNet stem after conv1 as one function: the norm's affine, ReLU and
the 3x3 / stride 2 / pad 1 max-pool, with a hand-written backward.

``norm_relu_max_pool(y, mean, mul, bias, dtype)`` equals

    z = ((y - mean) * mul + bias).astype(dtype)      # flax's `_normalize`
    nn.max_pool(nn.relu(z), (3, 3), (2, 2), ((1, 1), (1, 1)))

to the bit (a maximum rounds nothing), and never holds ``z``, ``relu(z)`` or
their cotangents as arrays of the map's full size.

Why (PERF.md section 6, PR 30). Autodiff writes the pool's backward as XLA's
``select-and-scatter``, which takes its operand, the post-ReLU map, from
memory and fuses with nothing: the step wrote that map, read it twice and
wrote its cotangent, and the norm's backward sums read the cotangent and
``y`` once more, all at the stem's resolution. Here

* the forward reads ``y``, applies the affine where it reads, and keeps each
  window's maximum and WHICH of its nine taps held it;
* the differentiated forward's residuals are that tap index (``int8``) and the
  winner's ``y``, both at the pooled resolution;
* the backward hands each window's cotangent to its winning tap by
  compare-and-select (no gather, no scatter, no read of a full-size map) and
  writes ``y``'s cotangent once; the cotangents of ``mean``, ``mul`` and
  ``bias`` are sums over the winners alone, so over the pooled arrays.

Two Pallas kernels, because the TPU compiler has no cheap way to say either
half (the writings timed: PERF.md): it does not fuse an elementwise producer
into a consumer that reads it through strided slices (with the seam after
the norm it wrote ``z`` first), and it interleaves the four parity classes of
the cotangent through interior pads or a relayout copy, each dearer than the
``select-and-scatter`` it replaces. The kernels see a map as
``[H, W/2, 2, N, C]``, which is the layout the compiler gives the stem's maps
(batch in the sublanes, channels in the lanes) read row-major: the views
around them are bitcasts, and a column's parity is an index, not a stride.
Inside, two runs of columns sit side by side in the lanes: 64 channels fill
half a register, and the kernels are bound by the vector unit, not by memory,
until the lanes are full. Off the TPU the same kernels run in interpret mode
(`ops.interpret_mode`); under jit's auto-partitioning they run in a
`shard_map` along the batch that their lowering makes (`_by_batch`).

The tie rule is ``select-and-scatter``'s own under ``ge``: the first maximum
of a window in row-major order takes the whole cotangent. A window whose
maximum is not positive passes nothing, which is what ReLU's derivative at
and below 0 gives.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import core, dispatch
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core
from jax.interpreters import mlir
from jax.sharding import Mesh, PartitionSpec

from replication_faster_rcnn_tpu.ops import interpret_mode as _interpret

Array = jnp.ndarray

_NO_TAP = 9  # the residual's value where ReLU passed nothing
_CHUNK = 8  # pooled columns a kernel holds in registers at a time
_VMEM_LIMIT = 100 * 2**20  # whole rows of the map are resident: 21 MB at 300x300x64 b32


def _pooled(extent: int) -> int:
    return (extent + 1) // 2


def _chunk(wo: int):
    """Pooled columns a kernel works on at a time, and in how many runs: two
    runs sit side by side in the lanes (64 channels fill half a register)."""
    size = min(_CHUNK, wo)
    size -= size % 2 if size > 1 else 0
    return size, 2 if size > 1 else 1


def _chunks(wo: int, body, carry=None):
    """``carry = body(l0, at_start, at_end, carry)`` over the pooled columns a
    chunk at a time; returns the last. The first and the last chunk have
    static starts and are told so (``True``, else ``None``): their missing
    neighbour is a fill, not a load. The rest run in a loop; the last starts
    at ``wo - chunk`` and may overlap the one before."""
    size, _ = _chunk(wo)
    count = -(-wo // size)
    carry = body(0, True, True if count == 1 else None, carry)
    if count > 2:
        carry = jax.lax.fori_loop(1, count - 1, lambda c, carry: body(c * size, None, None, carry), carry)
    if count > 1:
        carry = body(wo - size, None, True, carry)
    return carry


def _side_by_side(ref, index, l0, count, span, runs, before=None, after=None):
    """``ref[index[0], l0 + i * span : ... + count, *index[1:]]`` for each run
    i, along the lanes. ``before`` / ``after``: the first run starts a column
    early / the last ends a column late, where the array has none: that
    column is the fill given (one or the other, not both)."""
    run = lambda i, shift, count: ref[(*index[:1], pl.ds(l0 + i * span + shift, count), *index[1:])]
    pad = lambda fill: jnp.full((1,) + ref.shape[-2:], fill, ref.dtype)
    pieces = [run(i, 0, count) for i in range(1 if before is not None else 0, runs - (1 if after is not None else 0))]
    if before is not None:
        pieces.insert(0, jnp.concatenate([pad(before), run(0, 1, count - 1)], axis=0) if count > 1 else pad(before))
    if after is not None:
        pieces.append(jnp.concatenate([run(runs - 1, 0, count - 1), pad(after)], axis=0) if count > 1 else pad(after))
    return jnp.concatenate(pieces, axis=-1)


def _store(ref, index, l0, span, value):
    """The runs of ``value``'s lanes back to their columns of ``ref[index]``."""
    c = ref.shape[-1]
    for i in range(value.shape[-1] // c):
        ref[(*index[:1], pl.ds(l0 + i * span, span), *index[1:])] = value[..., i * c : (i + 1) * c].astype(ref.dtype)


def _edge(shape, run, row, runs):
    """-inf at ``row`` of run ``run``'s lanes, 0 elsewhere, ``[rows, 1, lanes]``:
    added to z it keeps a tap that is off the map from winning."""
    rows, _, lanes = shape
    at_row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1, lanes), 0) == row
    in_run = jax.lax.broadcasted_iota(jnp.int32, (rows, 1, lanes), 2) // (lanes // runs) == run
    return jnp.where(at_row & in_run, -jnp.inf, 0.0).astype(jnp.float32)


def _pool_kernel(extent, dtype, with_tap, top_ref, mid_ref, bot_ref, mean_ref, mul_ref, bias_ref,
                 pooled_ref, *tap_won_refs):
    """One pooled row: its windows' rows 2k-1, 2k, 2k+1 of the map arrive as
    three blocks ``[1, Wo, 2, N, C]`` (the first and last clamped, and kept
    from winning here where the window hangs over the map's edge)."""
    h, w = extent
    k = pl.program_id(0)
    wo = pooled_ref.shape[1]
    size, runs = _chunk(wo)
    span = size // runs
    mean, mul, bias = (jnp.concatenate([t[...]] * runs, axis=-1) for t in (mean_ref, mul_ref, bias_ref))
    off = lambda inside: jnp.where(inside, 0.0, -jnp.inf).astype(jnp.float32)
    rows = (
        (top_ref, off(k > 0)),
        (mid_ref, None),
        (bot_ref, off(2 * k + 1 < h) if h % 2 else None),
    )

    def body(l0, at_start, at_end, _):
        def z_of(view, *offs):
            # float32 and int32 throughout (one mask layout); z is rounded to
            # `dtype` later, so the comparisons are `dtype`'s
            z = (view - mean) * mul + bias
            for outside in offs:
                z = z if outside is None else z + outside
            return z

        # columns -1 and, of an odd width, w are off the map
        shape = (span + 1, 1, mean.shape[-1])
        left = None if at_start is None else _edge(shape, 0, 0, runs)
        right = None if at_end is None or w % 2 == 0 else _edge(shape, runs - 1, span, runs)
        best = tap = won = None
        t = 0
        for ref, row_off in rows:
            # the odd columns 2l-1 .. 2l+1 of the chunk's windows are one
            # plane, a column longer: its affine is taken once
            fill = None if at_start is None else 0
            odd = _side_by_side(ref, (0, 1), l0 - 1, span + 1, span, runs, before=fill).astype(jnp.float32)
            even = _side_by_side(ref, (0, 0), l0, span, span, runs).astype(jnp.float32)
            z_odd, z_even = z_of(odd, row_off, left, right), z_of(even, row_off)
            for view, z in ((odd[:span], z_odd[:span]), (even, z_even), (odd[1:], z_odd[1:])):
                z = z.astype(dtype).astype(jnp.float32)
                # strictly greater: the first maximum keeps the window; ReLU's
                # floor is the first to beat
                better = z > (0.0 if best is None else best)
                if best is None:
                    best, won = jnp.where(better, z, 0.0), jnp.where(better, view, 0.0)
                    tap = _NO_TAP - _NO_TAP * better.astype(jnp.int32)
                else:
                    best, won = jnp.where(better, z, best), jnp.where(better, view, won)
                    tap = jnp.where(better, t, tap)
                t += 1
        _store(pooled_ref, (0,), l0, span, best)
        if with_tap:
            _store(tap_won_refs[0], (0,), l0, span, tap)
            _store(tap_won_refs[1], (0,), l0, span, won)

    _chunks(wo, body)


def _to_taps_kernel(tap_ref, below_tap_ref, g_ref, below_g_ref, won_ref, mean_ref, mul_ref, out_ref, sums_ref):
    """Rows 2k and 2k+1 of ``y``'s cotangent, ``[2, Wo, 2, N, C]``, from
    pooled rows k and k+1 (the latter clamped, and past the end not counted),
    and onto ``sums_ref`` ``[2, N, lanes]`` this row's share of the two sums
    the affine's terms need: of dz, and of dz * (y - mean), over the winners.

    Pixel (i, j) lies in one window if both are even, in two if one is odd,
    in four if both are: window (k, l) holds rows 2k-1..2k+1 and columns
    2l-1..2l+1. So each of the four parity classes is one, two or four
    compare-and-selects at the pooled resolution, summed in float32."""
    k = pl.program_id(0)
    wo = tap_ref.shape[1]
    size, runs = _chunk(wo)
    span = size // runs
    mean, mul = (jnp.concatenate([t[...]] * runs, axis=-1) for t in (mean_ref, mul_ref))
    no_row_below = k + 1 == pl.num_programs(0)
    counted_before_last = (-(-wo // size) - 1) * size  # the last chunk may overlap

    def body(l0, at_start, at_end, sums):
        def at(ref, right, fill, to):
            # no window lies right of the last
            after = None if not right or at_end is None else fill
            return _side_by_side(ref, (0,), l0 + right, span, span, runs, after=after).astype(to)

        taps = {(b, r): at(below_tap_ref if b else tap_ref, r, _NO_TAP, jnp.int32) for b in (0, 1) for r in (0, 1)}
        gs = {(b, r): at(below_g_ref if b else g_ref, r, 0, jnp.float32) for b in (0, 1) for r in (0, 1)}

        def routed(*terms):
            """sum of ``g[window]`` where ``tap[window] == t`` over the terms
            ``(t, below, right)``: the window one further down / right."""
            total = None
            for t, below, right in terms:
                # past the last pooled row no window wins anything
                t = jnp.where(no_row_below, -1, t) if below else t
                part = jnp.where(taps[below, right] == t, gs[below, right], 0.0)
                total = part if total is None else total + part
            # z's cotangent in its dtype, then float32 through the cast as
            # autodiff takes it: dy = dz * mul
            return total.astype(g_ref.dtype).astype(jnp.float32) * mul

        _store(out_ref, (0, 0), l0, span, routed((4, 0, 0)))
        _store(out_ref, (0, 1), l0, span, routed((5, 0, 0), (3, 0, 1)))
        _store(out_ref, (1, 0), l0, span, routed((7, 0, 0), (1, 1, 0)))
        _store(out_ref, (1, 1), l0, span, routed((8, 0, 0), (6, 0, 1), (2, 1, 0), (0, 1, 1)))

        # the windows of this chunk that won, once each
        dz = jnp.where(taps[0, 0] != _NO_TAP, gs[0, 0], 0.0)
        if at_end is not None and counted_before_last > l0:
            column = l0 + jax.lax.broadcasted_iota(jnp.int32, (span, 1, mean.shape[-1]), 0)
            column += span * (jax.lax.broadcasted_iota(jnp.int32, column.shape, 2) // (mean.shape[-1] // runs))
            dz = dz * (column >= counted_before_last).astype(jnp.float32)
        centred = _side_by_side(won_ref, (0,), l0, span, span, runs).astype(jnp.float32) - mean
        return sums[0] + jnp.sum(dz, axis=0), sums[1] + jnp.sum(dz * centred, axis=0)

    zero = jnp.zeros(sums_ref.shape[1:], jnp.float32)
    of_dz, of_dz_centred = _chunks(wo, body, (zero, zero))

    @pl.when(k == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    sums_ref[0] += of_dz
    sums_ref[1] += of_dz_centred


def _rows_first(x: Array) -> Array:
    """``[N, H, W, C] -> [H, ceil(W/2), 2, N, C]``: a bitcast where the
    compiler keeps the batch in the sublanes and W is even."""
    n, h, w, c = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, w % 2), (0, 0)))
    return jnp.transpose(x, (1, 2, 0, 3)).reshape(h, _pooled(w), 2, n, c)


def _per_sample(term: Array) -> Array:
    """``[1|N, 1, 1, C] -> [1|N, C]``."""
    return term.reshape(term.shape[0], term.shape[3])


def _params(rows: str):
    return pltpu.CompilerParams(dimension_semantics=(rows,), vmem_limit_bytes=_VMEM_LIMIT)


def _by_batch(fn, name: str):
    """`fn(*arrays, *static)` as one primitive whose lowering knows how the
    program is partitioned, which tracing under `jax.jit` does not.

    A Mosaic kernel cannot be partitioned automatically ("wrap the call in a
    shard_map"), and this TPU runtime has no `custom_partitioning` either
    (four chips, PR 30: "Custom emitter for CustomSPMDPartitioning not
    found"). So where the program is spread over several devices by jit's
    auto-partitioning, the lowering itself wraps `fn` in a `shard_map` over
    the program's own devices, split along the batch: axis 0 of the first
    operand, and of every operand and result as long there; whatever else
    an operand is sharded by is gathered first. Under `shard_map`, and on one
    device, it is `fn` itself. Only the hand-written forward and backward
    call it, so it needs no differentiation or batching rule."""
    prim = jex_core.Primitive(name)
    prim.multiple_results = True
    prim.def_impl(partial(dispatch.apply_primitive, prim))

    def flat(*arrays, static):
        return jax.tree.leaves(fn(*arrays, *static))

    prim.def_abstract_eval(
        lambda *avals, static: [core.ShapedArray(x.shape, x.dtype) for x in jax.eval_shape(partial(flat, static=static), *avals)]
    )

    def lowering(ctx, *operands, static):
        call = partial(flat, static=static)
        context = ctx.module_context.axis_context
        devices = getattr(context, "device_assignment", None)
        if devices is not None and len(devices) > 1:  # jit's auto-partitioning
            named = getattr(context, "abstract_mesh", None)
            shape, names = (named.axis_sizes, named.axis_names) if named is not None and not named.empty else ((len(devices),), ("batch",))
            mesh = Mesh(np.asarray(devices).reshape(shape), names)
            batch = ctx.avals_in[0].shape[0]
            axes, ways = [], 1
            for axis, size in zip(names, shape):  # the leading axes that divide the batch
                if batch % (ways * size):
                    break
                axes.append(axis)
                ways *= size
            along = lambda x: PartitionSpec(tuple(axes) if axes and x.shape[0] == batch else None)
            call = jax.shard_map(
                call, mesh=mesh, check_vma=False,
                in_specs=tuple(along(x) for x in ctx.avals_in), out_specs=[along(x) for x in ctx.avals_out],
            )
        return mlir.lower_fun(call, multiple_results=True)(ctx, *operands)

    mlir.register_lowering(prim, lowering)
    dispatch.prim_requires_devices_during_lowering.add(prim)  # or the lowering is told none

    def bound(*args):
        arrays = [x for x in args if isinstance(x, jax.Array)]
        static = tuple(args[len(arrays):])
        out = prim.bind(*arrays, static=static)
        return out[0] if len(out) == 1 else tuple(out)

    return bound


def _forward(y: Array, mean: Array, mul: Array, bias: Array, dtype, with_tap: bool):
    """max(relu(z)) over each window; with ``with_tap`` also the index 0..8
    of the first tap that holds it (``_NO_TAP`` where it is not positive) and
    that tap's ``y``."""
    n, h, w, c = y.shape
    ho, wo = _pooled(h), _pooled(w)
    row = lambda index: pl.BlockSpec((1, wo, 2, n, c), lambda k: (index(k), 0, 0, 0, 0))
    term = lambda t: pl.BlockSpec(t.shape, lambda k: (0, 0))
    pooled = lambda of: pl.BlockSpec((1, wo, n, c), lambda k: (k, 0, 0, 0))
    terms = [_per_sample(t) for t in (mean, mul, bias)]
    shapes = [jax.ShapeDtypeStruct((ho, wo, n, c), d) for d in (dtype, jnp.int8, y.dtype)]
    out = pl.pallas_call(
        partial(_pool_kernel, (h, w), dtype, with_tap),
        grid=(ho,),
        in_specs=[
            row(lambda k: jnp.maximum(2 * k - 1, 0)),
            row(lambda k: 2 * k),
            row(lambda k: jnp.minimum(2 * k + 1, h - 1)),
        ] + [term(t) for t in terms],
        out_specs=[pooled(s) for s in shapes] if with_tap else pooled(shapes[0]),
        out_shape=shapes if with_tap else shapes[0],
        compiler_params=_params("parallel"),
        interpret=_interpret(),
        name="stem_pool",
    )(*[_rows_first(y)] * 3, *terms)
    batch_first = lambda x: jnp.transpose(x, (2, 0, 1, 3))
    return tuple(batch_first(x) for x in out) if with_tap else batch_first(out)


def _to_taps(tap: Array, g: Array, won: Array, mean: Array, mul: Array, extent, dtype):
    """``y``'s cotangent, each window's ``g`` at its winning tap, times
    ``mul``; and the sums over the winners of dz and of dz * (y - mean), a
    sample at a time, ``[N, 2, 1, 1, C]``."""
    h, w = extent
    n, ho, wo, c = tap.shape
    runs = _chunk(wo)[1]
    rows = lambda x: jnp.transpose(x, (1, 2, 0, 3))
    row = lambda index: pl.BlockSpec((1, wo, n, c), lambda k: (index(k), 0, 0, 0))
    here, below = row(lambda k: k), row(lambda k: jnp.minimum(k + 1, ho - 1))
    mean, mul = _per_sample(mean), _per_sample(mul)
    out, sums = pl.pallas_call(
        _to_taps_kernel,
        grid=(ho,),
        in_specs=[here, below, here, below, here]
        + [pl.BlockSpec(t.shape, lambda k: (0, 0)) for t in (mean, mul)],
        out_specs=[
            pl.BlockSpec((2, wo, 2, n, c), lambda k: (k, 0, 0, 0, 0)),
            pl.BlockSpec((2, n, runs * c), lambda k: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, wo, 2, n, c), dtype),
            jax.ShapeDtypeStruct((2, n, runs * c), jnp.float32),
        ],
        compiler_params=_params("arbitrary"),  # in turn: each row adds onto the sums
        interpret=_interpret(),
        name="stem_pool_backward",
    )(rows(tap), rows(tap), rows(g), rows(g), rows(won), mean, mul)
    dy = jnp.transpose(out.reshape(h, 2 * wo, n, c), (2, 0, 1, 3))[:, :, :w]
    return dy, jnp.swapaxes(sums.reshape(2, n, runs, c).sum(axis=2), 0, 1)[:, :, None, None, :]


_forward = _by_batch(_forward, "stem_pool")
_to_taps = _by_batch(_to_taps, "stem_pool_backward")


def norm_relu_max_pool(y: Array, mean: Array, mul: Array, bias: Array, dtype) -> Array:
    """``[N, H, W, C] -> [N, ceil(H/2), ceil(W/2), C]``. ``mean``, ``mul``
    and ``bias`` broadcast against ``y`` (``[1, 1, 1, C]`` for BatchNorm,
    ``[N, 1, 1, C]`` where the statistics are a sample's own)."""
    return _norm_relu_max_pool(y, mean, mul, bias, jnp.dtype(dtype), y.shape[1:3])


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _norm_relu_max_pool(y, mean, mul, bias, dtype, extent):  # extent: (H, W), for the backward
    return _forward(y, mean, mul, bias, dtype, False)


def _norm_relu_max_pool_fwd(y, mean, mul, bias, dtype, extent):
    pooled, tap, won = _forward(y, mean, mul, bias, dtype, True)
    return pooled, (tap, won, mean, mul, bias)


def _sum_like(x: Array, like: Array) -> Array:
    """``x`` summed over the axes along which ``like`` was broadcast."""
    axes = tuple(i for i, d in enumerate(like.shape) if d == 1 and x.shape[i] != 1)
    return jnp.sum(x, axis=axes, keepdims=True).astype(like.dtype)


def _norm_relu_max_pool_bwd(dtype, extent, res, g):
    tap, won, mean, mul, bias = res
    # z = ((y - mean) * mul + bias).astype(dtype), so with dz the map's
    # cotangent (g at each window's winner, float32 through the cast):
    # dy = dz * mul, dbias = sum dz, dmul = sum dz * (y - mean), dmean = -sum dz * mul,
    # and every sum runs over the winners alone: the pooled arrays hold them.
    # The barrier keeps g's producer what it is without this function (a
    # convolution's fusion that writes [N, H, W, C]); without it the compiler
    # writes g rows first from inside that fusion, at twice its time, and
    # regroups the fusions of the two blocks behind it (PERF.md section 6, PR 30)
    g = jax.lax.optimization_barrier(g)
    dy, sums = _to_taps(tap, g, won, mean, mul, extent, won.dtype)
    of_dz, of_dz_centred = sums[:, 0], sums[:, 1]
    return dy, _sum_like(-of_dz * mul, mean), _sum_like(of_dz_centred, mul), _sum_like(of_dz, bias)


_norm_relu_max_pool.defvjp(_norm_relu_max_pool_fwd, _norm_relu_max_pool_bwd)
