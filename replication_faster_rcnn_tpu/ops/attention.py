"""Causal attention over a packed row, with or without a sliding window.

One writing (ROADMAP D2): three Pallas kernels of the repo's own, compiled
on a TPU and run in interpret mode everywhere else (``ops.interpret_mode``),
as `ops/pool_ops.py` runs its kernels. Queries and keys are walked a block at
a time with a running maximum and sum; a block that lies wholly outside the
mask (later keys; on a windowed layer also keys a window or more back) is
neither fetched nor computed: the grid's last axis runs over the key blocks a
query tile can see, counted from the first, and a step past the last repeats
that block's index (no copy) and does nothing. No ``[heads, T, T]`` array is
ever formed. The backward pass recomputes the probabilities from the saved
log-sum-exp, one kernel for the queries' gradient and one for the keys' and
values'.

The query heads that share a KV head go through the kernels together: a
query tile is ``group x BLOCK_Q`` rows against one block of keys, so ``k``
and ``v`` are read once a tile and never repeated, and the products are 2,048
rows tall at the published sizes. Only a block that the mask's edge crosses
builds the mask; the others are plain products.

A row whose length is no multiple of the key block is padded at its end: a
padded key is later than every real query, and a padded query's output is
cut off again.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from replication_faster_rcnn_tpu.ops import interpret_mode

Array = jnp.ndarray

BLOCK_Q = 256  # query positions a tile, each with all the heads of its group
BLOCK_KV = 512  # key positions a block
_LANES = 128
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)  # a hidden score: finite, so no inf - inf
_VMEM_BYTES = 64 * 1024 * 1024
# what the forward rule keeps for the backward kernels (the tiles of q, k and v as the
# kernels take them, the output tiles, the log-sum-exp), by the names a caller's
# `jax.checkpoint` may keep them under (`save_only_these_names`): its backward pass then
# neither runs the forward kernel again nor builds its operands. Without such a policy
# the names change nothing
RESIDUAL_NAMES = ("attention_q", "attention_k", "attention_v", "attention_out", "attention_lse")


def visible(t: int, window: Optional[int]) -> Array:
    """``[t, t]`` bool: key j is visible to query i if it is not later and,
    with a window, fewer than ``window`` positions earlier."""
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    return seen if window is None else seen & (i - j < window)


class _Walk:
    """Which blocks meet under the mask, in block indices; shapes only."""

    def __init__(self, t: int, window: Optional[int]) -> None:
        # powers of two, so that a query tile divides a key block and the padded row
        self.bkv = next(b for b in (_LANES, 2 * _LANES, BLOCK_KV) if b >= min(t, BLOCK_KV))
        self.bq = min(BLOCK_Q, self.bkv)
        self.t = -(-t // self.bkv) * self.bkv  # padded
        self.window = None if window is None or window >= self.t else int(window)
        self.nq, self.nkv = self.t // self.bq, self.t // self.bkv
        w = self.window
        # key blocks a query tile walks, query tiles a key block walks
        self.kv_steps = self.nkv if w is None else min(self.nkv, (w + self.bq - 2) // self.bkv + 2)
        self.q_steps = self.nq if w is None else min(self.nq, (w + self.bkv - 2) // self.bq + 2)

    def first_kv(self, i):
        return 0 if self.window is None else jnp.maximum(i * self.bq - self.window + 1, 0) // self.bkv

    def last_kv(self, i):
        return (i * self.bq + self.bq - 1) // self.bkv

    def first_q(self, c):
        return (c * self.bkv) // self.bq

    def last_q(self, c):
        if self.window is None:
            return self.nq - 1
        return jnp.minimum((c * self.bkv + self.bkv + self.window - 2) // self.bq, self.nq - 1)

    def whole(self, i, c):
        """No query of tile i hides a key of block c."""
        seen = c * self.bkv + self.bkv - 1 <= i * self.bq
        if self.window is not None:
            seen = seen & (i * self.bq + self.bq - 1 - c * self.bkv < self.window)
        return seen

    def scores(self, s, i, c, queries_first: bool):
        """``s`` of tile i against block c with the hidden pairs put out;
        rows are queries (``group x bq``, a head's positions together) where
        ``queries_first``, else keys."""
        qs = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0 if queries_first else 1)
        ks = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 if queries_first else 0)
        qs = i * self.bq + jnp.bitwise_and(qs, self.bq - 1)
        ks = c * self.bkv + ks
        seen = ks <= qs
        if self.window is not None:
            seen = seen & (qs - ks < self.window)
        return jnp.where(seen, s, _MASKED)

    def masked(self, s, i, c, queries_first: bool):
        return jax.lax.cond(
            self.whole(i, c), lambda: s, lambda: self.scores(s, i, c, queries_first)
        )


def _column(row: Array) -> Array:
    """``[1, R]`` -> ``[R, 1]``, through the transpose unit."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, row.shape[1])))[:, :1]


def _nt(a: Array, b: Array) -> Array:
    """``a @ b.T`` with float32 sums."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _nn(a: Array, b: Array) -> Array:
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _forward_kernel(walk: _Walk, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
    i, j = pl.program_id(1), pl.program_id(2)
    c = walk.first_kv(i) + j

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(c <= walk.last_kv(i))
    def _():
        s = walk.masked(_nt(q_ref[...], k_ref[...]), i, c, True)  # [R, bkv]
        m_old = m_scr[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        scale = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = scale * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = scale * acc_scr[...] + _nn(p.astype(v_ref.dtype), v_ref[...])
        m_scr[...] = m_new

    @pl.when(j == walk.kv_steps - 1)
    def _():
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l_scr[...])  # [R, 1]
        lse_ref[...] = jnp.transpose(jnp.broadcast_to(lse, (lse.shape[0], _LANES)))[:1]


def _dq_kernel(walk: _Walk, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, lse_scr, delta_scr, acc_scr):
    i, j = pl.program_id(1), pl.program_id(2)
    c = walk.first_kv(i) + j

    @pl.when(j == 0)
    def _():
        lse_scr[...] = _column(lse_ref[...])
        delta_scr[...] = _column(delta_ref[...])
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(c <= walk.last_kv(i))
    def _():
        s = walk.masked(_nt(q_ref[...], k_ref[...]), i, c, True)  # [R, bkv]
        p = jnp.exp(s - lse_scr[...])
        ds = p * (_nt(do_ref[...], v_ref[...]) - delta_scr[...])
        acc_scr[...] += _nn(ds.astype(k_ref.dtype), k_ref[...])

    @pl.when(j == walk.kv_steps - 1)
    def _():
        dq_ref[...] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(walk: _Walk, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr):
    c, j = pl.program_id(1), pl.program_id(2)
    i = walk.first_q(c) + j

    @pl.when(j == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(i <= walk.last_q(c))
    def _():
        # keys down the rows here: the queries' statistics are rows as they are stored
        s = walk.masked(_nt(k_ref[...], q_ref[...]), i, c, False)  # [bkv, R]
        p = jnp.exp(s - lse_ref[...])
        dv_scr[...] += _nn(p.astype(do_ref.dtype), do_ref[...])
        ds = p * (_nt(v_ref[...], do_ref[...]) - delta_ref[...])
        dk_scr[...] += _nn(ds.astype(q_ref.dtype), q_ref[...])

    @pl.when(j == walk.q_steps - 1)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_BYTES
    )


def _specs(walk: _Walk, rows: int, d: int):
    """Block specs of a query tile, a key block and a tile's statistics, for
    a grid (n, query tile, key step) and for a grid (n, key block, query step)."""
    kv_of = lambda i, j: jnp.minimum(walk.first_kv(i) + j, walk.last_kv(i))
    q_of = lambda c, j: jnp.minimum(walk.first_q(c) + j, walk.last_q(c))
    by_q = {
        "tile": pl.BlockSpec((None, None, rows, d), lambda n, i, j: (n, i, 0, 0)),
        "keys": pl.BlockSpec((None, walk.bkv, d), lambda n, i, j: (n, kv_of(i, j), 0)),
        "stat": pl.BlockSpec((None, None, 1, rows), lambda n, i, j: (n, i, 0, 0)),
    }
    by_kv = {
        "tile": pl.BlockSpec((None, None, rows, d), lambda n, c, j: (n, q_of(c, j), 0, 0)),
        "keys": pl.BlockSpec((None, walk.bkv, d), lambda n, c, j: (n, c, 0)),
        "stat": pl.BlockSpec((None, None, 1, rows), lambda n, c, j: (n, q_of(c, j), 0, 0)),
    }
    return by_q, by_kv


def _forward(walk: _Walk, q: Array, k: Array, v: Array):
    """``q``: ``[n, nq, rows, d]``; ``k``, ``v``: ``[n, t, d]``. Returns the
    output as ``q`` is laid out and the log-sum-exp ``[n, nq, 1, rows]``."""
    n, nq, rows, d = q.shape
    by_q, _ = _specs(walk, rows, d)
    return pl.pallas_call(
        functools.partial(_forward_kernel, walk),
        grid=(n, nq, walk.kv_steps),
        in_specs=[by_q["tile"], by_q["keys"], by_q["keys"]],
        out_specs=[by_q["tile"], by_q["stat"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct((n, nq, 1, rows), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32)] * 2 + [pltpu.VMEM((rows, d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret_mode(),
        name="attention_forward",
    )(q, k, v)


def _backward(walk: _Walk, q: Array, k: Array, v: Array, o: Array, lse: Array, do: Array):
    n, nq, rows, d = q.shape
    by_q, by_kv = _specs(walk, rows, d)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, :, None, :]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, walk),
        grid=(n, nq, walk.kv_steps),
        in_specs=[by_q["tile"], by_q["keys"], by_q["keys"], by_q["tile"], by_q["stat"], by_q["stat"]],
        out_specs=by_q["tile"],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32)] * 2 + [pltpu.VMEM((rows, d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret_mode(),
        name="attention_backward_dq",
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, walk),
        grid=(n, walk.nkv, walk.q_steps),
        in_specs=[by_kv["tile"], by_kv["keys"], by_kv["keys"], by_kv["tile"], by_kv["stat"], by_kv["stat"]],
        out_specs=[by_kv["keys"], by_kv["keys"]],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((walk.bkv, d), jnp.float32)] * 2,
        compiler_params=_params(),
        interpret=interpret_mode(),
        name="attention_backward_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tiles_attention(walk: _Walk, q: Array, k: Array, v: Array) -> Array:
    return _forward(walk, q, k, v)[0]


def _tiles_attention_fwd(walk, q, k, v):
    q, k, v, o, lse = map(checkpoint_name, (q, k, v, *_forward(walk, q, k, v)), RESIDUAL_NAMES)
    return o, (q, k, v, o, lse)


def _tiles_attention_bwd(walk, res, do):
    return _backward(walk, *res, do)


_tiles_attention.defvjp(_tiles_attention_fwd, _tiles_attention_bwd)


def attention(q: Array, k: Array, v: Array, window: Optional[int] = None) -> Array:
    """``softmax(q k^T / sqrt(d) + mask) v`` a row at a time.

    ``q``: ``[B, T, H, d]``; ``k``, ``v``: ``[B, T, KV, d]`` with ``H`` a
    multiple of ``KV`` (query head ``h`` reads KV head ``h // (H / KV)``).
    Returns ``[B, T, H, d]`` in ``q``'s dtype; the softmax is in float32.
    """
    b, t, h, d = q.shape
    kv = k.shape[2]
    group = h // kv
    walk = _Walk(t, window)
    pad = [(0, 0), (0, walk.t - t), (0, 0), (0, 0)]
    q = jnp.pad((q * (d ** -0.5)).astype(q.dtype), pad)
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    # a tile: the group's heads for BLOCK_Q positions, a head's positions together
    q = q.reshape(b, walk.nq, walk.bq, kv, group, d)
    q = jnp.transpose(q, (0, 3, 1, 4, 2, 5)).reshape(b * kv, walk.nq, group * walk.bq, d)
    k, v = (jnp.transpose(x, (0, 2, 1, 3)).reshape(b * kv, walk.t, d) for x in (k, v))
    o = _tiles_attention(walk, q, k, v)
    o = jnp.transpose(o.reshape(b, kv, walk.nq, group, walk.bq, d), (0, 2, 4, 1, 3, 5))
    return o.reshape(b, walk.t, h, d)[:, :t]
