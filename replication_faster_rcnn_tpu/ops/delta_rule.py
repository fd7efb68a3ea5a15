"""The gated delta rule over a packed row, in its chunked form.

A value head carries a matrix state ``S`` ``[dk, dv]``, nought at the start
of a row, and every token rewrites it:

    S- = exp(g_t) S_{t-1};  S_t = S- + k_t (x) beta_t (v_t - S-^T k_t);  o_t = S_t^T q_t

One writing (ROADMAP D2): plain `jax.numpy`, the same on every backend, in
chunks of ``CHUNK`` tokens. Inside a chunk, with ``gam_i`` the sum of ``g`` up
to token i and ``Gam_ij = exp(gam_i - gam_j)`` for ``i >= j`` (every exponent
at most nought), the tokens' writes solve a unit triangular system:

    A = -strict_lower((beta K) K^T * Gam);  T = (I - A)^-1
    U = T (beta V);  W = T (beta K * exp(gam))

``A`` is nilpotent, so ``T = (I + A)(I + A^2)(I + A^4)...`` : matrix products.
All of that is a chunk's own and runs for many chunks at once (`_prepare`).
What is left to run chunk after chunk is the state (`_states`):

    V_new = U - W S;  S <- exp(gam_C) S + (K * exp(gam_C - gam))^T V_new

and the outputs follow for all chunks together: ``O = (Q * exp(gam)) S +
lower(Q K^T * Gam) V_new``. ``gam``, ``T``, ``U``, ``V_new`` and ``S`` are
float32; a matrix product takes its operands in the dtype of ``v`` (``T``'s
own products excepted) and sums in float32.

The row is walked in segments of ``SEGMENT`` chunks. The forward pass keeps
the state at each segment's start and nothing else of its own; the backward
pass (`jax.custom_vjp`) walks the segments from the last, builds a segment's
``T``, ``U``, ``W`` again, runs its states forward from the kept one, then
the cotangents of state and ``V_new`` backward through its chunks, and hands
what is a chunk's own back through `_prepare`. No chunk's intermediates
outlive their segment. The inputs, the kept states and the output carry the
names of ``RESIDUAL_NAMES``, which a caller's `jax.checkpoint` may keep
(`save_only_these_names`): its backward pass then runs no forward pass of
this function. Without such a policy the names change nothing.

A row whose length is no multiple of a segment is padded at its end with
tokens that write nothing (``beta`` nought, ``g`` nought); their outputs are
cut off again.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

Array = jnp.ndarray

CHUNK = 64  # tokens whose writes are solved together
SEGMENT = 4  # chunks between two kept states (PERF.md section 6, PR 34: 4, 8 and 16 read on the chip)
HI = jax.lax.Precision.HIGHEST
# q, k, v, g, beta as the function takes them, the state at each segment's start, the output
RESIDUAL_NAMES = ("delta_q", "delta_k", "delta_v", "delta_g", "delta_beta", "delta_state", "delta_out")


def _mm(spec: str, a: Array, b: Array, dtype) -> Array:
    """``einsum`` with both operands in ``dtype`` and float32 sums."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype), preferred_element_type=jnp.float32)


@jax.custom_vjp
def _inverse(a: Array) -> Array:
    """``(I - a)^-1`` of strictly lower triangular ``a`` ``[..., C, C]``,
    float32: ``a`` is nilpotent, so the inverse is the finite product
    ``(I + a)(I + a^2)(I + a^4)...``"""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    out, power, reach = eye + a, a, 2
    while reach < c:
        power = jnp.matmul(power, power, precision=HI)
        out = jnp.matmul(out, eye + power, precision=HI)
        reach *= 2
    return out


def _inverse_fwd(a):
    t = _inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    # d(I - a)^-1 = T da T
    tt = jnp.swapaxes(t, -1, -2)
    return (jnp.matmul(jnp.matmul(tt, dt, precision=HI), tt, precision=HI),)


_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _prepare(q: Array, k: Array, v: Array, g: Array, beta: Array):
    """What is a chunk's own, for every chunk of a segment at once. ``q``,
    ``k``: ``[N, KH, S, C, dk]``; ``v``: ``[N, H, S, C, dv]``; ``g``, ``beta``:
    ``[N, H, S, C]`` float32. Returns ``U`` (float32), ``W``, ``Q exp(gam)``,
    ``lower(Q K^T Gam)``, ``K exp(gam_C - gam)`` (in ``v``'s dtype) and
    ``exp(gam_C)`` (float32)."""
    dtype = v.dtype
    rep = v.shape[1] // k.shape[1]
    q, k = (jnp.repeat(x, rep, axis=1) for x in (q, k))
    c = v.shape[3]
    gam = jnp.cumsum(g, axis=-1)
    at = jnp.arange(c)
    lower = at[:, None] >= at[None, :]
    strict = at[:, None] > at[None, :]
    # the exponent is put to nought where the mask hides it: no overflow, no nan in its gradient
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gam[..., :, None] - gam[..., None, :], 0.0)), 0.0)
    kk = _mm("nhsid,nhsjd->nhsij", k, k, dtype)
    t = _inverse(-jnp.where(strict, kk * decay * beta[..., :, None], 0.0))
    kf = k.astype(jnp.float32)
    u = jnp.einsum("nhsij,nhsjd->nhsid", t, beta[..., None] * v.astype(jnp.float32), precision=HI)
    w = jnp.einsum("nhsij,nhsjd->nhsid", t, (beta * jnp.exp(gam))[..., None] * kf, precision=HI)
    qg = q.astype(jnp.float32) * jnp.exp(gam)[..., None]
    p = jnp.where(lower, _mm("nhsid,nhsjd->nhsij", q, k, dtype) * decay, 0.0)
    last = gam[..., -1:]
    kd = kf * jnp.exp(last - gam)[..., None]
    return u, w.astype(dtype), qg.astype(dtype), p.astype(dtype), kd.astype(dtype), jnp.exp(last[..., 0])


def _states(state: Array, u: Array, w: Array, kd: Array, a: Array):
    """The chunks of a segment one after another from ``state`` ``[N, H, dk,
    dv]``: each chunk's starting state ``[S, N, H, dk, dv]``, its ``V_new``
    ``[S, N, H, C, dv]``, and the state after the last."""
    dtype = w.dtype

    def one(s, chunk):
        u, w, kd, a = chunk
        vn = u - _mm("nhcd,nhde->nhce", w, s, dtype)
        return a[..., None, None] * s + _mm("nhcd,nhce->nhde", kd, vn, dtype), (s, vn)

    by_chunk = lambda x: jnp.moveaxis(x, 2, 0)
    state, (starts, vn) = jax.lax.scan(one, state, tuple(map(by_chunk, (u, w, kd, a))))
    return starts, vn, state


def _segment_forward(state: Array, q, k, v, g, beta):
    u, w, qg, p, kd, a = _prepare(q, k, v, g, beta)
    starts, vn, state = _states(state, u, w, kd, a)
    o = _mm("nhscd,snhde->nhsce", qg, starts, v.dtype) + _mm("nhsij,snhjd->nhsid", p, vn, v.dtype)
    return state, o.astype(v.dtype)


def _segment_backward(state: Array, d_state: Array, q, k, v, g, beta, d_o):
    """Cotangents of one segment's inputs and of its starting state, from
    those of its outputs ``d_o`` and of its last state ``d_state``."""
    dtype = v.dtype
    (u, w, qg, p, kd, a), back = jax.vjp(_prepare, q, k, v, g, beta)
    starts, vn, _ = _states(state, u, w, kd, a)

    def one(ds, chunk):
        w, qg, p, kd, a, do = chunk
        dvn = _mm("nhij,nhid->nhjd", p, do, dtype) + _mm("nhcd,nhde->nhce", kd, ds, dtype)
        before = a[..., None, None] * ds + _mm("nhcd,nhce->nhde", qg, do, dtype) - _mm("nhcd,nhce->nhde", w, dvn, dtype)
        return before, (dvn, ds)

    by_chunk = lambda x: jnp.moveaxis(x, 2, 0)
    d_state, (dvn, ds) = jax.lax.scan(one, d_state, tuple(map(by_chunk, (w, qg, p, kd, a, d_o))), reverse=True)
    # what needs a chunk's state and cotangents but no neighbour: all chunks at once
    dw = -_mm("snhce,snhde->nhscd", dvn, starts, dtype)
    dqg = _mm("nhsce,snhde->nhscd", d_o, starts, dtype)
    dp = _mm("nhsie,snhje->nhsij", d_o, vn, dtype)
    dkd = _mm("snhce,snhde->nhscd", vn, ds, dtype)
    da = jnp.einsum("snhde,snhde->nhs", ds, starts)
    du = jnp.moveaxis(dvn, 0, 2)
    cots = back((du, dw.astype(dtype), dqg.astype(dtype), dp.astype(dtype), dkd.astype(dtype), da))
    return d_state, cots


def _by_segment(x: Array, segment: int) -> Array:
    """``[N, heads, chunks, ...]`` -> ``[segments, N, heads, segment, ...]``."""
    n, h, chunks = x.shape[:3]
    return jnp.moveaxis(x.reshape((n, h, chunks // segment, segment) + x.shape[3:]), 2, 0)


def _from_segments(x: Array) -> Array:
    x = jnp.moveaxis(x, 0, 2)
    return x.reshape(x.shape[:2] + (x.shape[2] * x.shape[3],) + x.shape[4:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunked(segment: int, q, k, v, g, beta) -> Tuple[Array, Array]:
    """Arrays by chunk, ``[N, heads, chunks, C, ...]``. Returns the outputs
    as ``v`` is laid out and the state after the last chunk."""
    o, _, state = _walk(segment, q, k, v, g, beta)
    return o, state


def _walk(segment, q, k, v, g, beta):
    n, h, _, _, dv = v.shape
    first = jnp.zeros((n, h, k.shape[-1], dv), jnp.float32)

    def one(state, seg):
        after, o = _segment_forward(state, *seg)
        return after, (o, state)

    last, (o, starts) = jax.lax.scan(one, first, tuple(_by_segment(x, segment) for x in (q, k, v, g, beta)))
    return _from_segments(o), starts, last


def _chunked_fwd(segment, q, k, v, g, beta):
    q, k, v, g, beta = map(checkpoint_name, (q, k, v, g, beta), RESIDUAL_NAMES[:5])
    o, starts, last = _walk(segment, q, k, v, g, beta)
    starts, o = checkpoint_name(starts, RESIDUAL_NAMES[5]), checkpoint_name(o, RESIDUAL_NAMES[6])
    return (o, last), (q, k, v, g, beta, starts)


def _chunked_bwd(segment, res, cot):
    q, k, v, g, beta, starts = res
    d_o, d_last = cot

    def one(d_state, seg):
        state, *rest = seg
        return _segment_backward(state, d_state, *rest)

    _, cots = jax.lax.scan(
        one, d_last, (starts,) + tuple(_by_segment(x, segment) for x in (q, k, v, g, beta, d_o)), reverse=True
    )
    return tuple(_from_segments(c) for c in cots)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def gated_delta_rule(q: Array, k: Array, v: Array, g: Array, beta: Array):
    """The recurrence above over each row of a batch.

    ``q``, ``k``: ``[B, T, KH, dk]``, as the state is to meet them (norms and
    scales are the caller's); ``v``: ``[B, T, H, dv]`` with ``H`` a multiple of
    ``KH`` (value head ``j`` reads key head ``j // (H / KH)``); ``g`` (the log
    of the decay, at most nought) and ``beta``: ``[B, T, H]`` float32. Returns
    ``o`` ``[B, T, H, dv]`` in ``v``'s dtype and each head's state at the row's
    end ``[B, H, dk, dv]`` float32.
    """
    t = v.shape[1]
    chunks = -(-t // CHUNK)
    segment = min(SEGMENT, chunks)
    chunks = -(-chunks // segment) * segment
    pad = chunks * CHUNK - t

    def by_chunk(x):
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 2)  # [B, heads, T, ...]
        return x.reshape(x.shape[:2] + (chunks, CHUNK) + x.shape[3:])

    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    o, state = _chunked(segment, *map(by_chunk, (q, k, v, g, beta)))
    o = jnp.moveaxis(o.reshape(o.shape[:2] + (chunks * CHUNK, o.shape[-1])), 2, 1)
    return o[:, :t], state
