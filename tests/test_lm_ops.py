"""The sequence model's two ops against their plain forms (ops/attention.py,
ops/grouped_mm.py): the kernels in interpret mode, tiny sizes, CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.ops.attention import RESIDUAL_NAMES, attention, visible
from replication_faster_rcnn_tpu.ops.grouped_mm import grouped_matmul

pytestmark = pytest.mark.pallas_interpret


def _plain_attention(q, k, v, window):
    """softmax(q k^T / sqrt(d) + mask) v with the KV heads repeated."""
    t, group, d = q.shape[1], q.shape[2] // k.shape[2], q.shape[3]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    p = jax.nn.softmax(jnp.where(visible(t, window), scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# (row length, window): a window and none, a row that is no multiple of the
# 128-wide block (padded inside), a window longer than the row, rows of three
# key blocks of 512 that a window walks a part of, a row between two and three
# blocks of 128 (padded to one key block of 512 that two query tiles share)
@pytest.mark.parametrize(
    "t,window", [(64, 8), (64, None), (200, 48), (64, 100), (1100, 300), (1100, None), (320, 100), (320, None)]
)
def test_attention_matches_a_plain_masked_softmax_forward_and_gradients(t, window):
    b, h, kv, d = 2, 4, 2, 16  # grouped heads: two query heads a KV head
    kq, kk, kv_, kg = jax.random.split(jax.random.PRNGKey(t + (window or 0)), 4)
    q = jax.random.normal(kq, (b, t, h, d))
    k = jax.random.normal(kk, (b, t, kv, d))
    v = jax.random.normal(kv_, (b, t, kv, d))
    g = jax.random.normal(kg, (b, t, h, d))
    out, pull = jax.vjp(lambda *a: attention(*a, window), q, k, v)
    want, want_pull = jax.vjp(lambda *a: _plain_attention(*a, window), q, k, v)
    # float32 both ways; the kernel sums a block at a time, so not to the bit
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref in zip(pull(g), want_pull(g)):
        np.testing.assert_allclose(got, ref, atol=5e-5)


def test_attention_never_looks_ahead_or_past_its_window():
    """Moving a key that the mask hides moves nothing: the last key for every
    query but the last, and with a window of 4 the first key for queries 4 on."""
    t = 32
    q, k, v = (jax.random.normal(key, (1, t, 2, 16)) for key in jax.random.split(jax.random.PRNGKey(0), 3))
    base = attention(q, k, v, 4)
    moved = attention(q, k.at[:, -1].add(3.0).at[:, 0].add(3.0), v.at[:, -1].add(3.0).at[:, 0].add(3.0), 4)
    np.testing.assert_allclose(moved[:, 4:-1], base[:, 4:-1], atol=1e-6)
    assert not np.allclose(moved[:, :4], base[:, :4], atol=1e-3)


# a row of 700 positions is padded to two key blocks of 512: four query tiles of 256
@pytest.mark.parametrize("window", [300, None])
def test_a_checkpoint_keeps_the_named_residuals_of_attention_and_no_other(window, capsys):
    """Under `jax.checkpoint` with `save_only_these_names(*RESIDUAL_NAMES)` the
    backward pass is handed what the forward rule keeps (the tiles of q, the
    padded rows of k and v, the output tiles and the log-sum-exp) and nothing
    else, not even the arguments, which nothing is built from again; under a
    plain `jax.checkpoint` the arguments and nothing else: the names change
    nothing for a caller without the policy."""
    b, t, h, kv, d = 1, 700, 4, 2, 16
    q, k, v = jnp.zeros((b, t, h, d)), jnp.zeros((b, t, kv, d)), jnp.zeros((b, t, kv, d))
    f = lambda q, k, v: attention(q, k, v, window)

    def kept(g):
        """(the arguments kept, the shapes of what else is kept)"""
        jax.ad_checkpoint.print_saved_residuals(g, q, k, v)
        lines = capsys.readouterr().out.splitlines()
        arguments = [line for line in lines if "from the argument" in line]
        return len(arguments), sorted(line.split()[0] for line in lines if line not in arguments)

    named = jax.checkpoint(f, policy=jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES))
    n, padded, tiles, rows = b * kv, 1024, 1024 // 256, (h // kv) * 256
    q_tiles, keys, stat = f"f32[{n},{tiles},{rows},{d}]", f"f32[{n},{padded},{d}]", f"f32[{n},{tiles},1,{rows}]"
    assert kept(named) == (0, sorted([q_tiles, keys, keys, q_tiles, stat]))
    assert kept(jax.checkpoint(f)) == (3, [])


def _plain_grouped(rows, weights, sizes):
    group = jnp.repeat(jnp.arange(len(sizes)), sizes, total_repeat_length=rows.shape[0])
    valid = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
    return jnp.where(valid[:, None], jnp.einsum("mk,mkn->mn", rows, weights[group]), 0.0)


# groups that fill the buffer, leave most of it empty, and are all empty
@pytest.mark.parametrize("sizes", [(100, 0, 120, 36), (10, 0, 3, 37), (0, 0, 0, 0)])
def test_grouped_matmul_matches_a_product_a_row_and_is_zero_past_the_last_group(sizes):
    sizes = jnp.asarray(sizes, jnp.int32)
    kr, kw = jax.random.split(jax.random.PRNGKey(1))
    rows, weights = jax.random.normal(kr, (256, 64)), jax.random.normal(kw, (4, 64, 32))
    f = lambda r, w: jnp.sum(jnp.square(grouped_matmul(r, w, sizes)))
    g = lambda r, w: jnp.sum(jnp.square(_plain_grouped(r, w, sizes)))
    out, want = grouped_matmul(rows, weights, sizes), _plain_grouped(rows, weights, sizes)
    np.testing.assert_allclose(out, want, atol=1e-4)
    assert not np.any(np.asarray(out)[int(jnp.sum(sizes)):])
    for got, ref in zip(jax.grad(f, (0, 1))(rows, weights), jax.grad(g, (0, 1))(rows, weights)):
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-3)


def test_grouped_matmul_wants_rows_in_tiles():
    with pytest.raises(ValueError, match="multiple of 128"):
        grouped_matmul(jnp.zeros((100, 8)), jnp.zeros((2, 8, 8)), jnp.asarray([1, 1], jnp.int32))
