"""The chunked gated delta rule (ops/delta_rule.py) against the benchmark's
token-by-token recurrence (perf/references/qwen3next.py), float32 on the CPU:
outputs and all five gradients; what a caller's checkpoint keeps."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import harness
from replication_faster_rcnn_tpu.ops import delta_rule
from replication_faster_rcnn_tpu.ops.delta_rule import RESIDUAL_NAMES, gated_delta_rule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = harness.load_file(os.path.join(ROOT, "perf", "references", "qwen3next.py"))
B, KH, H, DK, DV = 2, 2, 4, 16, 16


def _inputs(t, seed=0):
    """q and k of unit length (q over sqrt(dk)), decays from all but lost to all but kept."""
    r = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.randn(B, t, KH, DK)) / np.sqrt(DK)
    k = unit(r.randn(B, t, KH, DK))
    v = r.randn(B, t, H, DV)
    g = -np.exp(1.5 * r.randn(B, t, H) - 2.0)
    beta = 1.0 / (1.0 + np.exp(-r.randn(B, t, H)))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _recurrence(q, k, v, g, beta):
    return ref.gated_delta_recurrence(q, k, v, g, beta)


# 200 tokens are no multiple of a chunk of 64 (padded to 4 chunks); 256 are; 2 chunks a
# segment walks two segments, 1 chunk four, the committed length one; 64 tokens are one chunk
@pytest.mark.parametrize(
    "t,segment",
    [(200, 2), (256, 2), (256, 1), (200, delta_rule.SEGMENT), (256, delta_rule.SEGMENT), (64, delta_rule.SEGMENT)],
)
def test_the_chunked_form_is_the_recurrence_outputs_and_all_five_gradients(monkeypatch, t, segment):
    """Two writings of one function in float32: the sums run in another order
    (64 tokens solved together, the state carried by chunk), so outputs and
    gradients agree to 2e-5 of each array's largest magnitude."""
    monkeypatch.setattr(delta_rule, "SEGMENT", segment)
    args = _inputs(t)
    cot = jnp.asarray(np.random.RandomState(1).randn(B, t, H, DV), jnp.float32)
    want, want_grads = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(_recurrence(*a) * cot), argnums=range(5)))(*args)
    chunked = lambda *a: jnp.sum(gated_delta_rule(*a)[0] * cot)
    got, got_grads = jax.jit(jax.value_and_grad(chunked, argnums=range(5)))(*args)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) < 2e-5, name
    np.testing.assert_allclose(
        gated_delta_rule(*args)[0], _recurrence(*args), atol=2e-5 * float(jnp.max(jnp.abs(args[2])))
    )


def test_the_state_at_the_rows_end_is_the_recurrences_and_takes_a_cotangent(monkeypatch):
    """The second result, each head's state after the row's last token: what
    the token-by-token writing carries, and differentiable like the outputs."""
    monkeypatch.setattr(delta_rule, "SEGMENT", 2)  # two segments
    args = _inputs(200, seed=3)
    rep = H // KH

    def plain(q, k, v, g, beta):
        q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)

        def token(s, x):
            kt, vt, gt, bt = x
            s = jnp.exp(gt)[..., None, None] * s
            return s + kt[..., :, None] * (bt[..., None] * (vt - jnp.einsum("bhde,bhd->bhe", s, kt)))[..., None, :], None

        first = jnp.zeros((B, H, DK, DV), jnp.float32)
        return jax.lax.scan(token, first, tuple(jnp.moveaxis(x, 1, 0) for x in (k, v, g, beta)))[0]

    cot = jnp.asarray(np.random.RandomState(2).randn(B, H, DK, DV), jnp.float32)
    np.testing.assert_allclose(gated_delta_rule(*args)[1], plain(*args), atol=2e-5)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * cot), argnums=range(5))(*args)
    got = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a)[1] * cot), argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * max(float(jnp.max(jnp.abs(b))), 1.0), name


def test_a_token_that_writes_nothing_leaves_the_state_and_padding_is_such_a_token():
    """`beta` nought and `g` nought: the state after the token is the state
    before it. A row is padded with such tokens, so its outputs do not depend
    on how far it is padded."""
    q, k, v, g, beta = _inputs(100, seed=5)
    still = lambda x, fill: jnp.concatenate([x, jnp.full((B, 28) + x.shape[2:], fill, x.dtype)], axis=1)
    longer = (still(q, 0.3), still(k, 0.3), still(v, 1.0), still(g, 0.0), still(beta, 0.0))
    o, state = gated_delta_rule(q, k, v, g, beta)
    o_longer, state_longer = gated_delta_rule(*longer)
    np.testing.assert_allclose(o_longer[:, :100], o, atol=1e-6)
    np.testing.assert_allclose(state_longer, state, atol=1e-6)


def test_a_checkpoint_keeps_the_named_residuals_of_the_delta_rule_and_runs_no_forward_again(capsys, monkeypatch):
    """Under `save_only_these_names(*RESIDUAL_NAMES)` the backward pass is
    handed the five inputs by chunk, the state at each segment's start and
    the output, and the gradient holds the forward's walk once (one scan over
    segments forward, one backward); under a plain `jax.checkpoint` it holds
    the arguments alone and walks forward twice."""
    monkeypatch.setattr(delta_rule, "SEGMENT", 4)
    t = 512  # 8 chunks, 2 segments
    args = _inputs(t)
    f = lambda *a: gated_delta_rule(*a)[0]

    def kept(fn):
        jax.ad_checkpoint.print_saved_residuals(fn, *args)
        lines = capsys.readouterr().out.splitlines()
        arguments = [line for line in lines if "from the argument" in line]
        return len(arguments), sorted(line.split()[0] for line in lines if line not in arguments)

    named = jax.checkpoint(f, policy=jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES))
    keys, values, scalars = f"f32[{B},{KH},8,64,{DK}]", f"f32[{B},{H},8,64,{DV}]", f"f32[{B},{H},8,64]"
    n_args, shapes = kept(named)
    # the output is named too, for a caller whose own backward pass reads it (the layer's gated
    # norm); this function's backward pass does not, so alone it is not kept
    assert n_args == 0 and shapes == sorted([keys, keys, values, scalars, scalars, f"f32[2,{B},{H},{DK},{DV}]"])
    assert kept(jax.checkpoint(f)) == (5, [])
    walks = lambda fn: str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(fn(*a))))(*args)).count("scan[")
    # a walk forward is a scan over segments holding a scan over chunks; the walk backward holds two more
    assert walks(jax.checkpoint(f)) - walks(named) == 2


def test_the_triangular_inverse_is_the_inverse_and_its_gradient_is_autodiffs():
    # entries as the layer's are: a product of unit keys times a write strength and a decay
    a = jnp.tril(jnp.asarray(0.2 * np.random.RandomState(0).randn(3, 64, 64), jnp.float32), -1)
    t = delta_rule._inverse(a)
    np.testing.assert_allclose(jnp.matmul(jnp.eye(64) - a, t), jnp.broadcast_to(jnp.eye(64), a.shape), atol=2e-4)
    cot = jnp.asarray(np.random.RandomState(1).randn(3, 64, 64), jnp.float32)
    want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(jnp.eye(64) - a) * cot))(a)
    got = jax.grad(lambda a: jnp.sum(delta_rule._inverse(a) * cot))(a)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3 * float(jnp.max(jnp.abs(want))))
