"""The sequence model (models/lm.py) against the benchmark's plain reference
(perf/references/afmoe.py) on seeded weights, at the tiny preset's sizes, CPU:
loss, every leaf's gradient and three steps of Adam with the balance bias;
the expert layer's share of the model; no pair dropped whatever the routing."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from perf import harness
from replication_faster_rcnn_tpu.config import get_config
from replication_faster_rcnn_tpu.models import lm
from replication_faster_rcnn_tpu.train.train_step import TrainState, make_optimizer, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = harness.load_file(os.path.join(ROOT, "perf", "references", "afmoe.py"))
with open(os.path.join(ROOT, "tests", "perf_yardstick", "lm", "configs", "trinity_tiny.json")) as f:
    SIZES = json.load(f)["sizes"]


def _config(dtype):
    cfg = get_config("trinity_tiny")
    return cfg.replace(model=dataclasses.replace(cfg.model, compute_dtype=dtype))


def _tree(flat):
    return traverse_util.unflatten_dict({tuple(k.split("/")): v for k, v in flat.items()})


def _flat(tree):
    return traverse_util.flatten_dict(tree, sep="/")


@pytest.fixture(scope="module")
def seeded():
    sz = ref.Sizes(SIZES, 2)
    flat = ref.init_params(sz, jax.random.PRNGKey(7))
    rng = np.random.RandomState(11)
    batches = [{"tokens": rng.randint(0, sz.vocab, (2, sz.seq_len)).astype(np.int32)} for _ in range(3)]
    return sz, flat, batches


@pytest.fixture(scope="module")
def reference_steps(seeded):
    """The reference's three steps: losses, first gradient, parameters, bias."""
    sz, params, batches = seeded
    adam = ref.init_adam(params)
    step = jax.jit(lambda p, a, b, s: ref.train_step(p, a, b, None, s, sz))
    losses, first = [], None
    for i, batch in enumerate(batches):
        params, adam, parts, grad = step(params, adam, batch, jnp.asarray(i, jnp.int32))
        losses.append(parts)
        first = grad if first is None else first
    return losses, first, params, adam["router_bias"]


def test_the_parameter_tree_is_the_references_leaves(seeded):
    sz, flat, _ = seeded
    own = _flat(lm.param_shapes(get_config("trinity_tiny").lm))
    assert own == {k: v.shape for k, v in flat.items()}
    # ISSUE 31's count for the chip's share of Trinity-Mini, to the parameter
    full = jax.tree_util.tree_leaves(lm.param_shapes(get_config("trinity_mini_ep8").lm), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in full) == 663_508_992


def test_three_float32_steps_follow_the_reference(seeded, reference_steps):
    """Float32 program against the float32 reference: the same sums in
    another order (blocked attention, rows sorted by expert), so the first
    gradient agrees to 1e-4 of each leaf's norm. Adam divides a gradient by
    its own magnitude, so a value near nought may move by a whole step of
    lr either way: parameters are held to a tenth of three such steps in the
    mean and the bias, which moves by whole signs, to one sign."""
    sz, flat, batches = seeded
    cfg = _config("float32")
    tx, _ = make_optimizer(cfg, steps_per_epoch=100)
    params = _tree(flat)
    _, stats = lm.init(cfg, jax.random.PRNGKey(0))
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats=stats,
        opt_state=tx.init(params), rng=jax.random.PRNGKey(0),
    )
    step = jax.jit(make_train_step(None, cfg, tx))
    want_losses, want_grad, want_params, want_bias = reference_steps
    for i, batch in enumerate(batches):
        state, metrics = step(state, batch)
        assert float(metrics["tokens_dropped"]) == 0.0
        np.testing.assert_allclose(metrics["loss"], want_losses[i]["loss"], rtol=2e-5)
        assert abs(float(metrics["expert_assignments"]) - float(want_losses[i]["expert_assignments"])) <= 2
        if i == 0:
            # Adam's first moment after one step is 0.1 x the gradient it got
            mu = _flat(next(p.mu for p in state.opt_state if hasattr(p, "mu")))
            for name, g in want_grad.items():
                gap = float(jnp.linalg.norm(mu[name] / 0.1 - g) / jnp.maximum(jnp.linalg.norm(g), 1e-12))
                assert gap < 1e-4, (name, gap)
    for name, p in _flat(state.params).items():
        assert float(jnp.mean(jnp.abs(p - want_params[name]))) < 0.3 * cfg.train.lr, name
    for name, b in state.batch_stats["router_bias"].items():
        assert float(jnp.max(jnp.abs(b - want_bias[name]))) <= 2.5 * cfg.lm.load_balance_coeff, name
        assert abs(float(jnp.mean(b))) < 1e-6 and float(jnp.max(jnp.abs(b))) > 0


def test_the_bfloat16_loss_and_gradient_are_the_references_to_rounding(seeded, reference_steps):
    """bfloat16 compute (8 bits of mantissa, float32 sums) against float32:
    the loss to 2e-2, each leaf's gradient norm to a tenth of the reference's
    norm of that leaf or of the median leaf. The routers' kernels see flipped
    near-tie choices besides and are held to three tenths."""
    sz, flat, batches = seeded
    cfg = _config("bfloat16")
    _, stats = lm.init(cfg, jax.random.PRNGKey(0))
    loss_of = lambda p: lm.losses(None, cfg, p, stats, batches[0], None)[0]
    loss, grads = jax.jit(jax.value_and_grad(loss_of))(_tree(flat))
    want_losses, want_grad, _, _ = reference_steps
    np.testing.assert_allclose(loss, want_losses[0]["loss"], rtol=2e-2)
    # the reference's gradient has the L2 term added: take it off again
    plain = {k: g - sz.weight_decay * flat[k] for k, g in want_grad.items()}
    norms = {k: float(jnp.linalg.norm(g)) for k, g in plain.items()}
    median = sorted(norms.values())[len(norms) // 2]
    for name, g in _flat(grads).items():
        gap = abs(float(jnp.linalg.norm(g)) - norms[name]) / max(norms[name], median)
        assert gap < (0.3 if "router" in name else 0.1), (name, gap)


@pytest.fixture(scope="module")
def tiny_loss(seeded):
    """`lm.losses` at `trinity_tiny` as the preset stands (bfloat16) on the
    seeded weights' first batch, as a function of the parameters."""
    _, flat, batches = seeded
    cfg = get_config("trinity_tiny")
    _, stats = lm.init(cfg, jax.random.PRNGKey(0))
    return cfg, _tree(flat), lambda p: lm.losses(None, cfg, p, stats, batches[0], None)[0]


@pytest.fixture(scope="module")
def gradient_jaxpr(tiny_loss):
    _, params, loss_of = tiny_loss
    return str(jax.make_jaxpr(jax.grad(loss_of))(params))


@pytest.mark.parametrize("kernel", ["attention_forward", "attention_backward_dq", "attention_backward_dkv"])
def test_the_gradient_runs_each_attention_kernel_once_a_layer(tiny_loss, gradient_jaxpr, kernel):
    """The layer's checkpoint keeps the attention function's own residuals
    (`lm.KEPT`), so the backward pass does not run the forward kernel again:
    one call of each of the three kernels a layer, where a checkpoint that
    kept nothing held two of the forward."""
    cfg, _, _ = tiny_loss
    assert len(re.findall(rf"name={kernel}\b", gradient_jaxpr)) == len(cfg.lm.layer_types)


def test_what_the_checkpoint_keeps_changes_no_gradient(tiny_loss, monkeypatch):
    """The oracle is the parent's writing, built here: the same `losses` whose
    layers are checkpointed with no policy. Both gradients are taken op by op
    (no jit around them): each operation is then its own XLA:CPU program, the
    kept arrays are the very ones the second run of the forward kernel and of
    its operands made, and every leaf agrees TO THE BIT. Under one `jax.jit`
    that is not to be had on the CPU: with the recomputation gone XLA:CPU
    groups the layer's bfloat16 fusions otherwise and keeps excess precision
    through another set of roundings (PERF.md section 6, PR 30 and PR 32), so
    most leaves agree to bfloat16 rounding of their norm only."""
    _, params, loss_of = tiny_loss
    got = jax.grad(loss_of)(params)
    monkeypatch.setattr(lm, "KEPT", None)
    want = jax.grad(loss_of)(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(w)) > 0, path
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))


def _expert_layer_inputs(sz, flat, seed=3):
    at = "layers_2/"
    p = {
        "router": {"kernel": flat[at + "router/kernel"]},
        "experts": {k: flat[at + f"experts/{k}"] for k in ("w1", "w3", "w2")},
    }
    h = jax.random.normal(jax.random.PRNGKey(seed), (128, sz.hidden), jnp.float32)
    return at, p, h


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """All four shares of one expert layer (experts 0-3, 4-7, 8-11, 12-15),
    the shared expert counted once, against the reference with every expert
    held: the share is the model's."""
    whole = ref.Sizes({**SIZES, "lm.experts_held": 16}, 2)
    flat = ref.init_params(whole, jax.random.PRNGKey(5))
    at, p, h = _expert_layer_inputs(whole, flat)
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(9), (whole.experts,))
    q = ref.make_rounding("float32")
    chosen, weights, counts = ref.route(h, p["router"]["kernel"], bias, whole)
    shared = ref.swiglu(h, flat[at + "shared/w1"], flat[at + "shared/w3"], flat[at + "shared/w2"], q)
    want = shared + ref.held_experts(h, chosen, weights, *(p["experts"][k] for k in ("w1", "w3", "w2")), whole, q)
    base = get_config("trinity_tiny").lm
    total, pairs = shared, 0.0
    for first in range(0, 16, 4):
        share = dataclasses.replace(base, first_expert=first)
        held = {"router": p["router"], "experts": {k: v[first : first + 4] for k, v in p["experts"].items()}}
        y, stats = lm.expert_layer(share, held, bias, h)
        np.testing.assert_array_equal(stats["counts"], counts)  # every share routes over all 16
        assert float(stats["dropped"]) == 0.0
        total, pairs = total + y, pairs + float(stats["assignments"])
    assert pairs == h.shape[0] * base.experts_per_token  # each pair is computed on one chip
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("held_bias,biased,pairs", [(10.0, 4, 256), (-10.0, 4, 0), (10.0, 1, None)])
def test_no_pair_is_dropped_whatever_the_routing(seeded, held_bias, biased, pairs):
    """The balance bias forced so that every token chooses held experts only
    (the buffers' worst case: tokens x top-k rows, two buffers of 128 full),
    so that none does, and so that every token chooses the first held expert
    and one more of the 15 by its score (the second buffer part full)."""
    sz, flat, _ = seeded
    at, p, h = _expert_layer_inputs(sz, flat)
    cfg = get_config("trinity_tiny").lm
    assert lm.buffer_rows(cfg, h.shape[0]) == (128, 2)
    bias = jnp.where(jnp.arange(sz.experts) < biased, held_bias, 0.0)
    y, stats = jax.jit(lambda p, b, h: lm.expert_layer(cfg, p, b, h))(p, bias, h)
    chosen, weights, _ = ref.route(h, p["router"]["kernel"], bias, sz)
    if pairs is None:
        pairs = int(jnp.sum(chosen < sz.held))
        assert 128 < pairs < 256
    assert float(stats["dropped"]) == 0.0 and float(stats["assignments"]) == pairs
    want = ref.held_experts(h, chosen, weights, *(p["experts"][k] for k in ("w1", "w3", "w2")), sz, ref.make_rounding("float32"))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert bool(jnp.any(y != 0)) == (pairs > 0)
    # and the rows' gradient comes back through every buffer the pairs reached
    got = jax.grad(lambda h: jnp.sum(jnp.square(lm.expert_layer(cfg, p, bias, h)[0])))(h)
    def plain(h):
        chosen, weights, _ = ref.route(h, p["router"]["kernel"], bias, sz)
        return jnp.sum(jnp.square(ref.held_experts(
            h, chosen, weights, *(p["experts"][k] for k in ("w1", "w3", "w2")), sz, ref.make_rounding("float32"))))

    plain = jax.grad(plain)(h)
    np.testing.assert_allclose(got, plain, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("biased,reach", [((), 1), ((0,), 3), ((0, 1), 4)])
def test_a_step_goes_through_as_many_buffers_as_its_pairs_reach(seeded, biased, reach):
    """512 tokens, top-2 of 16, two experts held: buffers of 256 rows, four of
    them at the most. Even routing stays in the first; with the first held
    expert forced on every token the pairs reach the third; with both, the
    fourth is full. Output and the rows' gradient are the reference's each time."""
    sz2 = ref.Sizes({**SIZES, "lm.experts_held": 2}, 2)
    _, flat, _ = seeded
    _, p, _ = _expert_layer_inputs(sz2, flat)
    held = {"router": p["router"], "experts": {k: v[:2] for k, v in p["experts"].items()}}
    h = jax.random.normal(jax.random.PRNGKey(11), (512, sz2.hidden), jnp.float32)
    cfg = dataclasses.replace(get_config("trinity_tiny").lm, experts_held=2)
    rows, buffers = lm.buffer_rows(cfg, 512)
    assert (rows, buffers) == (256, 4)
    bias = jnp.zeros((sz2.experts,)).at[jnp.asarray(biased, jnp.int32)].set(10.0)

    def plain(h):
        chosen, weights, _ = ref.route(h, held["router"]["kernel"], bias, sz2)
        return ref.held_experts(
            h, chosen, weights, *(held["experts"][k] for k in ("w1", "w3", "w2")), sz2, ref.make_rounding("float32")
        )

    y, stats = jax.jit(lambda h: lm.expert_layer(cfg, held, bias, h))(h)
    pairs = float(stats["assignments"])
    assert float(stats["dropped"]) == 0.0 and -(-int(pairs) // rows) == reach
    np.testing.assert_allclose(y, plain(h), rtol=1e-4, atol=1e-5)
    got = jax.grad(lambda h: jnp.sum(jnp.square(lm.expert_layer(cfg, held, bias, h)[0])))(h)
    np.testing.assert_allclose(got, jax.grad(lambda h: jnp.sum(jnp.square(plain(h))))(h), rtol=1e-3, atol=1e-4)


def test_the_balance_bias_moves_against_the_load_and_stays_centred():
    cfg = get_config("trinity_tiny").lm
    counts = jnp.asarray([40.0, 0.0] + [16.0] * 14)
    moved = lm.next_bias(cfg, jnp.zeros((16,)), counts)
    assert float(moved[0]) < 0 < float(moved[1]) and abs(float(jnp.mean(moved))) < 1e-9
    assert float(jnp.max(jnp.abs(moved))) <= 2 * cfg.load_balance_coeff
