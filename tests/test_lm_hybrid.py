"""The hybrid sequence model (models/lm.py with `linear_attention` layers: a
gated delta rule beside gated full attention, a softmax router, a gated
shared expert) against the benchmark's plain reference
(perf/references/qwen3next.py) on seeded weights, at the `qwen3_next_tiny`
preset's sizes, CPU: loss and every leaf's gradient, three steps of Adam, the
expert layer's share of the model, the rotary embedding over part of a head,
the halves of the gated q-projection; through `Trainer` and `cli train`."""

import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from perf import harness
from replication_faster_rcnn_tpu.config import get_config
from replication_faster_rcnn_tpu.models import lm
from replication_faster_rcnn_tpu.ops import delta_rule
from replication_faster_rcnn_tpu.telemetry import stages
from replication_faster_rcnn_tpu.train.train_step import TrainState, make_optimizer, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = harness.load_file(os.path.join(ROOT, "perf", "references", "qwen3next.py"))
with open(os.path.join(ROOT, "tests", "perf_yardstick", "hybrid", "configs", "qwen3_next_tiny.json")) as f:
    SIZES = json.load(f)["sizes"]
PLAIN = ref.make_rounding("float32")


def _config(dtype):
    cfg = get_config("qwen3_next_tiny")
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
    """The reference's three steps: losses, first gradient, parameters."""
    sz, params, batches = seeded
    adam = ref.init_adam(params)
    step = jax.jit(lambda p, a, b, s: ref.train_step(p, a, b, None, s, sz))
    losses, first = [], None
    for i, batch in enumerate(batches):
        params, adam, parts, grad = step(params, adam, batch, jnp.asarray(i, jnp.int32))
        losses.append(parts)
        first = grad if first is None else first
    return losses, first, params


def test_the_parameter_tree_is_the_references_leaves_and_the_share_counts_as_issue_33_reckons(seeded):
    sz, flat, _ = seeded
    own = _flat(lm.param_shapes(get_config("qwen3_next_tiny").lm))
    assert own == {k: v.shape for k, v in flat.items()}
    shapes = _flat(lm.param_shapes(get_config("qwen3_next_ep16").lm))
    count = lambda prefix: sum(int(np.prod(s)) for k, s in shapes.items() if k.startswith(prefix))
    # a delta-rule mixer, the full layer's mixer, what every layer has beside its mixer, the whole share
    assert count("layers_0/linear/") == 33_718_464 and count("layers_3/attn/") == 27_263_488
    assert count("layers_1/") - count("layers_1/linear/") == 104_863_744
    assert count("") == 625_667_136
    assert not lm.init(get_config("qwen3_next_tiny"), jax.random.PRNGKey(0))[1]  # no balance bias to carry


def test_three_float32_steps_follow_the_reference(seeded, reference_steps):
    """Float32 program against the float32 reference: the same sums in
    another order (the delta rule in chunks, blocked attention, rows sorted
    by expert), so the loss agrees to 2e-5 and the first gradient to 1e-4 of
    each leaf's norm. Adam divides a gradient by its own magnitude, so a
    value near nought may move by a whole step of lr either way: parameters
    are held to a tenth of three such steps in the mean."""
    sz, flat, batches = seeded
    cfg = _config("float32")
    tx, _ = make_optimizer(cfg, steps_per_epoch=100)
    params = _tree(flat)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={}, opt_state=tx.init(params), rng=jax.random.PRNGKey(0),
    )
    step = jax.jit(make_train_step(None, cfg, tx))
    want_losses, want_grad, want_params = reference_steps
    for i, batch in enumerate(batches):
        state, metrics = step(state, batch)
        assert float(metrics["tokens_dropped"]) == 0.0 and "router_bias_absmax" not in metrics
        assert 0.0 < float(metrics["delta_decay_mean"]) < 1.0 and float(metrics["delta_state_absmax"]) > 0.0
        np.testing.assert_allclose(metrics["loss"], want_losses[i]["loss"], rtol=2e-5)
        assert abs(float(metrics["expert_assignments"]) - float(want_losses[i]["expert_assignments"])) <= 2
        if i == 0:
            # Adam's first moment after one step is 0.1 x the gradient it got
            mu = _flat(next(p.mu for p in state.opt_state if hasattr(p, "mu")))
            for name, g in want_grad.items():
                gap = float(jnp.linalg.norm(mu[name] / 0.1 - g) / jnp.maximum(jnp.linalg.norm(g), 1e-12))
                assert gap < 1e-4, (name, gap)
    assert not state.batch_stats
    for name, p in _flat(state.params).items():
        assert float(jnp.mean(jnp.abs(p - want_params[name]))) < 0.3 * cfg.train.lr, name


def test_the_bfloat16_loss_and_gradient_norms_are_the_references_to_rounding(seeded, reference_steps):
    """The preset as it stands (bfloat16 operands, float32 sums; the delta
    rule's state and triangular solve float32) against float32: the loss to
    2e-2, each leaf's gradient norm to a tenth of the reference's norm of
    that leaf or of the median leaf; the routers' kernels see flipped
    near-tie choices besides and are held to three tenths."""
    sz, flat, batches = seeded
    cfg = get_config("qwen3_next_tiny")
    loss, grads = jax.jit(jax.value_and_grad(lambda p: lm.losses(None, cfg, p, {}, batches[0], None)[0]))(_tree(flat))
    want_losses, want_grad, _ = reference_steps
    np.testing.assert_allclose(loss, want_losses[0]["loss"], rtol=2e-2)
    norms = {k: float(jnp.linalg.norm(g)) for k, g in want_grad.items()}
    median = sorted(norms.values())[len(norms) // 2]
    for name, g in _flat(grads).items():
        gap = abs(float(jnp.linalg.norm(g)) - norms[name]) / max(norms[name], median)
        assert gap < (0.3 if "router" in name else 0.1), (name, gap)


def test_a_delta_rule_layer_keeps_the_functions_residuals_and_what_is_kept_changes_no_float32_gradient(seeded, monkeypatch, capsys):
    """A delta-rule layer's checkpoint (`lm.kept`) keeps, beside the layer's
    arguments, `lm.DELTA_KEPT` of what the function names: g and beta by
    chunk, the state at each segment's start, and the output (the gated
    norm's backward pass reads it): its backward pass builds q, k and v again
    (the convolution's own backward pass needs the projection anyway) and
    walks the recurrence backward only. In
    float32, where a kept array and its recomputation are the same sums, the
    gradient with nothing kept agrees to 1e-5 of each leaf's norm."""
    _, flat, batches = seeded
    cfg = get_config("qwen3_next_tiny")
    params = _tree(flat)
    x = jnp.zeros((2, cfg.data.seq_len, cfg.lm.hidden_size), jnp.bfloat16)
    one = jax.checkpoint(lambda p, x: lm.layer(cfg.lm, 0, p, None, x)[0], policy=lm.kept(cfg.lm))
    jax.ad_checkpoint.print_saved_residuals(one, params["layers_0"], x)
    saved = sorted(line.split()[0] for line in capsys.readouterr().out.splitlines() if "from the argument" not in line)
    values, scalars = "bf16[2,4,4,64,16]", "f32[2,4,4,64]"  # [B, heads, chunks, 64, ...]
    assert saved == sorted([values, scalars, scalars, "f32[1,2,4,16,16]"])
    assert set(lm.DELTA_KEPT) < set(delta_rule.RESIDUAL_NAMES)
    cfg = _config("float32")
    loss_of = lambda p: lm.losses(None, cfg, p, {}, batches[0], None)[0]
    got = jax.jit(jax.grad(loss_of))(params)
    monkeypatch.setattr(lm, "KEPT", None)
    assert lm.kept(cfg.lm) is None
    want = jax.jit(jax.grad(loss_of))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(g - w)) <= 1e-5 * float(jnp.linalg.norm(w)), jax.tree_util.keystr(path)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Both shares of one expert layer (experts 0-3 and 4-7 of 8: the tiny
    twin of 512 / 32), the gated shared expert counted once, against the
    reference with every expert held: the share is the model's."""
    whole = ref.Sizes({**SIZES, "lm.experts_held": 8}, 2)
    flat = ref.init_params(whole, jax.random.PRNGKey(5))
    at = "layers_1/"
    experts = {k: flat[at + f"experts/{k}"] for k in ("w1", "w3", "w2")}
    kernel = flat[at + "router/kernel"]
    h = jax.random.normal(jax.random.PRNGKey(3), (128, whole.hidden), jnp.float32)
    chosen, weights = ref.route(h, kernel, whole)
    shared = ref.swiglu(h, flat[at + "shared/w1"], flat[at + "shared/w3"], flat[at + "shared/w2"], PLAIN)
    shared = jax.nn.sigmoid(h @ flat[at + "shared_gate/kernel"]) * shared
    want = shared + ref.held_experts(h, chosen, weights, experts["w1"], experts["w3"], experts["w2"], whole, PLAIN)
    np.testing.assert_allclose(jnp.sum(weights, axis=1), 1.0, rtol=1e-6)  # the chosen weights normalised, no scale
    base = get_config("qwen3_next_tiny").lm
    total, pairs = shared, 0.0
    for first in (0, 4):
        share = dataclasses.replace(base, first_expert=first)
        held = {"router": {"kernel": kernel}, "experts": {k: v[first : first + 4] for k, v in experts.items()}}
        y, stats = lm.expert_layer(share, held, None, h)
        assert float(stats["dropped"]) == 0.0
        total, pairs = total + y, pairs + float(stats["assignments"])
    assert pairs == h.shape[0] * base.experts_per_token  # each pair is computed on one chip
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("held_bias,reached", [(10.0, 1), (0.0, 0), (-10.0, 0)])
def test_the_overflow_buffers_under_one_loop_are_those_under_the_switch(monkeypatch, held_bias, reached):
    """Beyond `SWITCH_BUFFERS` buffers a step's overflow runs under one loop on
    the device, with a loop of its own in the backward pass
    (`lm.through_buffers`), in place of a switch that would hold a copy of a
    buffer's program for every count of buffers. The same arithmetic: forced
    onto the loop, a layer whose every token chooses held experts only (the
    second buffer full), none forced, and none held give the switch's value
    and gradients to the bit."""
    base = get_config("trinity_tiny").lm  # 128 tokens x top-2 of 16, 4 held: two buffers of 128 rows
    assert lm.buffer_rows(base, 128) == (128, 2)
    r = np.random.RandomState(0)
    p = {
        "router": {"kernel": jnp.asarray(r.randn(64, 16), jnp.float32)},
        "experts": {k: jnp.asarray(0.1 * r.randn(*s), jnp.float32) for k, s in (("w1", (4, 64, 32)), ("w3", (4, 64, 32)), ("w2", (4, 32, 64)))},
    }
    h = jnp.asarray(r.randn(128, 64), jnp.float32)
    bias = jnp.zeros(16).at[:4].set(held_bias)
    f = lambda p, h: jnp.sum(lm.expert_layer(base, p, bias, h)[0] ** 2)
    pairs = float(lm.expert_layer(base, p, bias, h)[1]["assignments"])
    assert (pairs > 128) == bool(reached)
    want = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(p, h)
    monkeypatch.setattr(lm, "SWITCH_BUFFERS", 0)
    assert "while" in str(jax.make_jaxpr(f)(p, h)) and "while" not in str(jax.make_jaxpr(lambda p, h: want[0])(p, h))
    got = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(p, h)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
    cfg = get_config("qwen3_next_ep16").lm  # the published sizes take the loop: eight buffers of 20,480 rows
    monkeypatch.undo()
    assert lm.buffer_rows(cfg, 16384) == (20480, 8) and 8 > lm.SWITCH_BUFFERS


def test_the_rotary_embedding_turns_the_leading_quarter_of_a_head_and_leaves_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 32), jnp.float32)
    got = lm.rotary(x, 1e7, 0.25)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    assert float(jnp.max(jnp.abs(got[:, 1:, :, :8] - x[:, 1:, :, :8]))) > 0.1
    np.testing.assert_array_equal(got[:, 0], x[:, 0])  # position 0 is not turned
    np.testing.assert_allclose(got, ref.rotary(x, 1e7, 8), atol=1e-6)
    # over the whole head it is the writing the other presets run
    np.testing.assert_allclose(lm.rotary(x, 1e4), ref.rotary(x, 1e4, 32), atol=1e-6)


def test_a_heads_part_of_wq_is_its_q_then_its_gate_and_swapped_halves_do_not_pass(seeded):
    """The full layer's mixer alone against the reference's; then the same
    with every head's two halves of `wq` exchanged in the reference's weights
    (the gate where q belongs): far outside any tolerance."""
    sz, flat, _ = seeded
    cfg = _config("float32")
    at = "layers_3/"
    x = jax.random.normal(jax.random.PRNGKey(4), (2, sz.seq_len, sz.hidden), jnp.float32)
    p = _tree({k[len(at):]: v for k, v in flat.items() if k.startswith(at)})
    got = lm.attention_block(cfg.lm, p, x, False)
    h = ref.rms_norm(x, 1.0 + flat[at + "attn_norm/scale"], sz.eps)
    np.testing.assert_allclose(got, ref.full_attention_mixer(flat, at, h, sz, PLAIN), atol=2e-5)
    wq = flat[at + "attn/wq"].reshape(sz.hidden, sz.heads, 2, sz.head)
    swapped = dict(flat, **{at + "attn/wq": wq[:, :, ::-1].reshape(sz.hidden, -1)})
    wrong = ref.full_attention_mixer(swapped, at, h, sz, PLAIN)
    assert float(jnp.linalg.norm(got - wrong)) > 0.3 * float(jnp.linalg.norm(got))


def test_the_delta_rule_mixer_alone_is_the_references_and_its_counters_read_the_state(seeded):
    sz, flat, _ = seeded
    cfg = _config("float32")
    at = "layers_0/"
    x = jax.random.normal(jax.random.PRNGKey(6), (2, sz.seq_len, sz.hidden), jnp.float32)
    p = _tree({k[len(at):]: v for k, v in flat.items() if k.startswith(at)})
    got, stats = lm.delta_block(cfg.lm, p, x)
    h = ref.rms_norm(x, 1.0 + flat[at + "attn_norm/scale"], sz.eps)
    np.testing.assert_allclose(got, ref.linear_attention_mixer(flat, at, h, sz, PLAIN), atol=2e-5)
    assert 0.5 < float(stats["decay_mean"]) < 1.0  # the seeded heads keep most of their state a token
    # the convolution looks back three tokens and never ahead
    taps = flat[at + "linear/conv"]
    row = jax.random.normal(jax.random.PRNGKey(8), (1, 12, taps.shape[0]), jnp.float32)
    out = lm.causal_conv(row, taps)
    np.testing.assert_allclose(out[0, 0], row[0, 0] * taps[:, 3], rtol=1e-6)
    later = row.at[0, 7:].set(0.0)
    np.testing.assert_array_equal(lm.causal_conv(later, taps)[0, :7], out[0, :7])


class TestHybridStageScopes:
    """The two scopes ISSUE 33 adds, in the lowered step of the tiny hybrid
    preset, forward and backward, the operation's nested in the layer's."""

    @pytest.fixture(scope="class")
    def names(self):
        from replication_faster_rcnn_tpu.train.train_step import create_train_state

        cfg = get_config("qwen3_next_tiny")
        tx, _ = make_optimizer(cfg, steps_per_epoch=1)
        state = jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0), tx)[1])
        batch = {"tokens": jax.ShapeDtypeStruct((2, cfg.data.seq_len), jnp.int32)}
        text = jax.jit(make_train_step(None, cfg, tx)).lower(state, batch).as_text(debug_info=True)
        return set(re.findall(r'loc\("([^"]*/[^"]*)"\(', text))  # the operations' names, as tests/test_stage_scopes.py reads them

    # the latent layer's and the multi-token-prediction module's are in the latent preset's: tests/test_lm_latent.py
    @pytest.mark.parametrize("scope", [s for s in stages.LM_STAGES if s not in (stages.UPDATE, stages.LM_LATENT, stages.LM_MTP)])
    def test_every_stage_is_in_the_lowered_step_forward_and_backward(self, names, scope):
        held = [n for n in names if scope in n]
        assert [n for n in held if "transpose(" not in n], scope
        assert [n for n in held if "transpose(" in n], scope

    def test_the_delta_rule_lies_inside_the_layers_scope(self, names):
        assert any(n.rfind(stages.LM_DELTA_CORE) > n.find(stages.LM_LINEAR_ATTENTION) >= 0 for n in names)
        assert any(n.rfind(stages.LM_ATTN_CORE) > n.find(stages.LM_ATTENTION) >= 0 for n in names)
        assert stages.LM_STAGES.index(stages.LM_DELTA_CORE) == stages.LM_STAGES.index(stages.LM_LINEAR_ATTENTION) + 1


def test_trainer_trains_the_hybrid_preset_strictly_and_writes_the_counters_its_step_has(tmp_path):
    from replication_faster_rcnn_tpu.train import Trainer

    cfg = get_config("qwen3_next_tiny")
    cfg = cfg.replace(debug=dataclasses.replace(cfg.debug, strict=True))
    trainer = Trainer(cfg, workdir=str(tmp_path / "w"), devices=jax.devices()[:1], telemetry_dir=str(tmp_path / "tel"))
    batches = iter(trainer.loader)
    with trainer.strict_session():
        losses = [float(trainer.train_one_batch(batch=next(batches))["loss"]) for _ in range(12)]
    trainer.flush_telemetry()
    assert all(np.isfinite(losses)) and trainer.strict.report()["programs"]["train_step"]["recompiles_after_warmup"] == 0
    with open(tmp_path / "tel" / "trace.json") as f:
        counters = {e["name"] for e in json.load(f)["traceEvents"] if e.get("ph") == "C" and e["name"].startswith("lm/")}
    assert counters == {f"lm/{k}" for k in lm.COUNTERS if k not in ("router_bias_absmax", "mtp_loss")}


def test_cli_train_runs_the_hybrid_preset(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    got = subprocess.run(
        [sys.executable, "-m", "replication_faster_rcnn_tpu.cli", "train", "--config", "qwen3_next_tiny", "--steps", "3",
         "--log-every", "1", "--strict", "--workdir", str(tmp_path / "w")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert got.returncode == 0, got.stderr[-2000:]
    assert "delta_state_absmax=" in got.stdout and "recompiles_after_warmup=0" in got.stdout


@pytest.mark.parametrize("preset", ["voc_resnet18", "trinity_tiny"])
def test_a_detector_and_a_windowed_attention_process_import_nothing_issue_33_adds(preset):
    """`setup_s` of the two older cells has no slack: building their trainer's
    step imports neither the delta rule nor the new reference."""
    code = (
        "import sys, jax\n"
        "from replication_faster_rcnn_tpu.config import get_config\n"
        "from replication_faster_rcnn_tpu.train import Trainer, create_train_state, make_optimizer, make_train_step\n"
        "import replication_faster_rcnn_tpu.cli\n"
        f"cfg = get_config({preset!r})\n"
        "tx, _ = make_optimizer(cfg, 1)\n"
        "from replication_faster_rcnn_tpu.train.train_step import model_kind\n"
        "model = model_kind(cfg).build(cfg)\n"
        "state = jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0), tx)[1])\n"
        "step = make_train_step(model, cfg, tx)\n"
        "if cfg.is_sequence_model:\n"
        "    import jax.numpy as jnp\n"
        "    jax.jit(step).lower(state, {'tokens': jax.ShapeDtypeStruct((2, cfg.data.seq_len), jnp.int32)})\n"
        "bad = [m for m in sys.modules if 'delta_rule' in m or 'qwen3next' in m]\n"
        "print('LOADED', bad)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    got = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-2000:]
    assert "LOADED []" in got.stdout, got.stdout
