"""The latent-attention sequence model (models/lm.py with `latent_attention`
layers and the multi-token-prediction module) against the benchmark's plain
reference (perf/references/joyai.py) on seeded weights, at the `joyai_tiny`
preset's sizes, CPU: the attention kernels with a value width apart from the
query/key width, the interleaved rotary embedding, the latent mixer forward
and backward, the MTP loss and its gradient into the trunk, three steps of
Adam, the expert layer's share of the model, the stage scopes; through
`Trainer`."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from perf import harness
from replication_faster_rcnn_tpu.config import get_config
from replication_faster_rcnn_tpu.models import lm
from replication_faster_rcnn_tpu.ops.attention import attention, visible
from replication_faster_rcnn_tpu.telemetry import stages
from replication_faster_rcnn_tpu.train.train_step import TrainState, make_optimizer, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = harness.load_file(os.path.join(ROOT, "perf", "references", "joyai.py"))
with open(os.path.join(ROOT, "tests", "perf_yardstick", "latent", "configs", "joyai_tiny.json")) as f:
    SIZES = json.load(f)["sizes"]
PLAIN = ref.make_rounding("float32")


def _config(dtype="float32", **lm_fields):
    cfg = get_config("joyai_tiny")
    return cfg.replace(
        model=dataclasses.replace(cfg.model, compute_dtype=dtype), lm=dataclasses.replace(cfg.lm, **lm_fields)
    )


def _tree(flat):
    return traverse_util.unflatten_dict({tuple(k.split("/")): v for k, v in flat.items()})


def _flat(tree):
    return traverse_util.flatten_dict(tree, sep="/")


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.maximum(jnp.linalg.norm(want), 1e-12))


@pytest.fixture(scope="module")
def seeded():
    sz = ref.Sizes(SIZES, 2)
    flat = ref.init_params(sz, jax.random.PRNGKey(7))
    rng = np.random.RandomState(11)
    batches = [{"tokens": rng.randint(0, sz.vocab, (2, sz.seq_len)).astype(np.int32)} for _ in range(3)]
    return sz, flat, batches


# ------------------------------------------------------------ the kernels


def _plain_attention(q, k, v, scale):
    """softmax(scale q k^T + causal) v with the KV heads repeated; v as wide as it is."""
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(visible(t, None), scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# (query heads, KV heads, q/k width, v width): group 1 as latent attention has it, q/k wider than v
# (24 against 16: the tiny preset's nope + rope and v) and narrower, and a group of two
@pytest.mark.pallas_interpret
@pytest.mark.parametrize("h,kv,d,dv", [(4, 4, 24, 16), (2, 2, 16, 32), (4, 2, 24, 16)])
def test_attention_with_a_value_width_apart_matches_a_plain_softmax_forward_and_gradients(h, kv, d, dv):
    b, t = 1, 200  # a row that is no multiple of the 128-wide block: padded inside
    kq, kk, kv_, kg = jax.random.split(jax.random.PRNGKey(h + d + dv), 4)
    q, k = jax.random.normal(kq, (b, t, h, d)), jax.random.normal(kk, (b, t, kv, d))
    v, g = jax.random.normal(kv_, (b, t, kv, dv)), jax.random.normal(kg, (b, t, h, dv))
    scale = 0.3  # given by the caller, not read from the width
    out, pull = jax.vjp(lambda *a: attention(*a, None, scale), q, k, v)
    want, want_pull = jax.vjp(lambda *a: _plain_attention(*a, scale), q, k, v)
    assert out.shape == (b, t, h, dv)
    # float32 both ways; the kernel sums a block at a time, so not to the bit
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, expect in zip(pull(g), want_pull(g)):
        assert got.shape == expect.shape
        np.testing.assert_allclose(got, expect, atol=5e-5)


@pytest.mark.pallas_interpret
def test_q_and_k_padded_with_noughts_under_their_own_scale_give_the_same_attention():
    """What the caller's scale is for: a query and key of 24 padded to 32
    with noughts, at 1 / sqrt(24), attend as the unpadded pair does; and where
    the widths are equal, no scale and 1 / sqrt(d) are one program."""
    b, t, h, d, dv = 1, 128, 2, 24, 16
    q, k, v = (jax.random.normal(key, (b, t, h, w)) for key, w in zip(jax.random.split(jax.random.PRNGKey(3), 3), (d, d, dv)))
    pad = [(0, 0)] * 3 + [(0, 8)]
    np.testing.assert_allclose(
        attention(jnp.pad(q, pad), jnp.pad(k, pad), v, None, d ** -0.5), attention(q, k, v), atol=1e-6
    )
    qq, kk, vv = (jax.ShapeDtypeStruct((b, t, h, 16), jnp.float32),) * 3
    lowered = lambda f: jax.jit(f).lower(qq, kk, vv).as_text()
    assert lowered(lambda *a: attention(*a)) == lowered(lambda *a: attention(*a, None, 16 ** -0.5))


def test_interleaved_rotary_turns_each_pair_2i_2i_plus_1_by_its_own_angle():
    """Against the rotation written out a pair at a time: lanes (2i, 2i + 1)
    of position p turned by p * theta^(-2i / d); the unfolded pairs (i, i +
    d/2) are still what they were."""
    b, t, heads, d, theta = 1, 9, 2, 8, 3.2e7
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (b, t, heads, d)), np.float64)
    want, halves = np.empty_like(x), np.empty_like(x)
    for p in range(t):
        for i in range(d // 2):
            a = p * theta ** (-2.0 * i / d)
            c, s = np.cos(a), np.sin(a)
            e, o = x[:, p, :, 2 * i], x[:, p, :, 2 * i + 1]
            want[:, p, :, 2 * i], want[:, p, :, 2 * i + 1] = e * c - o * s, o * c + e * s
            lo, hi = x[:, p, :, i], x[:, p, :, i + d // 2]
            halves[:, p, :, i], halves[:, p, :, i + d // 2] = lo * c - hi * s, hi * c + lo * s
    got = lm.rotary(jnp.asarray(x, jnp.float32), theta, 1.0, interleave=True)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(ref.rotary_interleaved(jnp.asarray(x, jnp.float32), theta), want, atol=1e-5)
    np.testing.assert_allclose(lm.rotary(jnp.asarray(x, jnp.float32), theta), halves, atol=1e-5)


# --------------------------------------------------------------- the model


def test_the_parameter_tree_is_the_references_leaves_and_the_share_counts_the_held_experts(seeded):
    sz, flat, _ = seeded
    assert _flat(lm.param_shapes(get_config("joyai_tiny").lm)) == {k: v.shape for k, v in flat.items()}
    shapes = _flat(lm.param_shapes(get_config("joyai_flash_ep16").lm))
    count = lambda prefix: sum(int(np.prod(s)) for k, s in shapes.items() if k.startswith(prefix))
    # an MLA mixer with its pre-norm, the dense layer, an expert layer, the MTP module, the vocabulary slice
    assert count("layers_0/attn/") + count("layers_0/attn_norm/") == 26_349_568
    assert count("layers_0/") == 70_391_808 and count("layers_1/") == 107_091_968 and count("mtp/") == 115_486_720
    assert count("embed/") + count("head/") + count("final_norm/") == 66_193_408
    assert count("") == 680_439_808  # 10.89 GB at 16 B a parameter
    bias = lm.init(get_config("joyai_tiny"), jax.random.PRNGKey(0))[1]["router_bias"]
    assert sorted(bias) == ["layers_1", "layers_2", "mtp"]


def test_the_latent_mixer_is_the_references_forward_and_backward(seeded):
    """`latent_block` (the kernels interpreted, float32) against the
    reference's mixer over the normed input: the same sums in another order
    (the kernel's blocks), so outputs to 1e-5 and each leaf's gradient to
    1e-4 of its norm."""
    sz, flat, _ = seeded
    cfg = _config()
    p = _tree({k.split("/", 1)[1]: v for k, v in flat.items() if k.startswith("layers_1/")})
    x = jax.random.normal(jax.random.PRNGKey(1), (2, sz.seq_len, sz.hidden))
    g = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    own = lambda p, x: lm.latent_block(cfg.lm, p, x)
    plain = lambda p, x: ref.latent_attention(
        {"layers_1/" + k: v for k, v in _flat(p).items()}, "layers_1/", ref.rms_norm(x, p["attn_norm"]["scale"], sz.eps), sz, PLAIN
    )
    out, pull = jax.vjp(own, p, x)
    want, want_pull = jax.vjp(plain, p, x)
    assert _rel(out, want) < 1e-5
    (gp, gx), (wp, wx) = pull(g), want_pull(g)
    assert _rel(gx, wx) < 1e-4
    for name, w in _flat(wp).items():
        if name.startswith("attn"):
            assert _rel(_flat(gp)[name], w) < 1e-4, name


def test_the_mtp_loss_and_its_gradient_into_the_trunk_are_the_references(seeded):
    """The module's loss over a trunk output, and its gradient with respect
    to that output (what flows into the trunk) and to the shared embedding
    and head, against the reference's lines for the module."""
    sz, flat, batches = seeded
    cfg = _config()
    tokens = jnp.asarray(batches[0]["tokens"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, sz.seq_len, sz.hidden))
    bias = jnp.zeros((sz.experts,), jnp.float32)

    def own(p, x):
        return lm.mtp_loss(cfg.lm, _tree(p), {lm.MTP: bias}, x, tokens)[0]

    def plain(p, x):
        following = jnp.concatenate([tokens[:, 1:], jnp.zeros((2, 1), tokens.dtype)], axis=1)
        e = ref.rms_norm(p["embed/table"][following], p["mtp/enorm/scale"], sz.eps)
        h = ref._mm(jnp.concatenate([e, ref.rms_norm(x, p["mtp/hnorm/scale"], sz.eps)], axis=-1), p["mtp/eh_proj/kernel"], PLAIN)
        h, _ = ref.layer(p, "mtp/block/", False, bias, h, sz, PLAIN)
        return ref.head_loss(p, "mtp/final_norm/scale", h, tokens, 2, sz, PLAIN)

    (loss, (gp, gx)), (want, (wp, wx)) = (jax.value_and_grad(f, argnums=(0, 1))(flat, x) for f in (own, plain))
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    assert _rel(gx, wx) < 1e-4 and float(jnp.linalg.norm(gx)) > 0.0
    for name in ("embed/table", "head/kernel", "mtp/eh_proj/kernel", "mtp/block/attn/wkv_b", "mtp/final_norm/scale"):
        assert _rel(gp[name], wp[name]) < 1e-4, name
    assert float(jnp.max(jnp.abs(gp["layers_0/attn/wkv_b"]))) == 0.0  # the trunk's leaves only through x


def test_three_float32_steps_follow_the_reference(seeded):
    """Float32 program against the float32 reference: the same sums in
    another order (blocked attention, rows sorted by expert), so the losses
    agree to 2e-5 and the first gradient to 1e-4 of each leaf's norm; the
    balance biases (the MTP layer's among them) move as the reference's.
    Adam divides a gradient by its own magnitude, so a value near nought may
    move by a whole step of lr either way: parameters are held to a tenth of
    three such steps in the mean."""
    sz, flat, batches = seeded
    cfg = _config()
    tx, _ = make_optimizer(cfg, steps_per_epoch=100)
    params = _tree(flat)
    _, stats = lm.init(cfg, jax.random.PRNGKey(0))
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats=stats, opt_state=tx.init(params), rng=jax.random.PRNGKey(0),
    )
    step = jax.jit(make_train_step(None, cfg, tx))
    rparams, adam = flat, ref.init_adam(flat)
    rstep = jax.jit(lambda p, a, b, s: ref.train_step(p, a, b, None, s, sz))
    for i, batch in enumerate(batches):
        state, metrics = step(state, batch)
        rparams, adam, parts, grad = rstep(rparams, adam, batch, jnp.asarray(i, jnp.int32))
        assert float(metrics["tokens_dropped"]) == 0.0
        for key in ("loss", "nll_loss", "mtp_loss"):
            np.testing.assert_allclose(metrics[key], parts[key], rtol=2e-5, err_msg=key)
        assert abs(float(metrics["expert_assignments"]) - float(parts["expert_assignments"])) <= 2
        if i == 0:
            # Adam's first moment after one step is 0.1 x the gradient it got
            mu = _flat(next(p.mu for p in state.opt_state if hasattr(p, "mu")))
            for name, g in grad.items():
                assert _rel(mu[name] / 0.1, g) < 1e-4, name
    for name, b in adam["router_bias"].items():
        np.testing.assert_allclose(state.batch_stats["router_bias"][name], b, atol=1e-7, err_msg=name)
    for name, p in _flat(state.params).items():
        assert float(jnp.mean(jnp.abs(p - rparams[name]))) < 0.3 * cfg.train.lr, name


def test_the_shares_of_sixteen_chips_add_up_to_the_uncut_layer(seeded):
    """The tie of one chip's share to the model: the program's expert layer
    on each of the four shares of the tiny preset's 16 experts (4 held,
    `first_expert` 0, 4, 8, 12), added up, with what every share computes
    alike (the mixer, the shared expert, the residual) counted once, is the
    uncut reference layer that holds all 16."""
    sz, flat, _ = seeded
    whole = ref.Sizes(dict(SIZES, **{"lm.experts_held": 16}), 2)
    full = ref.init_params(whole, jax.random.PRNGKey(5))
    at = "layers_1/"
    x = jax.random.normal(jax.random.PRNGKey(6), (2, sz.seq_len, sz.hidden))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(8), (sz.experts,))
    uncut, _ = ref.layer(full, at, False, bias, x, whole, PLAIN)
    common = x + ref.latent_attention(full, at, ref.rms_norm(x, full[at + "attn_norm/scale"], sz.eps), whole, PLAIN)
    h = ref.rms_norm(common, full[at + "ffn_norm/scale"], sz.eps)
    common = common + ref.swiglu(h, full[at + "shared/w1"], full[at + "shared/w3"], full[at + "shared/w2"], PLAIN)
    total = -3.0 * common
    for first in (0, 4, 8, 12):
        cfg = _config(first_expert=first)
        own = {k[len(at):]: (v[first : first + 4] if "/experts/" in k else v) for k, v in full.items() if k.startswith(at)}
        y, _ = lm.layer(cfg.lm, 1, _tree(own), bias, x)
        total = total + y
    assert _rel(total, uncut) < 1e-5


@pytest.mark.parametrize("fixed,lean", [(2.0, 0.0), (2.0, 1.0), (3.875, 0.0)])
def test_the_whole_buffer_gives_what_the_routed_tiles_give(seeded, fixed, lean):
    """`experts_fixed_rows` only adds rows that weigh 0 to the last held
    expert's group: the expert layer's output and its gradients are those of
    the routed tiles alone, to the bit, at the usual buffer with one buffer
    and, with a bias that sends every pair to the held experts, with the
    second buffer too; with a larger buffer (the full preset's share) to
    float32 rounding of the weights' gradients."""
    sz, flat, _ = seeded
    at = "layers_1/"
    own = _tree({k[len(at):]: v for k, v in flat.items() if k.startswith(at)})
    h = jax.random.normal(jax.random.PRNGKey(9), (2 * sz.seq_len, sz.hidden))
    bias = jnp.where(jnp.arange(sz.experts) < sz.held, lean, 0.0)

    def run(fixed):
        cfg = _config(experts_fixed_rows=fixed)

        def f(p, h):
            y, stats = lm.expert_layer(cfg.lm, p, bias, h)
            return jnp.sum(y * y), stats

        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(own, h)

    (v0, stats), g0 = run(0.0)
    (v1, _), g1 = run(fixed)
    rows, _ = lm.buffer_rows(_config(experts_fixed_rows=0.0).lm, h.shape[0])
    assert lm.buffer_rows(_config().lm, h.shape[0]) == (rows, 2)
    assert (float(stats["assignments"]) > rows) == bool(lean)
    np.testing.assert_array_equal(v1, v0)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0)):
        if fixed == lm.USUAL_ROWS:
            np.testing.assert_array_equal(a, b)
        else:
            # a larger buffer takes another row tile (512 rows against 256), so the
            # weights' gradients add up their rows in another order: float32 rounding
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5 * float(jnp.max(jnp.abs(b))))


# ------------------------------------------------------- scopes, Trainer


class TestTheLatentPresetsStep:
    @pytest.fixture(scope="class")
    def names(self):
        import re

        from replication_faster_rcnn_tpu.train.train_step import create_train_state

        cfg = get_config("joyai_tiny")
        tx, _ = make_optimizer(cfg, steps_per_epoch=1)
        state = jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0), tx)[1])
        batch = {"tokens": jax.ShapeDtypeStruct((2, cfg.data.seq_len), jnp.int32)}
        text = jax.jit(make_train_step(None, cfg, tx)).lower(state, batch).as_text(debug_info=True)
        return set(re.findall(r'loc\("([^"]*/[^"]*)"\(', text))  # the operations' names, as tests/test_stage_scopes.py reads them

    # the delta-rule layer's two are in the hybrid preset's step: tests/test_lm_hybrid.py
    @pytest.mark.parametrize(
        "scope", [s for s in stages.LM_STAGES if s not in (stages.UPDATE, stages.LM_LINEAR_ATTENTION, stages.LM_DELTA_CORE)]
    )
    def test_every_stage_is_in_the_lowered_step_forward_and_backward(self, names, scope):
        held = [n for n in names if scope in n]
        assert [n for n in held if "transpose(" not in n], scope
        assert [n for n in held if "transpose(" in n], scope

    def test_the_latents_lie_inside_the_attention_and_the_mtp_layer_in_the_layers_scopes(self, names):
        assert any(n.rfind(stages.LM_LATENT) > n.find(stages.LM_ATTENTION) >= 0 for n in names)
        assert any(n.rfind(stages.LM_ATTN_CORE) > n.find(stages.LM_ATTENTION) >= 0 for n in names)
        assert any(n.rfind(stages.LM_LATENT) > n.find(stages.LM_MTP) >= 0 for n in names)
        assert any(n.rfind(stages.LM_EXPERT_MM) > n.find(stages.LM_MTP) >= 0 for n in names)


def test_trainer_trains_the_latent_preset_strictly_and_writes_the_mtp_loss(tmp_path):
    from replication_faster_rcnn_tpu.train import Trainer

    cfg = get_config("joyai_tiny")
    cfg = cfg.replace(debug=dataclasses.replace(cfg.debug, strict=True))
    trainer = Trainer(cfg, workdir=str(tmp_path / "w"), devices=jax.devices()[:1], telemetry_dir=str(tmp_path / "tel"))
    batches = iter(trainer.loader)
    with trainer.strict_session():
        metrics = [trainer.train_one_batch(batch=next(batches)) for _ in range(12)]
    trainer.flush_telemetry()
    rows = jax.device_get(metrics)
    assert all(np.isfinite(r["loss"]) and r["loss"] == pytest.approx(r["nll_loss"] + 0.1 * r["mtp_loss"], rel=1e-5) for r in rows)
    assert trainer.strict.report()["programs"]["train_step"]["recompiles_after_warmup"] == 0
    assert sorted(jax.device_get(trainer.state.batch_stats)["router_bias"]) == ["layers_1", "layers_2", "mtp"]
    with open(tmp_path / "tel" / "trace.json") as f:
        counters = {e["name"] for e in json.load(f)["traceEvents"] if e.get("ph") == "C" and e["name"].startswith("lm/")}
    assert counters == {f"lm/{k}" for k in lm.COUNTERS if not k.startswith("delta_")}
