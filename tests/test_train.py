"""Training tests: loss semantics vs hand calculations, the jitted step's
invariants, schedule shape, and the 2-image overfit check (SURVEY.md §4f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.config import (
    DataConfig,
    FasterRCNNConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from replication_faster_rcnn_tpu.data import SyntheticDataset
from replication_faster_rcnn_tpu.data.loader import collate
from replication_faster_rcnn_tpu.train import losses
from replication_faster_rcnn_tpu.train.train_step import (
    create_train_state,
    make_optimizer,
    make_train_step,
)


def _tiny_cfg(batch_size=2, **train_kw):
    return FasterRCNNConfig(
        model=ModelConfig(backbone="resnet18", roi_op="align", compute_dtype="float32"),
        data=DataConfig(dataset="synthetic", image_size=(64, 64), max_boxes=8),
        train=TrainConfig(batch_size=batch_size, n_epoch=4, **train_kw),
        mesh=MeshConfig(num_data=1),
    )


class TestLosses:
    def test_smooth_l1_knee(self):
        # sigma=1: quadratic below 1, linear above (reference train.py:43-52)
        x = jnp.asarray([0.0, 0.5, 1.0, 3.0])
        y = losses.smooth_l1(x, jnp.zeros(4), sigma=1.0)
        np.testing.assert_allclose(np.asarray(y), [0.0, 0.125, 0.5, 2.5])

    def test_smooth_l1_sigma3(self):
        # sigma=3 (py-faster-rcnn RPN choice): knee at 1/9
        x = jnp.asarray([0.05, 0.5])
        y = losses.smooth_l1(x, jnp.zeros(2), sigma=3.0)
        np.testing.assert_allclose(
            np.asarray(y), [0.5 * 9 * 0.05**2, 0.5 - 0.5 / 9], rtol=1e-6
        )

    def test_loc_loss_positive_only_and_normalized(self):
        pred = jnp.asarray([[1.0, 0, 0, 0], [2.0, 0, 0, 0], [9.0, 0, 0, 0]])
        target = jnp.zeros((3, 4))
        labels = jnp.asarray([1, 1, 0])  # third is negative: excluded
        # per-sample smooth-l1 sums: 0.5, 1.5 ; / n_pos=2
        out = losses.loc_loss(pred, target, labels)
        np.testing.assert_allclose(float(out), (0.5 + 1.5) / 2)

    def test_loc_loss_no_positives_is_zero(self):
        out = losses.loc_loss(
            jnp.ones((4, 4)), jnp.zeros((4, 4)), jnp.zeros(4, jnp.int32)
        )
        np.testing.assert_allclose(float(out), 0.0)

    def test_ignore_cross_entropy(self):
        logits = jnp.asarray([[10.0, 0.0], [0.0, 10.0], [5.0, 5.0]])
        labels = jnp.asarray([0, 1, -1])  # last ignored
        out = float(losses.ignore_cross_entropy(logits, labels))
        assert out < 1e-3  # two confident correct, ignored excluded

    def test_ignore_cross_entropy_all_ignored(self):
        out = losses.ignore_cross_entropy(
            jnp.ones((3, 2)), jnp.full(3, -1, jnp.int32)
        )
        assert np.isfinite(float(out)) and float(out) == 0.0

    # value AND gradient against optax's integer-label CE, the writing before
    # PR 28 (tests/oracles.py): op by op, where XLA:CPU contracts nothing, the
    # two agree to the bit (inside one jitted fusion the select's backward
    # gets a fused multiply-add, a last-place difference)
    @pytest.mark.parametrize(
        "classes,ignored", [(2, 0.6), (21, 0.3), (2, 1.0)], ids=["rpn_c2", "head_c21", "all_ignored"]
    )
    def test_ignore_cross_entropy_equals_optax_value_and_gradient(self, classes, ignored):
        from tests import oracles

        rng = np.random.default_rng(classes)
        logits = rng.normal(0, 3, (3, 40, classes)).astype(np.float32)
        logits[0, 0, 0] = -np.inf  # another class's infinite logit makes no NaN (0 * inf would)
        labels = rng.integers(0, classes, (3, 40))
        labels[rng.uniform(size=labels.shape) < ignored] = -1
        if ignored < 1.0:
            labels[0, 0] = classes - 1  # that row counts, under another label
        logits, labels = jnp.asarray(logits), jnp.asarray(labels.astype(np.int32))
        value, grad = jax.value_and_grad(losses.ignore_cross_entropy)(logits, labels)
        want, want_grad = jax.value_and_grad(oracles.ignore_cross_entropy_optax)(logits, labels)
        np.testing.assert_array_equal(np.asarray(value), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(grad), np.asarray(want_grad))

    def test_ignore_cross_entropy_lowers_without_gather_or_scatter(self):
        # the engagement check: the label pick is a select both ways
        from tests import oracles

        logits, labels = jnp.zeros((2, 30, 2)), jnp.zeros((2, 30), jnp.int32)
        new, old = (
            jax.jit(jax.value_and_grad(f)).lower(logits, labels).as_text()
            for f in (losses.ignore_cross_entropy, oracles.ignore_cross_entropy_optax)
        )
        assert oracles.largest_gather(new) == 0 and "stablehlo.scatter" not in new
        assert oracles.largest_gather(old) == 60 and "stablehlo.scatter" in old


class TestSchedule:
    def test_epoch_granular_cosine(self):
        cfg = _tiny_cfg()
        _, sched = make_optimizer(cfg, steps_per_epoch=10)
        lr0 = float(sched(0))
        assert lr0 == pytest.approx(cfg.train.lr)
        # constant within an epoch (reference scheduler.step() per epoch)
        assert float(sched(9)) == pytest.approx(lr0)
        assert float(sched(10)) < lr0
        # cosine reaches ~0 at n_epoch
        assert float(sched(10 * cfg.train.n_epoch)) == pytest.approx(0.0, abs=1e-8)

    def test_linear_lr_scaling_and_warmup(self):
        """The large-batch recipe: lr_scaling='linear' scales the cosine
        peak by batch/base_batch, and warmup_epochs ramps linearly up to
        that peak before the cosine takes over."""
        cfg = _tiny_cfg(
            8, lr_scaling="linear", base_batch_size=2, warmup_epochs=1.0
        )
        _, sched = make_optimizer(cfg, steps_per_epoch=10)
        peak = cfg.train.lr * 8 / 2
        # ramp: (step+1)/warmup_steps of the scaled peak
        assert float(sched(0)) == pytest.approx(peak / 10)
        assert float(sched(4)) == pytest.approx(peak / 2)
        assert float(sched(9)) == pytest.approx(peak)
        # after warmup the epoch-granular cosine runs at the scaled peak
        assert float(sched(10)) == pytest.approx(
            peak * 0.5 * (1 + np.cos(np.pi / cfg.train.n_epoch))
        )

    def test_host_schedule_matches_jnp_schedule(self):
        """host_schedule is the pure-Python twin the log path evaluates;
        any drift from the jnp schedule silently logs the wrong lr."""
        from replication_faster_rcnn_tpu.train.train_step import host_schedule

        for kw in (
            {},
            dict(lr_scaling="linear", base_batch_size=4, warmup_epochs=0.5),
            dict(warmup_epochs=2.0),
        ):
            cfg = _tiny_cfg(8, **kw)
            _, sched = make_optimizer(cfg, steps_per_epoch=6)
            host = host_schedule(cfg, steps_per_epoch=6)
            for step in range(6 * cfg.train.n_epoch + 2):
                np.testing.assert_allclose(
                    host(step), float(sched(step)), rtol=1e-6,
                    err_msg=f"step {step} with {kw}",
                )

    def test_lars_trust_ratio_bounds_update(self):
        """train.lars appends LAMB-style trust-ratio scaling after Adam:
        the per-leaf update norm becomes lr * |param| regardless of the
        raw gradient scale."""
        cfg = _tiny_cfg(2, lars=True)
        tx, _ = make_optimizer(cfg, steps_per_epoch=10)
        params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((3,))}
        opt_state = tx.init(params)
        grads = {"w": jnp.full((4, 4), 100.0), "b": jnp.ones((3,))}
        updates, _ = tx.update(grads, opt_state, params)
        w_ratio = float(
            jnp.linalg.norm(updates["w"]) / jnp.linalg.norm(params["w"])
        )
        assert w_ratio == pytest.approx(cfg.train.lr, rel=1e-4)
        # a zero-norm leaf must not produce NaNs (optax safe-norm path)
        assert np.all(np.isfinite(np.asarray(updates["b"])))

    def test_invalid_lr_scaling_rejected(self):
        with pytest.raises(ValueError, match="lr_scaling"):
            _tiny_cfg(2, lr_scaling="sqrt")


@pytest.fixture(scope="module")
def step_setup():
    cfg = _tiny_cfg()
    tx, _ = make_optimizer(cfg, steps_per_epoch=10)
    model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
    step = jax.jit(make_train_step(model, cfg, tx))
    ds = SyntheticDataset(cfg.data, length=2)
    batch = collate([ds[0], ds[1]])
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    return cfg, model, state, step, batch


class TestTrainStep:
    @pytest.mark.slow
    def test_vgg16_step_with_dropout_rng(self):
        # the VGG16 tail's dropout draws a 'dropout' rng inside the jitted
        # step; trimmed budgets keep the fc6 matmul small on CPU
        from replication_faster_rcnn_tpu.config import ProposalConfig, ROITargetConfig

        cfg = _tiny_cfg().replace(
            model=ModelConfig(backbone="vgg16", roi_op="pool", compute_dtype="float32"),
            proposals=ProposalConfig(pre_nms_train=128, post_nms_train=32),
            roi_targets=ROITargetConfig(n_sample=8),
        )
        tx, _ = make_optimizer(cfg, steps_per_epoch=10)
        model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
        step = jax.jit(make_train_step(model, cfg, tx))
        ds = SyntheticDataset(cfg.data, length=2)
        batch = {k: jnp.asarray(v) for k, v in collate([ds[0], ds[1]]).items()}
        new_state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert int(new_state.step) == 1

    def test_metrics_finite_and_params_update(self, step_setup):
        cfg, model, state, step, batch = step_setup
        new_state, metrics = step(state, batch)
        vals = {k: float(v) for k, v in jax.device_get(metrics).items()}
        assert all(np.isfinite(v) for v in vals.values()), vals
        assert vals["loss"] > 0
        assert int(new_state.step) == 1
        # params actually moved
        leaf = jax.tree_util.tree_leaves(state.params)[0]
        new_leaf = jax.tree_util.tree_leaves(new_state.params)[0]
        assert not np.allclose(np.asarray(leaf), np.asarray(new_leaf))

    def test_batch_stats_update(self, step_setup):
        cfg, model, state, step, batch = step_setup
        new_state, _ = step(state, batch)
        old = jax.tree_util.tree_leaves(state.batch_stats)[0]
        new = jax.tree_util.tree_leaves(new_state.batch_stats)[0]
        assert not np.allclose(np.asarray(old), np.asarray(new))

    def test_deterministic_given_state(self, step_setup):
        cfg, model, state, step, batch = step_setup
        _, m1 = step(state, batch)
        _, m2 = step(state, batch)
        assert float(m1["loss"]) == float(m2["loss"])

    @pytest.mark.slow
    def test_remat_preserves_step_semantics(self, step_setup):
        """model.remat=True (per-block jax.checkpoint) must leave the
        parameter tree and the computed update unchanged — it only trades
        backward-pass FLOPs for activation memory."""
        import dataclasses

        cfg, model, state, step, batch = step_setup
        rcfg = cfg.replace(model=dataclasses.replace(cfg.model, remat=True))
        tx, _ = make_optimizer(rcfg, steps_per_epoch=10)
        rmodel, rstate = create_train_state(rcfg, jax.random.PRNGKey(0), tx)
        assert (
            jax.tree_util.tree_structure(rstate.params)
            == jax.tree_util.tree_structure(state.params)
        )
        rstep = jax.jit(make_train_step(rmodel, rcfg, tx))
        new_state, metrics = step(state, batch)
        rnew_state, rmetrics = rstep(rstate, batch)
        np.testing.assert_allclose(
            float(metrics["loss"]), float(rmetrics["loss"]), rtol=1e-6
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(new_state.params),
            jax.tree_util.tree_leaves(rnew_state.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )

    @pytest.mark.slow
    def test_bf16_mu_matches_f32_update_approximately(self, step_setup):
        """train.adam_mu_dtype=bfloat16 stores Adam's first moment in
        bf16 (half the moment traffic in the update phase); the computed
        update must stay close to the f32 run — bf16 has ~3 decimal
        digits, so the per-step divergence is bounded, not bit-zero."""
        import dataclasses

        cfg, model, state, step, batch = step_setup
        bcfg = cfg.replace(
            train=dataclasses.replace(cfg.train, adam_mu_dtype="bfloat16")
        )
        tx, _ = make_optimizer(bcfg, steps_per_epoch=10)
        bmodel, bstate = create_train_state(bcfg, jax.random.PRNGKey(0), tx)
        bstep = jax.jit(make_train_step(bmodel, bcfg, tx))
        new_state, _ = step(state, batch)
        bnew_state, bmetrics = bstep(bstate, batch)
        assert np.isfinite(float(bmetrics["loss"]))
        # the stored mu really is bf16
        mu_leaves = jax.tree_util.tree_leaves(bnew_state.opt_state)
        assert any(a.dtype == jnp.bfloat16 for a in mu_leaves)
        # compare the applied UPDATES, not the params (the first-step
        # update magnitude is ~lr, so a params-level atol near lr would
        # accept a zeroed update): deltas must be nonzero and agree to
        # bf16 mantissa precision (~0.4% relative)
        moved = 0.0
        for p0, p32, pbf in zip(
            jax.tree_util.tree_leaves(state.params),
            jax.tree_util.tree_leaves(new_state.params),
            jax.tree_util.tree_leaves(bnew_state.params),
        ):
            d32 = np.asarray(p32) - np.asarray(p0)
            dbf = np.asarray(pbf) - np.asarray(p0)
            moved = max(moved, float(np.abs(d32).max()))
            np.testing.assert_allclose(dbf, d32, rtol=2e-2, atol=2e-6)
        assert moved > 1e-5, f"f32 step barely moved params ({moved})"

    @pytest.mark.slow
    def test_overfit_two_images(self, step_setup):
        """Loss must drop substantially when repeating one tiny batch
        (SURVEY.md §4f overfit integration check, shortened for CI)."""
        cfg, model, state, step, batch = step_setup
        first = None
        for _ in range(12):
            state, metrics = step(state, batch)
            loss = float(metrics["loss"])
            if first is None:
                first = loss
        assert loss < 0.7 * first, (first, loss)


class TestLayerCostTable:
    def _load(self):
        import importlib.util
        import pathlib

        script = (
            pathlib.Path(__file__).resolve().parents[1]
            / "benchmarks"
            / "layer_cost_table.py"
        )
        spec = importlib.util.spec_from_file_location("layer_cost", script)
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        return m

    def test_tiling_eff(self):
        m = self._load()
        assert m._eff(128, 128) == 1.0
        assert m._eff(576, 64) == pytest.approx((576 / 640) * 0.5)
        assert m._eff(147, 64) == pytest.approx((147 / 256) * 0.5)

    def test_collect_and_analyze_tiny(self, tmp_path, monkeypatch):
        m = self._load()
        monkeypatch.setattr(m, "OUT", str(tmp_path / "t.json"))
        monkeypatch.setattr(
            "sys.argv",
            ["layer_cost_table.py", "--batch-size", "2",
             "--image-size", "64", "64", "--measured-step-ms", "10"],
        )
        m.main()
        import json as _json

        out = _json.load(open(tmp_path / "t.json"))
        agg = out["aggregate"]
        # resnet18 trunk 15 convs + RPN 3 + head 5 = 23 regardless of shape
        assert agg["n_convs"] == 23
        assert 0 < agg["best_achievable_conv_mfu"] <= 1
        assert agg["compute_floor_ms_at_tiling_ceiling"] >= agg[
            "compute_floor_ms_at_peak"
        ]
        # every row's ceilings are valid fractions; stem dgrad skipped
        assert out["convs"][0]["dgrad_skipped"]
        for r in out["convs"]:
            for k in ("eff_fwd", "eff_dgrad", "eff_wgrad"):
                assert 0 < r[k] <= 1


class TestFrozenBN:
    """model.frozen_bn=True — BN runs on stored stats even in train mode
    (torchvision-detection FrozenBatchNorm2d convention)."""

    def _setup(self, frozen):
        import dataclasses

        cfg = _tiny_cfg()
        cfg = cfg.replace(
            model=dataclasses.replace(cfg.model, frozen_bn=frozen)
        )
        tx, _ = make_optimizer(cfg, steps_per_epoch=10)
        model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
        ds = SyntheticDataset(cfg.data, length=2)
        batch = {k: jnp.asarray(v) for k, v in collate([ds[0], ds[1]]).items()}
        return cfg, model, state, batch, tx

    def test_batch_stats_frozen_params_move(self):
        cfg, model, state, batch, tx = self._setup(True)
        step = jax.jit(make_train_step(model, cfg, tx))
        new_state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        for old, new in zip(
            jax.tree_util.tree_leaves(state.batch_stats),
            jax.tree_util.tree_leaves(new_state.batch_stats),
        ):
            np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
        # the affine (and everything else) still trains
        leaf = jax.tree_util.tree_leaves(state.params)[0]
        new_leaf = jax.tree_util.tree_leaves(new_state.params)[0]
        assert not np.allclose(np.asarray(leaf), np.asarray(new_leaf))

    def test_train_forward_equals_eval_forward(self):
        # with frozen stats the trunk is mode-independent (no dropout in
        # the ResNet trunk), so train and eval features must be identical
        cfg, model, state, batch, _ = self._setup(True)
        v = {"params": state.params, "batch_stats": state.batch_stats}
        f_train, _ = model.apply(
            v, batch["image"], True, method="extract_features",
            mutable=["batch_stats"],
        )
        f_eval = model.apply(v, batch["image"], False, method="extract_features")
        np.testing.assert_array_equal(np.asarray(f_train), np.asarray(f_eval))

    def test_unfrozen_still_updates_stats(self):
        cfg, model, state, batch, tx = self._setup(False)
        step = jax.jit(make_train_step(model, cfg, tx))
        new_state, _ = step(state, batch)
        old = jax.tree_util.tree_leaves(state.batch_stats)[0]
        new = jax.tree_util.tree_leaves(new_state.batch_stats)[0]
        assert not np.allclose(np.asarray(old), np.asarray(new))
