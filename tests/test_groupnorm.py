"""GroupNorm backbone option (`ModelConfig.norm="group"`): the BN-free
structural lever (GN removes train-mode BN's batch-stats reductions
entirely). Reference parity note: the reference is
BN-only (`nets/resnet_torch.py`); GN is a deliberate TPU-side extension.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from replication_faster_rcnn_tpu.config import ModelConfig, get_config


def _gn_config(preset="voc_resnet18", image_size=(64, 64), batch=2):
    cfg = get_config(preset)
    return cfg.replace(
        data=dataclasses.replace(
            cfg.data, dataset="synthetic", image_size=image_size
        ),
        train=dataclasses.replace(cfg.train, batch_size=batch),
        model=dataclasses.replace(cfg.model, norm="group"),
    )


class TestConfigValidation:
    def test_bad_norm_rejected(self):
        with pytest.raises(ValueError, match="norm must be"):
            ModelConfig(norm="layer")

    def test_frozen_bn_with_group_rejected(self):
        with pytest.raises(ValueError, match="meaningless"):
            ModelConfig(norm="group", frozen_bn=True)

    def test_bn_axis_with_group_rejected(self):
        with pytest.raises(ValueError, match="needs no axis"):
            ModelConfig(norm="group", bn_axis="data")

    def test_cli_norm_flag_plumbs(self):
        import argparse

        from replication_faster_rcnn_tpu import cli

        parser = argparse.ArgumentParser()
        cli._add_common(parser)
        cfg = cli._build_config(parser.parse_args(["--norm", "group"]))
        assert cfg.model.norm == "group"


class TestParamTree:
    def test_no_batch_stats_and_affine_at_bn_sites(self):
        from replication_faster_rcnn_tpu.train import (
            create_train_state,
            make_optimizer,
        )

        cfg = _gn_config()
        tx, _ = make_optimizer(cfg, 10)
        _, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
        # GN carries no running statistics
        assert not jax.tree_util.tree_leaves(state.batch_stats)
        # the BN-site names persist, holding the GN affine
        bn1 = state.params["trunk"]["bn1"]
        assert sorted(bn1.keys()) == ["bias", "scale"]

    def test_pretrained_graft_rejected_on_gn_model(self, tmp_path):
        """A torch BN checkpoint would graft silently onto the same-named
        GN affine params; the converter must fail fast instead."""
        from replication_faster_rcnn_tpu.models import convert
        from replication_faster_rcnn_tpu.train import (
            create_train_state,
            make_optimizer,
        )

        cfg = _gn_config()
        tx, _ = make_optimizer(cfg, 10)
        _, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
        variables = {
            "params": jax.device_get(state.params),
            "batch_stats": jax.device_get(state.batch_stats),
        }
        with pytest.raises(ValueError, match="GroupNorm"):
            convert.graft_into_variables(
                variables, str(tmp_path / "never_read.pth")
            )

    def test_gn_pretrain_grafts_and_bn_mismatch_raises(self):
        """The GN pretraining escape hatch must actually work end-to-end
        (make_classifier(norm='group') -> graft_classifier), and a
        BN-pretrained classifier must be rejected by the norm-mismatch
        guard instead of silently merging onto the GN detector."""
        from replication_faster_rcnn_tpu.train import (
            create_train_state,
            make_optimizer,
        )
        from replication_faster_rcnn_tpu.train.pretrain import (
            graft_classifier,
            make_classifier,
        )

        cfg = _gn_config()
        tx, _ = make_optimizer(cfg, 10)
        _, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
        det_vars = {
            "params": state.params,
            "batch_stats": state.batch_stats,
        }

        gn_cls = make_classifier(norm="group")
        x = jnp.zeros((1, 32, 32, 3), jnp.float32)
        gn_vars = gn_cls.init({"params": jax.random.PRNGKey(1)}, x, train=False)
        gn_vars = {
            "params": gn_vars["params"],
            "batch_stats": gn_vars.get("batch_stats", {}),
        }
        assert not jax.tree_util.tree_leaves(gn_vars["batch_stats"])
        grafted = graft_classifier(det_vars, gn_vars)
        # same structure class as before: the train state stays valid
        assert sorted(grafted["params"]["trunk"]["bn1"].keys()) == [
            "bias", "scale",
        ]

        bn_cls = make_classifier(norm="batch")
        bn_vars = bn_cls.init({"params": jax.random.PRNGKey(2)}, x, train=False)
        with pytest.raises(ValueError, match="normalization mismatch"):
            graft_classifier(det_vars, dict(bn_vars))

    def test_spmd_builder_skips_bn_axis_for_group(self):
        """make_shard_map_train_step must not bind a sync-BN axis on a GN
        model (the config layer rejects the combination)."""
        from replication_faster_rcnn_tpu.parallel.mesh import make_mesh
        from replication_faster_rcnn_tpu.parallel.spmd import (
            make_shard_map_train_step,
        )
        from replication_faster_rcnn_tpu.train import make_optimizer

        cfg = _gn_config()
        tx, _ = make_optimizer(cfg, 10)
        mesh = make_mesh(cfg.mesh)
        _, model = make_shard_map_train_step(cfg, tx, mesh)
        assert model.config.model.bn_axis is None
        assert model.config.model.norm == "group"


class TestTrainAndEval:
    @pytest.mark.slow
    def test_train_step_runs_and_is_finite(self):
        from replication_faster_rcnn_tpu.data import SyntheticDataset
        from replication_faster_rcnn_tpu.data.loader import collate
        from replication_faster_rcnn_tpu.train import (
            create_train_state,
            make_optimizer,
        )
        from replication_faster_rcnn_tpu.train.train_step import make_train_step

        cfg = _gn_config()
        tx, _ = make_optimizer(cfg, 10)
        model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
        ds = SyntheticDataset(cfg.data, length=2)
        batch = jax.tree_util.tree_map(
            jnp.asarray, collate([ds[0], ds[1]])
        )
        step = jax.jit(make_train_step(model, cfg, tx), donate_argnums=(0,))
        for _ in range(2):
            state, metrics = step(state, batch)
        assert jnp.isfinite(metrics["loss"])
        assert jnp.isfinite(metrics["grad_norm"])
        # still no mutable statistics after stepping
        assert not jax.tree_util.tree_leaves(state.batch_stats)
