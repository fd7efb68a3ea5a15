"""CLI wiring tests: flag -> config plumbing and the bounded-step train
smoke (the reference has no CLI at all — SURVEY.md §5 config/flag system)."""

import numpy as np
import pytest

from replication_faster_rcnn_tpu import cli


def _args(argv):
    import argparse

    parser = argparse.ArgumentParser()
    cli._add_common(parser)
    return parser.parse_args(argv)


class TestConfigPlumbing:
    def test_defaults_pick_flagship_preset(self):
        cfg = cli._build_config(_args([]))
        assert cfg.model.backbone == "resnet18"
        assert cfg.train.backend == "auto"
        # VOC presets flip by default (round 4, measured +12 val mAP pts)
        assert cfg.data.augment_hflip is True

    def test_no_augment_hflip_disables_preset_default(self):
        cfg = cli._build_config(_args(["--no-augment-hflip"]))
        assert cfg.data.augment_hflip is False

    def test_flags_override_preset(self):
        cfg = cli._build_config(
            _args(
                [
                    "--backbone", "resnext50_32x4d",
                    "--roi-op", "align",
                    "--batch-size", "4",
                    "--lr", "0.001",
                    "--backend", "spmd",
                    "--image-size", "128",
                ]
            )
        )
        assert cfg.model.backbone == "resnext50_32x4d"
        assert cfg.train.batch_size == 4
        assert cfg.train.lr == 0.001
        assert cfg.train.backend == "spmd"
        assert cfg.data.image_size == (128, 128)

    def test_vgg16_backbone_flag(self):
        cfg = cli._build_config(_args(["--backbone", "vgg16"]))
        assert cfg.model.backbone == "vgg16"
        assert cfg.model.head_channels == 4096

    def test_unknown_preset_fails(self):
        with pytest.raises(KeyError):
            cli._build_config(_args(["--config", "nope"]))


class TestEvalSmoke:
    @pytest.mark.slow
    def test_eval_per_class_table(self, tmp_path, capsys):
        rc = cli.main(
            [
                "eval",
                "--dataset", "synthetic",
                "--image-size", "64",
                "--batch-size", "2",
                "--max-images", "2",
                "--per-class",
                "--workdir", str(tmp_path),  # no checkpoint: fresh init
            ]
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "mAP@0.5" in out
        assert "aeroplane" in out  # per-class table rendered with VOC names


class TestTrainSmoke:
    @pytest.mark.slow
    def test_bounded_steps(self, tmp_path, capsys):
        rc = cli.main(
            [
                "train",
                "--dataset", "synthetic",
                "--image-size", "64",
                "--batch-size", "2",  # mesh auto-fits to batch (data axis 2)
                "--steps", "2",
                "--log-every", "1",
                "--workdir", str(tmp_path),
            ]
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "loss=" in out
        # loss stays finite over the smoke steps
        losses = [
            float(tok.split("=")[1])
            for line in out.splitlines()
            for tok in line.split()
            if tok.startswith("loss=")
        ]
        assert losses and all(np.isfinite(v) for v in losses)
