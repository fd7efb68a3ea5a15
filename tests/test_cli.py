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


def _bench_cfg(argv):
    return cli._build_config(_args(argv))


class TestBenchSuccess:
    """`cli bench` itself needs a chip (TestBenchNeedsAChip); the record
    the measurement functions build is checked here at a tiny size."""

    @pytest.mark.slow
    def test_measure_train_record(self):
        from replication_faster_rcnn_tpu import benchmark

        line = benchmark.measure_train(
            _bench_cfg(["--image-size", "64", "--batch-size", "8"])
        )
        assert line["metric"] == "train_images_per_sec_64x64"
        assert line["value"] > 0
        assert "error" not in line
        # the record carries the step's FLOPs and a per-stage wall-time
        # attribution; off-TPU the peak comes from the measured-matmul
        # basis and is labelled as such
        assert line["flops_per_step"] > 0
        assert line["mfu"] is not None and line["mfu"] > 0
        assert line["mfu_basis"] == "cpu_measured_matmul"
        bd = line["breakdown"]
        assert bd["trunk_ms"] > 0 and bd["step_ms"] > 0
        required = {
            "trunk_ms", "rpn_heads_ms", "proposal_nms_ms",
            "targets_ms", "head_loss_ms",
            "targets_head_loss_ms", "backward_ms", "opt_update_ms",
            "backward_update_ms", "step_ms",
        }
        # the direct optimizer-update row and its dispatch-floor
        # companions accompany the core keys (a failed row fails the run)
        assert set(bd) - required == {
            "opt_update_direct_ms", "dispatch_floor_ms",
            "opt_update_direct_adj_ms",
        }
        # the split must account for the lump it replaces
        assert bd["backward_update_ms"] == pytest.approx(
            bd["backward_ms"] + bd["opt_update_ms"], abs=0.05
        )

    @pytest.mark.slow
    def test_measure_eval_record(self):
        """The eval measurement covers the inference path (forward +
        decode + per-class NMS) and reports no baseline ratio (the
        reference has no eval path to race — SURVEY.md §2.1 #15)."""
        from replication_faster_rcnn_tpu import benchmark

        # no BENCH_EVAL_BATCH: exercise the second precedence tier (the
        # CLI config's train.batch_size feeds the eval batch)
        line = benchmark.measure_eval(
            _bench_cfg(["--image-size", "64", "--batch-size", "2"])
        )
        assert line["metric"] == "eval_images_per_sec_64x64"
        assert line["value"] > 0
        assert line["vs_baseline"] is None
        assert "error" not in line


class TestBenchMeshValidation:
    """ADVICE r1 #3: bad --num-model must fail fast with a descriptive
    error, not an opaque mesh reshape failure (or silent device drop)."""

    def test_num_model_exceeding_devices(self):
        from replication_faster_rcnn_tpu import benchmark

        with pytest.raises(ValueError, match="exceeds the 8 available"):
            benchmark.measure_train(_bench_cfg(
                ["--num-model", "16", "--image-size", "64", "--batch-size", "8"]
            ))

    def test_num_model_not_dividing_devices(self):
        from replication_faster_rcnn_tpu import benchmark

        with pytest.raises(ValueError, match="split evenly"):
            benchmark.measure_train(_bench_cfg(
                ["--num-model", "3", "--image-size", "64", "--batch-size", "8"]
            ))


class TestBenchNeedsAChip:
    """The bench has no path that measures on the CPU instead: with no
    accelerator it exits non-zero and prints no metric line."""

    def test_cli_bench_without_a_chip_exits_nonzero_and_prints_no_metric(
        self, capsys
    ):
        with pytest.raises(SystemExit) as exc:
            cli.main(["bench", "--image-size", "64", "--batch-size", "8"])
        assert exc.value.code not in (0, None)
        assert "no accelerator" in str(exc.value.code)
        assert "cpu" in str(exc.value.code)  # says what it found
        assert "metric" not in capsys.readouterr().out

    def test_bench_py_without_a_chip_exits_nonzero_and_prints_no_metric(self):
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, "bench.py"], cwd=repo, capture_output=True,
            text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert r.returncode != 0
        assert r.stdout.strip() == ""  # no JSON line, no number
        assert "no accelerator" in r.stderr


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
