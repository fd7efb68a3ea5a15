"""Aux-subsystem tests: profiling timer, NaN guards, facade API surface."""

import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.utils import debug, profiling


class TestProfiling:
    def test_trace_writes_dir(self, tmp_path):
        d = str(tmp_path / "trace")
        with profiling.trace(d):
            jnp.asarray([1.0]) + 1
        import os

        assert os.path.isdir(d)


class TestDebug:
    def test_assert_tree_finite_passes(self):
        debug.assert_tree_finite({"a": jnp.ones(3)}, "ok")

    def test_assert_tree_finite_raises(self):
        with pytest.raises(FloatingPointError, match="bad"):
            debug.assert_tree_finite({"a": jnp.asarray([1.0, np.nan])}, "bad")

    def test_finite_or_raise(self):
        vals = debug.finite_or_raise({"loss": jnp.asarray(1.0)}, 0)
        assert vals == {"loss": 1.0}
        with pytest.raises(FloatingPointError, match="step 7"):
            debug.finite_or_raise({"loss": jnp.asarray(np.inf)}, 7)


class TestFacade:
    def test_reference_api_surface(self):
        from replication_faster_rcnn_tpu.frcnn import FRCNN

        f = FRCNN("train")
        for name in ("get_data_loader", "get_network", "load_param", "save_param", "train"):
            assert callable(getattr(f, name))
        with pytest.raises(ValueError):
            FRCNN("predict")

    def test_get_network_and_loader(self):
        from replication_faster_rcnn_tpu.config import DataConfig, ModelConfig, get_config
        from replication_faster_rcnn_tpu.frcnn import FRCNN

        cfg = get_config("voc_resnet18").replace(
            data=DataConfig(dataset="synthetic", image_size=(64, 64), max_boxes=8),
            model=ModelConfig(compute_dtype="float32"),
        )
        f = FRCNN("train", config=cfg)
        model, variables = f.get_network()
        assert "params" in variables
        loader = f.get_data_loader(batch_size=2)
        batch = next(iter(loader))
        assert batch["image"].shape == (2, 64, 64, 3)
