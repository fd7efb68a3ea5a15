"""The `ops.backend` dispatch seam (ISSUE 13): resolution order
(scope > env-read-once > config > xla), OpsConfig validation, the
FRCNN_NMS / FRCNN_PALLAS_NMS rewiring onto the rebuilt pallas backend,
and the warmup registry's `__pallas` twin naming."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu import ops as ops_pkg
from replication_faster_rcnn_tpu.config import FasterRCNNConfig, OpsConfig
from replication_faster_rcnn_tpu.ops.nms import _tile_from_env, nms_fixed_auto
from replication_faster_rcnn_tpu.ops.nms_tiled import nms_fixed_tiled
from tests.test_boxes import rand_boxes

pytestmark = pytest.mark.pallas_interpret


class TestResolutionOrder:
    def test_default_is_xla(self):
        assert ops_pkg.resolve_backend() == "xla"
        assert ops_pkg.resolve_backend(FasterRCNNConfig()) == "xla"

    def test_config_backend_honored(self):
        cfg = FasterRCNNConfig(ops=OpsConfig(backend="pallas"))
        assert ops_pkg.resolve_backend(cfg) == "pallas"
        assert ops_pkg.want_pallas("nms", cfg)

    def test_scope_wins_over_config(self):
        cfg = FasterRCNNConfig(ops=OpsConfig(backend="pallas"))
        with ops_pkg.backend_scope("xla"):
            assert ops_pkg.resolve_backend(cfg) == "xla"
        assert ops_pkg.resolve_backend(cfg) == "pallas"

    def test_scopes_nest(self):
        with ops_pkg.backend_scope("pallas"):
            assert ops_pkg.resolve_backend() == "pallas"
            with ops_pkg.backend_scope("xla"):
                assert ops_pkg.resolve_backend() == "xla"
            assert ops_pkg.resolve_backend() == "pallas"
        assert ops_pkg.resolve_backend() == "xla"

    def test_scope_rejects_unknown_backend(self):
        with pytest.raises(ValueError, match="backend must be one of"):
            ops_pkg.backend_scope("cuda")

    def test_env_wins_over_config_and_is_read_once(self, monkeypatch):
        monkeypatch.setattr(ops_pkg, "_env_backend", None)
        monkeypatch.setenv("FRCNN_OPS_BACKEND", "pallas")
        assert ops_pkg.resolve_backend() == "pallas"
        # flipping the env mid-process must NOT flip the resolved backend
        monkeypatch.setenv("FRCNN_OPS_BACKEND", "xla")
        assert ops_pkg.resolve_backend() == "pallas"
        # but a scope still overrides the cached env value
        with ops_pkg.backend_scope("xla"):
            assert ops_pkg.resolve_backend() == "xla"

    def test_invalid_env_warns_and_is_ignored(self, monkeypatch):
        monkeypatch.setattr(ops_pkg, "_env_backend", None)
        monkeypatch.setattr(ops_pkg, "_warned", set())
        monkeypatch.setenv("FRCNN_OPS_BACKEND", "cuda")
        with pytest.warns(UserWarning, match="is not one of"):
            assert ops_pkg.resolve_backend() == "xla"

    def test_interpret_mode_on_cpu(self):
        assert ops_pkg.interpret_mode() is True  # conftest pins CPU

    def test_pallas_chosen_but_kernels_do_not_import_raises(self, monkeypatch):
        """Choosing pallas and not getting it is an error — not an XLA
        program under a pallas name."""
        import sys

        from replication_faster_rcnn_tpu.ops.nms import nms_fixed_auto

        # a None entry makes `import ...ops.pallas` raise ImportError
        monkeypatch.setitem(
            sys.modules, "replication_faster_rcnn_tpu.ops.pallas", None
        )
        monkeypatch.delattr(ops_pkg, "pallas", raising=False)
        cfg = FasterRCNNConfig(ops=OpsConfig(backend="pallas"))
        with pytest.raises(RuntimeError, match="failed to import"):
            ops_pkg.want_pallas("nms", cfg)
        boxes, scores = np.zeros((4, 4), np.float32), np.zeros(4, np.float32)
        with ops_pkg.backend_scope("pallas"):
            with pytest.raises(RuntimeError, match="failed to import"):
                nms_fixed_auto(boxes, scores, 0.5, 2)
        monkeypatch.setattr(ops_pkg, "_env_backend", "pallas")  # the env way
        with pytest.raises(RuntimeError, match="failed to import"):
            ops_pkg.want_pallas("roi_align")
        # the default backend never asks for the kernels at all
        monkeypatch.setattr(ops_pkg, "_env_backend", "")
        assert ops_pkg.want_pallas("nms") is False


class TestOpsConfig:
    def test_default_backend_xla(self):
        assert FasterRCNNConfig().ops.backend == "xla"

    def test_rejects_unknown_backend(self):
        with pytest.raises(ValueError, match="ops.backend must be"):
            OpsConfig(backend="tpu")

    def test_config_from_dict_roundtrip(self):
        from replication_faster_rcnn_tpu.config import config_from_dict

        cfg = config_from_dict({"ops": {"backend": "pallas"}})
        assert cfg.ops.backend == "pallas"
        assert config_from_dict({}).ops.backend == "xla"


class TestNmsEnvRewiring:
    """FRCNN_NMS=pallas and the legacy FRCNN_PALLAS_NMS=1 spelling were
    warn-and-fall-back tombstones after the round-5 kernel removal; they
    now resolve to the rebuilt `ops/pallas/` backend with bit-identical
    selections."""

    def _data(self, n=150):
        rng = np.random.default_rng(17)
        boxes = jnp.asarray(rand_boxes(n, rng, size=60.0))
        scores = jnp.asarray(rng.uniform(0, 1, n).astype(np.float32))
        return boxes, scores

    def _expect(self, boxes, scores):
        return nms_fixed_tiled(boxes, scores, 0.5, 40)

    def _check(self, boxes, scores):
        idx, val = nms_fixed_auto(boxes, scores, 0.5, 40)
        e_idx, e_val = self._expect(boxes, scores)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(e_idx))
        np.testing.assert_array_equal(np.asarray(val), np.asarray(e_val))

    def test_frcnn_nms_pallas(self, monkeypatch):
        monkeypatch.setenv("FRCNN_NMS", "pallas")
        self._check(*self._data())

    def test_legacy_pallas_opt_in(self, monkeypatch):
        monkeypatch.delenv("FRCNN_NMS", raising=False)
        monkeypatch.setenv("FRCNN_PALLAS_NMS", "1")
        self._check(*self._data())

    def test_backend_scope_routes_auto_dispatch(self):
        with ops_pkg.backend_scope("pallas"):
            self._check(*self._data())

    def test_unknown_choice_warns_and_uses_tiled(self, monkeypatch):
        monkeypatch.setenv("FRCNN_NMS", "warp")
        with pytest.warns(UserWarning, match="unknown FRCNN_NMS"):
            self._check(*self._data())

    def test_tile_env_parse_and_fallback(self, monkeypatch):
        monkeypatch.setenv("FRCNN_NMS_TILE", "256")
        assert _tile_from_env() == 256
        monkeypatch.setenv("FRCNN_NMS_TILE", "banana")
        with pytest.warns(UserWarning, match="invalid FRCNN_NMS_TILE"):
            assert _tile_from_env() == 512


class TestWarmupTwins:
    def test_twin_names_and_suffix(self):
        from replication_faster_rcnn_tpu.analysis.hlolint import audit_config
        from replication_faster_rcnn_tpu.train.warmup import (
            pallas_program_name,
            pallas_twin_base_names,
        )

        assert pallas_program_name("eval_infer") == "eval_infer__pallas"
        bases = pallas_twin_base_names(audit_config())
        # one twin per dispatch seam family: train step, eval, serving
        assert bases == ("train_loader_k1", "eval_infer", "serve_64x64_b1")

    def test_expected_audit_matrix_includes_twins(self):
        from replication_faster_rcnn_tpu.analysis.hlolint import (
            audit_config,
            expected_program_names,
        )

        names = expected_program_names(config=audit_config())
        twins = sorted(n for n in names if n.endswith("__pallas"))
        # the int8 serve program gets its own pallas twin (ISSUE 17):
        # the quantized GEMM is a distinct kernel whose provenance HX007
        # and HX008 audit separately from the f32 serve twin
        assert twins == [
            "eval_infer__pallas",
            "serve_64x64_b1__int8__pallas",
            "serve_64x64_b1__pallas",
            "train_loader_k1__pallas",
        ]

    def test_scope_jitted_identity_for_xla(self):
        from replication_faster_rcnn_tpu.train.warmup import scope_jitted

        f = jax.jit(lambda x: x + 1)
        assert scope_jitted(f, FasterRCNNConfig()) is f

    def test_scope_jitted_wraps_and_delegates_for_pallas(self):
        from replication_faster_rcnn_tpu.train.warmup import (
            _ScopedLower,
            scope_jitted,
        )

        f = jax.jit(lambda x: x + 1)
        wrapped = scope_jitted(f, backend="pallas")
        assert isinstance(wrapped, _ScopedLower)
        x = jnp.ones((3,), jnp.float32)
        np.testing.assert_array_equal(np.asarray(wrapped(x)), np.asarray(f(x)))
        lowered = wrapped.lower(x)
        assert "stablehlo" in lowered.as_text() or "module" in lowered.as_text()
