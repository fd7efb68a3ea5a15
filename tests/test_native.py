"""Native C++ library tests: builds via make, binds via ctypes, and matches
the numpy behavioral specs exactly (the fallbacks ARE the spec)."""

import os

import numpy as np
import pytest

from replication_faster_rcnn_tpu.data import native_ops
from tests import oracles


@pytest.fixture(scope="module")
def lib_available():
    if not native_ops.native_available():
        pytest.skip("native library unavailable (g++/make missing?)")
    return True


class TestResizeNormalize:
    mean = np.asarray([0.485, 0.456, 0.406], np.float32)
    std = np.asarray([0.229, 0.224, 0.225], np.float32)

    def test_native_matches_numpy_spec(self, lib_available):
        rng = np.random.RandomState(0)
        img = rng.randint(0, 256, (50, 100, 3), np.uint8)
        a = native_ops.resize_normalize(img, (64, 64), self.mean, self.std)
        b = native_ops._resize_normalize_numpy(img, (64, 64), self.mean, self.std)
        np.testing.assert_allclose(a, b, atol=2e-5)

    def test_upscale_and_downscale(self, lib_available):
        rng = np.random.RandomState(1)
        for shape, out in [((20, 30, 3), (64, 48)), ((200, 300, 3), (32, 32))]:
            img = rng.randint(0, 256, shape, np.uint8)
            a = native_ops.resize_normalize(img, out, self.mean, self.std)
            b = native_ops._resize_normalize_numpy(img, out, self.mean, self.std)
            assert a.shape == (*out, 3)
            np.testing.assert_allclose(a, b, atol=2e-5)

    def test_identity_size_is_pure_normalize(self, lib_available):
        rng = np.random.RandomState(2)
        img = rng.randint(0, 256, (16, 16, 3), np.uint8)
        a = native_ops.resize_normalize(img, (16, 16), self.mean, self.std)
        expect = (img.astype(np.float32) / 255.0 - self.mean) / self.std
        np.testing.assert_allclose(a, expect, atol=2e-5)


class TestNativeNMS:
    def _case(self, n=200, seed=0):
        rng = np.random.RandomState(seed)
        r1 = rng.uniform(0, 80, (n, 1))
        c1 = rng.uniform(0, 80, (n, 1))
        boxes = np.concatenate(
            [r1, c1, r1 + rng.uniform(5, 40, (n, 1)), c1 + rng.uniform(5, 40, (n, 1))],
            axis=1,
        ).astype(np.float32)
        scores = rng.uniform(size=n).astype(np.float32)
        return boxes, scores

    def test_matches_oracle(self, lib_available):
        boxes, scores = self._case()
        keep = native_ops.nms(boxes, scores, 0.5)
        expect = oracles.nms_np(boxes, scores, 0.5)
        np.testing.assert_array_equal(keep, expect)

    def test_matches_numpy_fallback(self, lib_available):
        boxes, scores = self._case(seed=3)
        a = native_ops.nms(boxes, scores, 0.7, max_keep=20)
        b = native_ops._nms_numpy(boxes, scores, 0.7, 20)
        np.testing.assert_array_equal(a, b)

    def test_max_keep_truncates(self, lib_available):
        boxes, scores = self._case(seed=4)
        keep = native_ops.nms(boxes, scores, 0.99, max_keep=5)
        assert len(keep) == 5

    def test_empty(self, lib_available):
        keep = native_ops.nms(
            np.zeros((0, 4), np.float32), np.zeros((0,), np.float32), 0.5
        )
        assert len(keep) == 0


def test_loader_uses_native_path(tmp_path, lib_available):
    """VOC loader output must equal the native resize+normalize of the raw
    decoded image."""
    from PIL import Image

    from replication_faster_rcnn_tpu.config import DataConfig
    from replication_faster_rcnn_tpu.data import VOCDataset
    from tests.test_data import _write_voc

    root = str(tmp_path / "VOC2007")
    _write_voc(root, ["img0"])
    cfg = DataConfig(dataset="voc", root_dir=root, image_size=(64, 64), max_boxes=8)
    ds = VOCDataset(cfg, "train")
    s = ds[0]
    with Image.open(f"{root}/JPEGImages/img0.jpg") as im:
        raw = np.asarray(im.convert("RGB"), np.uint8)
    expect = native_ops.resize_normalize(
        raw, (64, 64), cfg.pixel_mean, cfg.pixel_std
    )
    # the loader may decode via the native libjpeg kernel while `expect`
    # decodes via PIL; decoder version skew can move pixels by ~1/255,
    # which is ~0.02 in normalized units
    np.testing.assert_allclose(s["image"], expect, atol=0.03)


class TestJpegDecode:
    mean = np.asarray([0.485, 0.456, 0.406], np.float32)
    std = np.asarray([0.229, 0.224, 0.225], np.float32)

    def _jpeg_bytes(self, arr, mode="RGB", quality=90):
        import io

        from PIL import Image

        buf = io.BytesIO()
        Image.fromarray(arr, mode).save(buf, "JPEG", quality=quality)
        return buf.getvalue()

    def test_matches_pil_decode(self, lib_available):
        """Native decode (no prescale: source < 2x target) must match the
        PIL-decode + resize_normalize pipeline to decoder-skew tolerance."""
        import io

        from PIL import Image

        rng = np.random.RandomState(3)
        # smooth image: JPEG is lossy, parity is decoder-vs-decoder only
        base = rng.randint(0, 256, (6, 8, 3), np.uint8)
        img = np.kron(base, np.ones((16, 16, 1), np.uint8))
        data = self._jpeg_bytes(img)
        got = native_ops.decode_jpeg_resize_normalize(
            data, (80, 96), self.mean, self.std
        )
        assert got is not None
        out, oh, ow = got
        assert (oh, ow) == (96, 128)
        with Image.open(io.BytesIO(data)) as im:
            raw = np.asarray(im.convert("RGB"), np.uint8)
        expect = native_ops.resize_normalize(raw, (80, 96), self.mean, self.std)
        assert np.abs(out - expect).max() < 0.05

    def test_fast_scale_close_to_full_decode(self, lib_available):
        """DCT-domain 1/8 prescale followed by bilinear must stay close to
        the full-size-decode pipeline on a smooth image."""
        rng = np.random.RandomState(4)
        base = rng.randint(60, 200, (8, 8, 3), np.uint8)
        img = np.kron(base, np.ones((64, 64, 1), np.uint8))  # 512x512
        data = self._jpeg_bytes(img, quality=95)
        fast = native_ops.decode_jpeg_resize_normalize(
            data, (64, 64), self.mean, self.std, fast_scale=True
        )
        full = native_ops.decode_jpeg_resize_normalize(
            data, (64, 64), self.mean, self.std, fast_scale=False
        )
        assert fast is not None and full is not None
        assert fast[1:] == full[1:]
        assert np.abs(fast[0] - full[0]).mean() < 0.05

    def test_grayscale_converts_to_rgb(self, lib_available):
        rng = np.random.RandomState(5)
        img = np.kron(
            rng.randint(0, 256, (4, 4), np.uint8), np.ones((16, 16), np.uint8)
        )
        data = self._jpeg_bytes(img, mode="L")
        got = native_ops.decode_jpeg_resize_normalize(
            data, (32, 32), self.mean, self.std
        )
        assert got is not None
        out, oh, ow = got
        assert (oh, ow) == (64, 64) and out.shape == (32, 32, 3)
        # denormalize channel-wise: a gray source has R == G == B
        px = out * self.std + self.mean
        assert np.abs(px[..., 0] - px[..., 1]).max() < 0.02
        assert np.abs(px[..., 1] - px[..., 2]).max() < 0.02

    def test_garbage_returns_none(self, lib_available):
        assert (
            native_ops.decode_jpeg_resize_normalize(
                b"not a jpeg at all", (32, 32), self.mean, self.std
            )
            is None
        )

    def test_hand_built_so_is_rebuilt_not_loaded(self, lib_available):
        """An object this module did not build itself (here: a hand-run
        ``make JPEG=0``, i.e. no JPEG entry points) fails the stamp check
        and is rebuilt BEFORE anything is dlopened, so the process gets
        the full library. Runs in a subprocess: it must own the load from
        scratch."""
        import subprocess
        import sys

        code = """
import subprocess, numpy as np
import replication_faster_rcnn_tpu.data.native_ops as native_ops
subprocess.run(["make", "-B", "-C", native_ops._NATIVE_DIR, "JPEG=0"],
               check=True, capture_output=True)
assert not native_ops._stamp_matches()
import io
from PIL import Image
rng = np.random.RandomState(0)
img = rng.randint(0, 256, (64, 64, 3), np.uint8)
buf = io.BytesIO(); Image.fromarray(img).save(buf, "JPEG")
mean = np.zeros(3, np.float32); std = np.ones(3, np.float32)
got = native_ops.decode_jpeg_resize_normalize(buf.getvalue(), (32, 32), mean, std)
assert got is not None, "hand-built .so was loaded instead of rebuilt"
assert got[1:] == (64, 64)
assert native_ops._stamp_matches()
print("REBUILT-OK")
"""
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=300,
            cwd=native_ops._REPO,
        )
        assert r.returncode == 0 and "REBUILT-OK" in r.stdout, (
            r.stdout + r.stderr
        )

    def test_png_in_jpg_falls_back_to_pil(self, tmp_path, lib_available):
        """_load_image must survive a non-JPEG file with a .jpg name (the
        reference's datasets contain a few) via the PIL fallback."""
        from PIL import Image

        from replication_faster_rcnn_tpu.data.voc import _load_image

        rng = np.random.RandomState(6)
        img = rng.randint(0, 256, (40, 30, 3), np.uint8)
        path = str(tmp_path / "sneaky.jpg")
        Image.fromarray(img).save(path, "PNG")
        out, oh, ow = _load_image(path, (20, 20), self.mean, self.std)
        assert (oh, ow) == (40, 30)
        expect = native_ops.resize_normalize(img, (20, 20), self.mean, self.std)
        np.testing.assert_allclose(out, expect, atol=2e-5)


class TestScaleBoxes:
    def test_matches_numpy_semantics(self, lib_available):
        boxes = np.asarray(
            [[5, 10, 45, 60], [-1, -1, -1, -1], [7.4, 3.3, 20.6, 30.9]], np.float32
        )
        labels = np.asarray([1, -1, 5], np.int32)
        out = native_ops.scale_boxes(boxes, labels, 1.28, 0.64)
        scale = np.asarray([1.28, 0.64, 1.28, 0.64], np.float32)
        expect = np.where((labels >= 0)[:, None], np.round(boxes * scale), boxes)
        np.testing.assert_allclose(out, expect)
        # input untouched (copy semantics)
        assert boxes[0, 0] == 5.0

    def test_half_tie_rounds_to_even_like_numpy(self, lib_available):
        # scale 1.5 x coord 3 = 4.5: np.round gives 4 (half-to-even); the
        # native kernel must agree (nearbyint, not round)
        boxes = np.asarray([[3, 1, 5, 3]], np.float32)
        labels = np.asarray([1], np.int32)
        out = native_ops.scale_boxes(boxes, labels, 1.5, 1.5)
        np.testing.assert_array_equal(out[0], np.round(boxes[0] * 1.5))


class TestNeverLoadsAStaleObject:
    """native/build/ is gitignored and the chip tool copies the tree as it
    stands on disk, so an object can arrive from another checkout or
    another CPU. Only one this host built from the current source loads."""

    @pytest.fixture
    def sandbox(self, tmp_path, monkeypatch):
        import shutil

        native = tmp_path / "native"
        native.mkdir()
        for name in ("frcnn_native.cpp", "Makefile"):
            shutil.copy(os.path.join(native_ops._NATIVE_DIR, name), native)
        monkeypatch.setattr(native_ops, "_NATIVE_DIR", str(native))
        monkeypatch.setattr(native_ops, "_lib", None)
        monkeypatch.setattr(native_ops, "_lib_checked", False)
        builds = []
        real_build = native_ops._try_build

        def counting_build():
            builds.append(1)
            return real_build()

        monkeypatch.setattr(native_ops, "_try_build", counting_build)
        return native, builds

    def _reload(self, monkeypatch):
        monkeypatch.setattr(native_ops, "_lib", None)
        monkeypatch.setattr(native_ops, "_lib_checked", False)
        return native_ops._load_lib()

    def test_builds_once_then_trusts_its_own_stamp(self, sandbox, monkeypatch):
        _, builds = sandbox
        if native_ops._load_lib() is None:
            pytest.skip("no toolchain on this host")
        assert builds == [1] and native_ops._stamp_matches()
        assert self._reload(monkeypatch) is not None
        assert builds == [1]  # same source, same host: no rebuild

    def test_source_newer_than_object_rebuilds(self, sandbox, monkeypatch):
        native, builds = sandbox
        if native_ops._load_lib() is None:
            pytest.skip("no toolchain on this host")
        with open(native / "frcnn_native.cpp", "a") as f:
            f.write("\n// edited after the object was built\n")
        assert not native_ops._stamp_matches()
        assert self._reload(monkeypatch) is not None
        assert builds == [1, 1] and native_ops._stamp_matches()

    def test_object_from_another_host_is_rebuilt_or_ignored(
        self, sandbox, monkeypatch
    ):
        _, builds = sandbox
        if native_ops._load_lib() is None:
            pytest.skip("no toolchain on this host")
        # the same files, seen from a machine with another CPU
        monkeypatch.setattr(
            native_ops, "_host_signature", lambda: "elsewhere\nmodel name: x"
        )
        assert not native_ops._stamp_matches()
        assert self._reload(monkeypatch) is not None
        assert builds == [1, 1]
        # ... and where it cannot be rebuilt it is ignored, never loaded
        monkeypatch.setattr(
            native_ops, "_host_signature", lambda: "a third\nmodel name: y"
        )
        monkeypatch.setattr(native_ops, "_try_build", lambda: False)
        monkeypatch.setattr(
            native_ops.ctypes, "CDLL",
            lambda *a, **k: pytest.fail("loaded an object built elsewhere"),
        )
        assert self._reload(monkeypatch) is None
