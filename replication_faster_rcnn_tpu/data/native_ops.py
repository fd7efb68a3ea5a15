"""ctypes bindings for the native host-side kernels (native/frcnn_native.cpp)
with exact-equivalent numpy fallbacks.

The native library replaces, in the framework's own code, the compiled host
kernels the reference borrows from skimage/torchvision (SURVEY.md §2.3):
fused bilinear-resize+normalize for the data pipeline and greedy NMS for
CPU-side post-processing. The ``.so`` is a build product (``native/build/``
is gitignored), so it is only ever loaded when THIS host built it from the
source as it stands: a stamp beside it records the source, the host and
the object's own hash, and anything else — no object, an object from
another checkout or CPU (the build uses ``-march=native``), a hand-run
``make`` — is rebuilt first. If the build fails, the numpy fallbacks keep
everything working (the fallbacks ARE the behavioral spec — parity is
tested both ways).
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import os
import platform
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_NATIVE_DIR = os.path.join(_REPO, "native")
_SO_NAME = "libfrcnn_native.so"

_lib: Optional[ctypes.CDLL] = None
_lib_checked = False
_lib_lock = threading.Lock()  # loader threads race here on first batch


def _so_path() -> str:
    return os.path.join(_NATIVE_DIR, "build", _SO_NAME)


def _sha256(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _host_signature() -> str:
    """This host, as far as a ``-march=native`` object cares: the node
    name plus the CPU model and feature flags."""
    try:
        with open("/proc/cpuinfo") as f:
            cpu = sorted(
                {ln for ln in f if ln.startswith(("model name", "flags", "Features"))}
            )
    except OSError:
        cpu = [platform.processor() or platform.machine()]
    return platform.node() + "\n" + "".join(cpu)


def _build_key() -> str:
    """What a loadable object must have been built from and on."""
    h = hashlib.sha256(_host_signature().encode())
    for name in ("frcnn_native.cpp", "Makefile"):
        h.update(_sha256(os.path.join(_NATIVE_DIR, name)).encode())
    return h.hexdigest()


def _stamp_matches() -> bool:
    """True iff the object on disk is byte-for-byte what :func:`_try_build`
    last produced on this host from the current source."""
    so = _so_path()
    try:
        with open(so + ".stamp") as f:
            stamp = json.load(f)
        return stamp == {"key": _build_key(), "so_sha256": _sha256(so)}
    except (OSError, ValueError):
        return False


def _try_build() -> bool:
    """Best-effort make, degrading through host capabilities: full build,
    then without -march=native (older gcc), then without libjpeg (missing
    jpeglib.h — the JPEG entry points are simply absent), then both.

    Builds under a scratch name and moves the object, then its stamp,
    into place — a concurrent process never maps a half-written file, and
    a stamp never describes an object it was not written for."""
    so = _so_path()
    tmp = f"{_SO_NAME}.{os.getpid()}.tmp"
    for flags in ([], ["MARCH="], ["JPEG=0"], ["MARCH=", "JPEG=0"]):
        try:
            subprocess.run(
                ["make", "-B", "-C", _NATIVE_DIR, f"OUT=build/{tmp}", *flags],
                check=True, capture_output=True, timeout=120,
            )
        except (subprocess.SubprocessError, OSError):
            continue
        built = os.path.join(_NATIVE_DIR, "build", tmp)
        stamp = {"key": _build_key(), "so_sha256": _sha256(built)}
        os.replace(built, so)
        with open(built + ".stamp", "w") as f:
            json.dump(stamp, f)
        os.replace(built + ".stamp", so + ".stamp")
        return True
    return False


def _load_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_checked
    if _lib_checked:
        return _lib
    with _lib_lock:
        return _load_lib_locked()


def _load_lib_locked() -> Optional[ctypes.CDLL]:
    global _lib, _lib_checked
    if _lib_checked:
        return _lib
    _lib_checked = True
    if not _stamp_matches() and not _try_build():
        return None  # numpy fallbacks cover everything
    try:
        lib = ctypes.CDLL(_so_path())
    except OSError:
        return None
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    lib.resize_bilinear_normalize.argtypes = [
        u8p, ctypes.c_int, ctypes.c_int, f32p, ctypes.c_int, ctypes.c_int,
        f32p, f32p,
    ]
    lib.resize_bilinear_normalize.restype = None
    lib.nms_greedy.argtypes = [
        f32p, f32p, ctypes.c_int, ctypes.c_float, i32p, ctypes.c_int,
    ]
    lib.nms_greedy.restype = ctypes.c_int
    lib.scale_boxes.argtypes = [
        f32p, i32p, ctypes.c_int, ctypes.c_float, ctypes.c_float,
    ]
    lib.scale_boxes.restype = None
    if hasattr(lib, "decode_jpeg_resize_normalize"):  # absent in JPEG=0 builds
        lib.decode_jpeg_resize_normalize.argtypes = [
            u8p, ctypes.c_int64, f32p, ctypes.c_int, ctypes.c_int,
            f32p, f32p, ctypes.c_int, i32p, i32p,
        ]
        lib.decode_jpeg_resize_normalize.restype = ctypes.c_int
    _lib = lib
    return _lib


def native_available() -> bool:
    return _load_lib() is not None


def _resize_normalize_numpy(
    img: np.ndarray, out_hw: Tuple[int, int], mean: np.ndarray, std: np.ndarray
) -> np.ndarray:
    """The behavioral spec of the C++ kernel: bilinear with
    align_corners=False sampling, fused /255 + mean/std normalization."""
    sh, sw = img.shape[:2]
    dh, dw = out_hw
    sr = np.clip((np.arange(dh) + 0.5) * (sh / dh) - 0.5, 0, sh - 1)
    sc = np.clip((np.arange(dw) + 0.5) * (sw / dw) - 0.5, 0, sw - 1)
    r0 = sr.astype(np.int32)
    c0 = sc.astype(np.int32)
    r1 = np.minimum(r0 + 1, sh - 1)
    c1 = np.minimum(c0 + 1, sw - 1)
    fr = (sr - r0).astype(np.float32)[:, None, None]
    fc = (sc - c0).astype(np.float32)[None, :, None]
    im = img.astype(np.float32)
    top = im[r0][:, c0] * (1 - fc) + im[r0][:, c1] * fc
    bot = im[r1][:, c0] * (1 - fc) + im[r1][:, c1] * fc
    out = top * (1 - fr) + bot * fr
    return ((out / 255.0 - mean) / std).astype(np.float32)


def resize_normalize(
    img: np.ndarray,
    out_hw: Tuple[int, int],
    mean,
    std,
) -> np.ndarray:
    """uint8 HWC RGB -> normalized float32 [out_h, out_w, 3]."""
    img = np.ascontiguousarray(img, np.uint8)
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    lib = _load_lib()
    if lib is None:
        return _resize_normalize_numpy(img, out_hw, mean, std)
    dst = np.empty((out_hw[0], out_hw[1], 3), np.float32)
    lib.resize_bilinear_normalize(
        img, img.shape[0], img.shape[1], dst, out_hw[0], out_hw[1], mean, std
    )
    return dst


def scale_boxes(
    boxes: np.ndarray,
    labels: np.ndarray,
    row_scale: float,
    col_scale: float,
) -> np.ndarray:
    """Scale + round padded [m, 4] boxes to resized-image coords, leaving
    entries with label < 0 untouched (reference
    `utils/data_loader.py:66-69,115` semantics)."""
    boxes = np.ascontiguousarray(boxes, np.float32).copy()
    labels = np.ascontiguousarray(labels, np.int32)
    lib = _load_lib()
    if lib is None:
        real = labels >= 0
        scale = np.asarray([row_scale, col_scale, row_scale, col_scale], np.float32)
        return np.where(real[:, None], np.round(boxes * scale), boxes)
    lib.scale_boxes(boxes, labels, len(boxes), row_scale, col_scale)
    return boxes


def decode_jpeg_resize_normalize(
    data: bytes,
    out_hw: Tuple[int, int],
    mean,
    std,
    fast_scale: bool = True,
) -> Optional[Tuple[np.ndarray, int, int]]:
    """JPEG bytes -> (normalized float32 [out_h, out_w, 3], orig_h, orig_w).

    The whole loader hot path — decode, RGB conversion, bilinear resize,
    /255 + mean/std — in one native call. ``fast_scale`` enables libjpeg's
    DCT-domain 1/2..1/8 prescaling when the source is at least 2x the
    target in both dims (large decode savings, sub-bilinear-error quality
    difference). Returns None when the native library is unavailable or
    the bytes don't decode (caller falls back to PIL — which also covers
    non-JPEG files like the occasional PNG-in-.jpg).
    """
    lib = _load_lib()
    if lib is None or not hasattr(lib, "decode_jpeg_resize_normalize"):
        return None
    buf = np.frombuffer(data, np.uint8)
    dims = np.empty((2,), np.int32)
    dst = np.empty((out_hw[0], out_hw[1], 3), np.float32)
    rc = lib.decode_jpeg_resize_normalize(
        buf,
        buf.size,
        dst,
        out_hw[0],
        out_hw[1],
        np.asarray(mean, np.float32),
        np.asarray(std, np.float32),
        1 if fast_scale else 0,
        dims[0:1],
        dims[1:2],
    )
    if rc != 0:
        return None
    return dst, int(dims[0]), int(dims[1])


def _nms_numpy(
    boxes: np.ndarray, scores: np.ndarray, thresh: float, max_keep: int
) -> np.ndarray:
    order = np.argsort(-scores, kind="stable")
    dead = np.zeros(len(boxes), bool)
    area = (boxes[:, 2] - boxes[:, 0]) * (boxes[:, 3] - boxes[:, 1])
    keep = []
    for i in order:
        if dead[i] or len(keep) >= max_keep:
            if len(keep) >= max_keep:
                break
            continue
        keep.append(int(i))
        tl = np.maximum(boxes[i, :2], boxes[:, :2])
        br = np.minimum(boxes[i, 2:], boxes[:, 2:])
        wh = np.clip(br - tl, 0, None)
        inter = wh[:, 0] * wh[:, 1]
        union = area[i] + area - inter
        iou = np.where(union > 0, inter / np.maximum(union, 1e-12), 0.0)
        dead |= iou > thresh
    return np.asarray(keep, np.int32)


def nms(
    boxes: np.ndarray, scores: np.ndarray, thresh: float, max_keep: int = 1 << 30
) -> np.ndarray:
    """Greedy NMS on host; returns kept indices in descending score order."""
    boxes = np.ascontiguousarray(boxes, np.float32)
    scores = np.ascontiguousarray(scores, np.float32)
    max_keep = int(min(max_keep, len(boxes)))
    lib = _load_lib()
    if lib is None:
        return _nms_numpy(boxes, scores, thresh, max_keep)
    keep = np.empty((max(max_keep, 1),), np.int32)
    n = lib.nms_greedy(boxes, scores, len(boxes), thresh, keep, max_keep)
    return keep[:n]


# --- uint8 (device-normalize) variants -----------------------------------
# With mean=0 and std=1/255 the fused kernel's (x/255 - mean)/std affine
# is the identity on pixel values, so the SAME native code yields the
# resized image in 0..255 — no second C++ entry point needed. The f32->u8
# rounding costs ~1 ms once per sample (and only once ever with the RAM
# cache); in exchange the sample ships to the device at a quarter of the
# bytes and the normalize runs on-chip fused into the first conv
# (models/faster_rcnn.py::preprocess).

_U8_MEAN = (0.0, 0.0, 0.0)
_U8_STD = (1.0 / 255.0, 1.0 / 255.0, 1.0 / 255.0)


def _to_u8(arr: np.ndarray) -> np.ndarray:
    return np.clip(np.rint(arr), 0.0, 255.0).astype(np.uint8)


def resize_u8(img: np.ndarray, out_hw: Tuple[int, int]) -> np.ndarray:
    """uint8 HWC RGB -> bilinear-resized uint8 [out_h, out_w, 3]."""
    return _to_u8(resize_normalize(img, out_hw, _U8_MEAN, _U8_STD))


def decode_jpeg_resize_u8(
    data: bytes, out_hw: Tuple[int, int], fast_scale: bool = True
) -> Optional[Tuple[np.ndarray, int, int]]:
    """JPEG bytes -> (resized uint8 [out_h, out_w, 3], orig_h, orig_w);
    None if the native decoder is unavailable (caller falls back)."""
    res = decode_jpeg_resize_normalize(
        data, out_hw, _U8_MEAN, _U8_STD, fast_scale
    )
    if res is None:
        return None
    out, orig_h, orig_w = res
    return _to_u8(out), orig_h, orig_w
