"""The data's side of a Pascal-VOC configuration (its `feed_reference`):
the run's data set made from the seed, the program overrides that point the
trainer at it, one host batch's shapes, and the plain reference of the data
feed: what a row of a batch has to hold, worked out from the devkit's files
alone.

Mix parameters of the devkit (`make`): `n_images`, `image_wh`,
`jpeg_quality`, `noise_amplitude`, `boxes_per_image`, `box_frac`. Every seed
gets the same sizes and counts, other pixels, boxes and classes.
`noise_amplitude` sets the files' size (120 grey levels: about 100 KB at
500x375 and quality 85, a Pascal VOC photograph's).

For every row of the batches the timed path consumed: find the image it was
made from by its labels and boxes (the reference's own parse of the
annotation, its own scaling to the network's size, its own mirror), decode
that file with PIL, resize it with a plain half-pixel bilinear, normalize,
mirror where the boxes were mirrored, and compare the pixels. Which images a
batch holds and which of them are mirrored is the loader's choice; that the
pixels and the boxes of a row belong together and are resized and
normalized as the configuration states is what this checks. It imports
nothing of the program.

  feed_box_gap    largest distance, in pixels of the resized image, between
                  a row's boxes and the nearest annotation with the same
                  labels (plain or mirrored); 1e9 where no image has them
  feed_pixel_gap  largest over the rows of the mean absolute difference
                  between the row's pixels and the reference's, in
                  normalized units
"""

from __future__ import annotations

import json
import os
import shutil
import xml.etree.ElementTree as ET
from concurrent import futures
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# Pascal VOC's twenty classes in the devkit's order; class ids start at 1,
# 0 is the background.
VOC_NAMES = (
    "aeroplane", "bicycle", "bird", "boat", "bottle", "bus", "car", "cat", "chair",
    "cow", "diningtable", "dog", "horse", "motorbike", "person", "pottedplant",
    "sheep", "sofa", "train", "tvmonitor",
)
NO_MATCH = 1e9
DEVKIT_KEYS = ("n_images", "image_wh", "jpeg_quality", "noise_amplitude", "boxes_per_image", "box_frac")
NOISE_MID = 116  # the noise field's mean grey level


def _one_image(root: str, img_id: str, seed: int, index: int, mix: Dict[str, Any], noise: np.ndarray) -> int:
    from PIL import Image

    rng = np.random.RandomState((seed * 1_000_003 + index) % (2**32))
    w, h = mix["image_wh"]
    # colour blocks over noise whose amplitude gives a photograph's file
    # size: decode work follows the coded bytes. The noise is a window of
    # one field made once a devkit (drawing it anew for every image holds
    # the interpreter lock and tripled the set-up).
    dy, dx = rng.randint(0, noise.shape[0] - h), rng.randint(0, noise.shape[1] - w)
    arr = noise[dy : dy + h, dx : dx + w].copy()
    lo, hi = mix["boxes_per_image"]
    objs = []
    for _ in range(rng.randint(lo, hi + 1)):
        f_lo, f_hi = mix["box_frac"]
        bh = int(h * rng.uniform(f_lo, f_hi))
        bw = int(w * rng.uniform(f_lo, f_hi))
        y1, x1 = rng.randint(0, h - bh), rng.randint(0, w - bw)
        cls = rng.randint(0, len(VOC_NAMES))
        colour = np.asarray([(cls * 37) % 200, (cls * 91 + 60) % 200, (cls * 53 + 120) % 200], np.int16)
        patch = arr[y1 : y1 + bh, x1 : x1 + bw].astype(np.int16) - NOISE_MID + colour + 20
        arr[y1 : y1 + bh, x1 : x1 + bw] = np.clip(patch, 0, 255).astype(np.uint8)
        objs.append(
            f"<object><name>{VOC_NAMES[cls]}</name><difficult>0</difficult>"
            f"<bndbox><xmin>{x1 + 1}</xmin><ymin>{y1 + 1}</ymin>"
            f"<xmax>{x1 + bw}</xmax><ymax>{y1 + bh}</ymax></bndbox></object>"
        )
    path = os.path.join(root, "JPEGImages", img_id + ".jpg")
    Image.fromarray(arr).save(path, quality=mix["jpeg_quality"])
    with open(os.path.join(root, "Annotations", img_id + ".xml"), "w") as f:
        f.write(
            f"<annotation><size><width>{w}</width><height>{h}</height></size>"
            f"{''.join(objs)}</annotation>"
        )
    return os.path.getsize(path)


def make(root: str, seed: int, mix: Dict[str, Any]) -> Dict[str, Any]:
    """A VOC devkit under `root` made from `seed`, anew in every run: the
    same set-up work whether or not the seed was seen before. Returns its
    record."""
    record = {k: mix[k] for k in DEVKIT_KEYS}
    record["seed"] = seed
    shutil.rmtree(root, ignore_errors=True)
    for d in ("ImageSets/Main", "JPEGImages", "Annotations"):
        os.makedirs(os.path.join(root, d))
    ids = [f"{i:06d}" for i in range(mix["n_images"])]
    with open(os.path.join(root, "ImageSets", "Main", "train.txt"), "w") as f:
        f.write("\n".join(ids) + "\n")
    w, h = mix["image_wh"]
    half = int(mix["noise_amplitude"]) // 2
    noise = np.random.RandomState(seed % (2**32)).randint(
        NOISE_MID - half, NOISE_MID + half, (h + 64, w + 64, 3)
    ).astype(np.uint8)
    # PIL's encoder releases the interpreter lock: threads run side by side
    with futures.ThreadPoolExecutor(8) as pool:
        sizes = list(pool.map(lambda a: _one_image(root, a[1], seed, a[0], mix, noise), enumerate(ids)))
    record.update(mean_file_bytes=float(np.mean(sizes)), total_bytes=int(np.sum(sizes)))
    with open(os.path.join(root, "devkit.json"), "w") as f:
        json.dump(record, f)
    return record


def overrides(root: str) -> Dict[str, Any]:
    """The dotted program config keys that point the trainer at `root`."""
    return {"data.dataset": "voc", "data.root_dir": root}


def batch_spec(sizes: Dict[str, Any], batch: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """{key: (shape, dtype)} of one host batch as the loader collates it."""
    (h, w), m = sizes["data.image_size"], int(sizes["data.max_boxes"])
    return {
        "image": ((batch, h, w, 3), np.float32), "boxes": ((batch, m, 4), np.float32),
        "labels": ((batch, m), np.int32), "mask": ((batch, m), np.bool_), "difficult": ((batch, m), np.bool_),
    }


def notes(record: Dict[str, Any]) -> Dict[str, Any]:
    """What of `make`'s record a run's result line notes."""
    return {"devkit_mean_file_bytes": record["mean_file_bytes"]}


def annotations(root: str, image_hw: Tuple[int, int], max_boxes: int) -> Dict[Tuple[int, ...], List[Tuple[str, np.ndarray]]]:
    """{labels of an image: [(image id, its boxes [n, 4] as (y1, x1, y2, x2)
    at the network's size)]}. VOC's coordinates are 1-based and inclusive:
    the mins lose 1, the maxes stay; scaled boxes are rounded to whole pixels."""
    with open(os.path.join(root, "ImageSets", "Main", "train.txt")) as f:
        ids = [ln.split()[0] for ln in f if ln.strip()]
    by_labels: Dict[Tuple[int, ...], List[Tuple[str, np.ndarray]]] = {}
    out_h, out_w = image_hw
    for img_id in ids:
        ann = ET.parse(os.path.join(root, "Annotations", img_id + ".xml")).getroot()
        w, h = float(ann.findtext("size/width")), float(ann.findtext("size/height"))
        labels, boxes = [], []
        for obj in list(ann.iter("object"))[:max_boxes]:
            labels.append(VOC_NAMES.index(obj.findtext("name")) + 1)
            b = obj.find("bndbox")
            boxes.append([
                (float(b.findtext("ymin")) - 1.0) * out_h / h, (float(b.findtext("xmin")) - 1.0) * out_w / w,
                float(b.findtext("ymax")) * out_h / h, float(b.findtext("xmax")) * out_w / w,
            ])
        by_labels.setdefault(tuple(labels), []).append((img_id, np.rint(np.asarray(boxes, np.float64).reshape(-1, 4))))
    return by_labels


def mirrored(boxes: np.ndarray, width: int) -> np.ndarray:
    return np.stack([boxes[:, 0], width - boxes[:, 3], boxes[:, 2], width - boxes[:, 1]], axis=1)


def render(path: str, image_hw: Tuple[int, int], mean, std, how: str = "bilinear") -> np.ndarray:
    """A file's pixels as the network takes them: RGB, resized with
    half-pixel centres, (x / 255 - mean) / std in float32. `how` plants what
    the control and the fault tests put in the loader's place: "uint8"
    rounds the resized pixels to whole grey levels before normalizing (the
    nearest precision below float32 pixels), "nearest" takes the nearest
    source pixel in place of the bilinear blend."""
    from PIL import Image

    with Image.open(path) as im:
        src = np.asarray(im.convert("RGB"), np.float32)
    sh, sw = src.shape[:2]
    dh, dw = image_hw
    rows = np.clip((np.arange(dh, dtype=np.float32) + 0.5) * np.float32(sh / dh) - 0.5, 0, sh - 1)
    cols = np.clip((np.arange(dw, dtype=np.float32) + 0.5) * np.float32(sw / dw) - 0.5, 0, sw - 1)
    if how == "nearest":
        out = src[np.rint(rows).astype(np.int64)][:, np.rint(cols).astype(np.int64)]
    else:
        r0, c0 = rows.astype(np.int64), cols.astype(np.int64)
        r1, c1 = np.minimum(r0 + 1, sh - 1), np.minimum(c0 + 1, sw - 1)
        fr = (rows - r0).astype(np.float32)[:, None, None]
        fc = (cols - c0).astype(np.float32)[None, :, None]
        top, bottom = src[r0], src[r1]
        out = (top[:, c0] * (1 - fc) + top[:, c1] * fc) * (1 - fr) + (bottom[:, c0] * (1 - fc) + bottom[:, c1] * fc) * fr
    if how == "uint8":
        out = np.rint(out)
    return ((out / np.float32(255.0) - np.asarray(mean, np.float32)) / np.asarray(std, np.float32)).astype(np.float32)


def numbers(root: str, batches: List[Dict[str, np.ndarray]], sizes: Dict[str, Any],
            in_place: Optional[str] = None) -> Dict[str, Dict[str, Any]]:
    """The two gaps over every row of `batches`. With `in_place` the rows'
    pixels are not the loader's but this reference's own, rendered with that
    fault (see `render`): the control."""
    image_hw = tuple(int(v) for v in sizes["data.image_size"])
    mean, std = sizes["data.pixel_mean"], sizes["data.pixel_std"]
    known = annotations(root, image_hw, int(sizes["data.max_boxes"]))
    worst_box, box_at, worst_pix, pix_at = 0.0, "", 0.0, ""
    for b, batch in enumerate(batches):
        if batch["image"].dtype != np.float32:
            raise ValueError("the feed's reference compares float32 pixels; this batch holds " + str(batch["image"].dtype))
        for r in range(batch["image"].shape[0]):
            where = f"batch {b} row {r}"
            real = batch["labels"][r] >= 0
            n = int(real.sum())
            sound = bool(real[:n].all()) and bool((np.asarray(batch["mask"][r], bool) == real).all())
            boxes = np.asarray(batch["boxes"][r][:n], np.float64)
            found: List[Tuple[float, str, bool]] = []
            for img_id, ref_boxes in known.get(tuple(int(v) for v in batch["labels"][r][:n]), []) if sound else []:
                for flip in (False, True) if sizes["data.augment_hflip"] else (False,):
                    want = mirrored(ref_boxes, image_hw[1]) if flip else ref_boxes
                    found.append((float(np.abs(boxes - want).max()) if n else 0.0, img_id, flip))
            gap = min((g for g, _, _ in found), default=NO_MATCH)
            if gap > worst_box:
                worst_box, box_at = gap, where
            if gap >= NO_MATCH:
                continue
            pix = NO_MATCH
            for _, img_id, flip in [f for f in found if f[0] == gap]:
                path = os.path.join(root, "JPEGImages", img_id + ".jpg")
                want = render(path, image_hw, mean, std)
                got = batch["image"][r] if in_place is None else render(path, image_hw, mean, std, in_place)
                if flip:
                    want = want[:, ::-1]
                    got = got[:, ::-1] if in_place is not None else got
                pix = min(pix, float(np.abs(got - want).mean()))
            if pix > worst_pix:
                worst_pix, pix_at = pix, where
    return {
        "feed_box_gap": {"value": worst_box, "at": box_at},
        "feed_pixel_gap": {"value": worst_pix, "at": pix_at},
    }
