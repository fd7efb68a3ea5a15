"""The one traffic generator. A mix is a data file of parameters; this
reads it, makes a Pascal-VOC devkit from the seed, and hands the window
loop an iterator of what `Trainer.train_one_batch` takes.

Mix parameters (`mixes/<name>.json`):
  feed            "loader": the trainer's own DataLoader over the devkit,
                  epoch after epoch (decode, resize, normalize, collate on
                  the host, staged by the trainer);
                  "staged": `staged_batches` batches drawn from the same
                  loader, put on the device once during set-up the way the
                  trainer stages them, and cycled.
  n_images, image_wh, jpeg_quality, noise_amplitude, boxes_per_image,
  box_frac        the devkit: every seed gets the same sizes and counts,
                  other pixels, boxes and classes. `noise_amplitude` sets
                  the files' size (120 grey levels: about 100 KB at 500x375
                  and quality 85, a Pascal VOC photograph's).
  overrides       dotted program config keys this traffic sets.
"""

from __future__ import annotations

import json
import os
import shutil
from concurrent import futures
from typing import Any, Dict, Iterator, List

import numpy as np

# Pascal VOC's twenty classes, as the annotation files spell them.
VOC_NAMES = (
    "aeroplane", "bicycle", "bird", "boat", "bottle", "bus", "car", "cat", "chair",
    "cow", "diningtable", "dog", "horse", "motorbike", "person", "pottedplant",
    "sheep", "sofa", "train", "tvmonitor",
)


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


def build_devkit(root: str, seed: int, mix: Dict[str, Any]) -> Dict[str, Any]:
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


def epochs(loader) -> Iterator[Dict[str, np.ndarray]]:
    """The trainer's loader, epoch after epoch, as `Trainer.train` walks it."""
    epoch = 0
    while True:
        loader.set_epoch(epoch)
        yield from loader
        epoch += 1


class Feed:
    """What the window loop draws from: `next(feed)` gives the keyword
    arguments of one `train_one_batch` call. `first_host_batches` are the
    host copies of the first batches, kept for the reference."""

    def __init__(self, trainer, mix: Dict[str, Any], keep: int) -> None:
        self.kind = mix["feed"]
        self._it = epochs(trainer.loader)
        self.first_host_batches: List[Dict[str, np.ndarray]] = []
        self._keep = keep
        self._staged: List[Dict[str, Any]] = []
        self._i = 0
        if self.kind == "staged":
            import jax

            host = [next(self._it) for _ in range(int(mix["staged_batches"]))]
            self.first_host_batches = host[:keep]
            self._staged = [trainer._stage_batch(b, wait=True) for b in host]
            jax.block_until_ready(self._staged)
            self.close()
        elif self.kind != "loader":
            raise ValueError(f"unknown feed {self.kind!r}")

    def __iter__(self):
        return self

    def __next__(self) -> Dict[str, Any]:
        if self.kind == "staged":
            batch = self._staged[self._i % len(self._staged)]
            self._i += 1
            return {"staged": batch}
        batch = next(self._it)
        if len(self.first_host_batches) < self._keep:
            self.first_host_batches.append(batch)
        return {"batch": batch}

    def close(self) -> None:
        """Stop the loader's producer thread and its pool."""
        it, self._it = self._it, iter(())
        close = getattr(it, "close", None)
        if close is not None:
            close()

    def free(self) -> None:
        self._staged = []
