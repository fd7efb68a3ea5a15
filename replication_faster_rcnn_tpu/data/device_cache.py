"""Device-resident dataset cache: the TPU-native answer to a feed-bound
trainer.

Why: a loader-fed trainer ships every image host->device every step
(69 MB/step f32, 17 MB u8 at 600x600 b16), and where that link is the
bottleneck the step waits on it (ROADMAP S2 is to measure how much, on
the chip). The reference has no answer to this: its torch DataLoader
re-decodes and re-ships every image every epoch (`frcnn.py:19-23`,
`utils/data_loader.py:42-48`).

Design (upload once, then index): the whole fixed-shape dataset is
stacked into four contiguous arrays (image [N,H,W,3] uint8/f32, boxes
[N,M,4] f32, labels [N,M] i32, mask [N,M] bool) and placed in HBM once —
VOC2007 trainval at 600x600 uint8 is ~5.4 GB against a v5e's 16 GB.
Every step the host ships ONLY the batch selection (`sel`): indices,
flip bits, jitter geometry — a few hundred bytes. Batch materialization
(gather + hflip + jitter box transform) runs INSIDE the jitted train
step (`train/train_step.py::make_cached_train_step`), where XLA fuses it
with the on-chip normalize (`models/faster_rcnn.py::preprocess`) and the
on-chip scale-jitter resample (`ops/image.py::batched_scale_jitter`).

Augmentation decisions reuse the exact counter-mix the host pipeline
uses (`augment.draw_decisions`), so a cached run and a loader-fed run
with the same (seed, epoch) see identical samples; equivalence is pinned
in `tests/test_device_cache.py`.

Sharding: the cache is REPLICATED over the mesh (every chip holds the
full dataset, each gathers only its batch shard locally — no
collectives). Datasets beyond per-chip HBM need the host loader path or
a sharded cache + local sampling; the byte guard below makes the switch
explicit rather than letting device allocation fail mid-init.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from replication_faster_rcnn_tpu.data.augment import (
    draw_decisions,
    jitter_geometry,
)
from replication_faster_rcnn_tpu.data.loader import collate
from replication_faster_rcnn_tpu.telemetry import spans as tspans

# Above this the constructor refuses and points at --cache-ram / the
# host loader instead. v5e-1 has 16 GB HBM; model+optimizer+activations
# for the flagship fit in ~4 GB, so 8 GiB of dataset is a safe default.
DEFAULT_MAX_BYTES = 8 << 30


class DeviceCache:
    """Upload a map-style dataset's samples to device memory once.

    ``mesh`` (optional) replicates the arrays over a `jax.sharding.Mesh`;
    without it the arrays land on the default device.

    ``keep_host_meta`` additionally retains a host-side copy of the small
    non-image arrays (boxes, labels, mask, difficult, ...) as
    ``self.host_meta``. Training never reads ground truth on the host, so
    the trainer leaves this off; the cached-eval path turns it on because
    mAP scoring consumes GT host-side and a second full decode pass to
    re-derive it would defeat the cache.
    """

    def __init__(
        self,
        dataset,
        mesh=None,
        max_bytes: Optional[int] = None,
        keep_host_meta: bool = False,
    ):
        if max_bytes is None:
            max_bytes = int(
                os.environ.get("FRCNN_DEVICE_CACHE_MAX_BYTES", DEFAULT_MAX_BYTES)
            )

        def _over_cap(nbytes: int) -> ValueError:
            return ValueError(
                f"device cache would need {nbytes / 2**30:.2f} GiB "
                f"(> {max_bytes / 2**30:.2f} GiB cap). Use uint8 samples "
                "(data.device_normalize=True / --device-normalize) or fall "
                "back to the host loader (--cache-ram). Override with "
                "FRCNN_DEVICE_CACHE_MAX_BYTES."
            )

        # estimate BEFORE materializing anything: samples are fixed-shape,
        # so sample 0 prices the dataset — an over-cap f32 VOC (~21.6 GB)
        # must hit this error, not the host OOM killer, and must not pay
        # a full decode pass first
        first = {
            k: v for k, v in dataset[0].items() if k != "jitter"
        }
        est = sum(np.asarray(v).nbytes for v in first.values()) * len(dataset)
        if est > max_bytes:
            raise _over_cap(est)
        with tspans.current_tracer().span(
            "data/cache_upload", cat="data", n=len(dataset)
        ):
            stacked = collate([dataset[i] for i in range(len(dataset))])
            # jitter geometry attaches per-step via sel, never via the cache
            stacked.pop("jitter", None)
            nbytes = sum(v.nbytes for v in stacked.values())
            if nbytes > max_bytes:  # exact check (paranoia; shapes are fixed)
                raise _over_cap(nbytes)
            self.nbytes = nbytes
            self.n = len(dataset)
            self.image_hw = tuple(stacked["image"].shape[1:3])
            self.host_meta = (
                {k: v for k, v in stacked.items() if k != "image"}
                if keep_host_meta
                else None
            )
            if mesh is not None:
                from replication_faster_rcnn_tpu.parallel.mesh import replicated

                self.arrays = {
                    k: jax.device_put(v, replicated(mesh))
                    for k, v in stacked.items()
                }
            else:
                self.arrays = {k: jax.device_put(v) for k, v in stacked.items()}

    def __len__(self) -> int:
        return self.n


class CachedSampler:
    """Per-epoch batch selections for a :class:`DeviceCache`.

    Mirrors the host pipeline exactly: the epoch order is the
    DataLoader's ``np.random.RandomState(seed + epoch).permutation``
    (`data/loader.py::DataLoader._order`) and per-sample flip/jitter
    decisions come from the shared `augment.draw_decisions` counter-mix,
    so swapping feed paths changes NOTHING about what the model sees.

    Yields ``sel`` dicts: ``idx`` [B] i32, plus ``flip`` [B] bool when
    hflip is on and ``jitter`` [B,4] i32 when a scale range is set.
    """

    def __init__(
        self,
        n: int,
        image_hw,
        batch_size: int,
        seed: int,
        hflip: bool = False,
        scale_range=None,
        shuffle: bool = True,
        drop_last: bool = True,
        process_index: int = 0,
        process_count: int = 1,
        train_resolutions=(),
        bucket_chunk: int = 1,
    ):
        if scale_range is not None:
            lo, hi = float(scale_range[0]), float(scale_range[1])
            if not 0.1 <= lo <= hi <= 4.0:
                raise ValueError(
                    "scale_range must satisfy 0.1 <= lo <= hi <= 4, "
                    f"got {scale_range!r}"
                )
            scale_range = (lo, hi)
        self.n = int(n)
        self.h, self.w = int(image_hw[0]), int(image_hw[1])
        self.batch_size = int(batch_size)
        self.seed = int(seed)
        self.hflip = bool(hflip)
        self.scale_range = scale_range
        self.shuffle = bool(shuffle)
        self.drop_last = bool(drop_last)
        # Multi-process: each process draws the SAME global epoch order (same
        # seed) and keeps only its contiguous row block — matching the
        # process-contiguous device order of `mesh.make_mesh` so
        # `make_array_from_process_local_data` assembles the intended global
        # batch. draw_decisions is keyed on the GLOBAL sample index, so
        # augmentation is identical across topologies.
        if not 0 <= int(process_index) < int(process_count):
            raise ValueError(
                f"process_index {process_index} out of range for "
                f"process_count {process_count}"
            )
        if self.batch_size % int(process_count) != 0:
            raise ValueError(
                f"batch_size {batch_size} must divide evenly across "
                f"{process_count} processes"
            )
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        # multi-scale buckets: same assignment contract as
        # `DataLoader.bucket_of` — the sel dicts are shape-invariant, the
        # bucket only selects WHICH compiled program consumes them.
        self.train_resolutions = tuple(
            (int(r[0]), int(r[1])) for r in (train_resolutions or ())
        )
        self.bucket_chunk = max(1, int(bucket_chunk))
        self.epoch = 0
        self.start_batch = 0  # mid-epoch offset (set_epoch)

    def set_epoch(self, epoch: int, start_batch: int = 0) -> None:
        """Select the epoch, optionally resuming at a mid-epoch global
        batch offset — same contract as ``DataLoader.set_epoch``: the
        consumed prefix of the deterministic global order is skipped
        without being drawn, and the suffix re-partitions disjointly if
        ``process_count`` changed (elastic fleet shrink)."""
        if start_batch < 0:
            raise ValueError(f"start_batch must be >= 0, got {start_batch}")
        self.epoch = int(epoch)
        self.start_batch = int(start_batch)

    def bucket_of(self, batch_pos: int) -> int:
        """Resolution-bucket index for the global batch at ``batch_pos``
        — identical contract to ``DataLoader.bucket_of`` (pure function
        of seed/epoch/position; 0 when bucketing is off)."""
        if len(self.train_resolutions) <= 1:
            return 0
        from replication_faster_rcnn_tpu.data.augment import bucket_index

        return bucket_index(
            self.seed,
            self.epoch,
            int(batch_pos),
            len(self.train_resolutions),
            chunk=self.bucket_chunk,
        )

    def __len__(self) -> int:
        if self.drop_last:
            return self.n // self.batch_size
        return -(-self.n // self.batch_size)

    def selection(self, idxs: np.ndarray) -> Dict[str, np.ndarray]:
        """The sel dict for explicit sample indices (any feed order)."""
        sel: Dict[str, np.ndarray] = {"idx": np.asarray(idxs, np.int32)}
        if self.hflip:
            sel["flip"] = np.array(
                [
                    draw_decisions(self.seed, self.epoch, int(i),
                                   self.scale_range)[0]
                    for i in idxs
                ],
                dtype=bool,
            )
        if self.scale_range is not None:
            geoms = []
            for i in idxs:
                _, scale, off_y, off_x = draw_decisions(
                    self.seed, self.epoch, int(i), self.scale_range
                )
                geoms.append(
                    jitter_geometry(self.h, self.w, scale, off_y, off_x)
                )
            sel["jitter"] = np.asarray(geoms, np.int32)
        return sel

    def __iter__(self):
        if self.shuffle:
            order = np.random.RandomState(self.seed + self.epoch).permutation(
                self.n
            )
        else:
            order = np.arange(self.n)
        bs = self.batch_size
        local = bs // self.process_count
        lo = self.process_index * local
        end = len(order) - (len(order) % bs if self.drop_last else 0)
        for i in range(self.start_batch * bs, end, bs):
            yield self.selection(order[i + lo : i + lo + local])


def stack_selections(sels) -> Dict[str, np.ndarray]:
    """Stack K per-step selection dicts into one [K, B, ...] chunk for the
    fused multi-step dispatch (`train/train_step.py::make_cached_multi_step`
    scans over the leading axis). All selections must carry the same keys —
    they come from one `CachedSampler`, so they do."""
    if not sels:
        raise ValueError("stack_selections needs at least one selection")
    return {k: np.stack([s[k] for s in sels]) for k in sels[0]}


def materialize_batch(
    cache: Dict[str, jax.Array], sel: Dict[str, jax.Array]
) -> Dict[str, jax.Array]:
    """Device-side batch assembly: gather + hflip + jitter box affine.

    Runs inside the jitted step. Reproduces the host device-mode pipeline
    (`augment.AugmentedView` with ``scale_on_device``) op for op:
    flip-then-jitter, flips keyed on ``labels >= 0``, jitter box collapse
    to the padded-row convention. The image's jitter RESAMPLE is not done
    here — the ``jitter`` key passes through to `compute_losses`, which
    feeds `ops/image.py::batched_scale_jitter` exactly as the host
    device-jitter path does.
    """
    idx = sel["idx"]
    gathered = {k: jnp.take(v, idx, axis=0) for k, v in cache.items()}
    images = gathered["image"]
    boxes = gathered["boxes"]
    labels = gathered["labels"]
    mask = gathered["mask"]
    h = float(cache["image"].shape[1])
    w = float(cache["image"].shape[2])

    if "flip" in sel:
        flip = sel["flip"]
        images = jnp.where(flip[:, None, None, None], images[:, :, ::-1, :], images)
        valid = labels >= 0
        flipped_boxes = jnp.stack(
            [boxes[..., 0], w - boxes[..., 3], boxes[..., 2], w - boxes[..., 1]],
            axis=-1,
        )
        boxes = jnp.where((flip[:, None] & valid)[..., None], flipped_boxes, boxes)

    if "jitter" in sel:
        geom = sel["jitter"].astype(jnp.float32)  # [B, 4] (ch, cw, sy, sx)
        sy = (geom[:, 0] / h)[:, None]
        sx = (geom[:, 1] / w)[:, None]
        shift_y = geom[:, 2][:, None]
        shift_x = geom[:, 3][:, None]
        valid = labels >= 0
        # Per-row identity guard: the host path (`AugmentedView.__getitem__`)
        # skips jitter_boxes entirely when the rounded geometry is
        # (h, w, 0, 0) — a draw that resolves to no-op. Without the same
        # skip here the <1px collapse below would kill a raw GT box that is
        # already sub-pixel, even though no geometry was applied to it.
        identity = (
            (geom[:, 0] == h)
            & (geom[:, 1] == w)
            & (geom[:, 2] == 0.0)
            & (geom[:, 3] == 0.0)
        )[:, None]
        applied = valid & ~identity
        jb = jnp.stack(
            [
                boxes[..., 0] * sy - shift_y,
                boxes[..., 1] * sx - shift_x,
                boxes[..., 2] * sy - shift_y,
                boxes[..., 3] * sx - shift_x,
            ],
            axis=-1,
        )
        jb = jb.at[..., 0::2].set(jnp.clip(jb[..., 0::2], 0.0, h))
        jb = jb.at[..., 1::2].set(jnp.clip(jb[..., 1::2], 0.0, w))
        collapsed = ((jb[..., 2] - jb[..., 0]) < 1.0) | (
            (jb[..., 3] - jb[..., 1]) < 1.0
        )
        dead = applied & collapsed
        jb = jnp.where(dead[..., None], -1.0, jb)
        boxes = jnp.where(applied[..., None], jb, boxes)
        labels = jnp.where(dead, -1, labels)
        mask = jnp.where(dead, False, mask)

    batch = dict(gathered)  # pass-through keys (e.g. 'difficult') ride along
    batch.update(image=images, boxes=boxes, labels=labels, mask=mask)
    if "jitter" in sel:
        batch["jitter"] = sel["jitter"]
    return batch
