"""Central configuration registry.

The reference scatters every hyperparameter across module constants and
hard-coded literals (SURVEY.md §2.2; reference `utils/utils.py:6-21`,
`train.py:139-159`, `utils/data_loader.py:21,81`, `nets/heads.py:8,21-22`,
`nets/faster_rcnn.py:4-5`). This module centralizes all of them as frozen
dataclasses so configs are hashable (usable as jit static args) and the five
BASELINE.json configs are expressible as presets.

Box convention used throughout the framework (matches the reference's
row-major convention, reference `nets/faster_rcnn.py:10`,
`utils/data_loader.py:104-105`): boxes are ``[r1, c1, r2, c2]`` where ``r``
indexes image rows (height) and ``c`` image columns (width). Regression
deltas are ``[dr, dc, dh, dw]`` with ``h`` = row extent, ``w`` = col extent
(reference `utils/utils.py:47-100`, which calls the row axis "x").
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

VOC_CLASSES: Tuple[str, ...] = (
    "__background__",
    "aeroplane", "bicycle", "bird", "boat",
    "bottle", "bus", "car", "cat", "chair",
    "cow", "diningtable", "dog", "horse",
    "motorbike", "person", "pottedplant",
    "sheep", "sofa", "train", "tvmonitor",
)
VOC_NUM_CLASSES = len(VOC_CLASSES)  # 21 incl. background (reference utils/utils.py:15-21)

# COCO-2017 "thing" classes for the BASELINE config #5 (80 + background).
COCO_CLASSES: Tuple[str, ...] = (
    "__background__",
    "person", "bicycle", "car", "motorcycle", "airplane", "bus", "train",
    "truck", "boat", "traffic light", "fire hydrant", "stop sign",
    "parking meter", "bench", "bird", "cat", "dog", "horse", "sheep", "cow",
    "elephant", "bear", "zebra", "giraffe", "backpack", "umbrella", "handbag",
    "tie", "suitcase", "frisbee", "skis", "snowboard", "sports ball", "kite",
    "baseball bat", "baseball glove", "skateboard", "surfboard",
    "tennis racket", "bottle", "wine glass", "cup", "fork", "knife", "spoon",
    "bowl", "banana", "apple", "sandwich", "orange", "broccoli", "carrot",
    "hot dog", "pizza", "donut", "cake", "chair", "couch", "potted plant",
    "bed", "dining table", "toilet", "tv", "laptop", "mouse", "remote",
    "keyboard", "cell phone", "microwave", "oven", "toaster", "sink",
    "refrigerator", "book", "clock", "vase", "scissors", "teddy bear",
    "hair drier", "toothbrush",
)
COCO_NUM_CLASSES = len(COCO_CLASSES)  # 81 incl. background


@dataclasses.dataclass(frozen=True)
class AnchorConfig:
    """Anchor grid definition (reference `utils/anchors.py:5-61`,
    `nets/faster_rcnn.py:4-5`)."""

    base_size: int = 16
    ratios: Tuple[float, ...] = (0.5, 1.0, 2.0)
    scales: Tuple[float, ...] = (8.0, 16.0, 32.0)
    feat_stride: int = 16

    @property
    def num_base_anchors(self) -> int:
        return len(self.ratios) * len(self.scales)


@dataclasses.dataclass(frozen=True)
class ProposalConfig:
    """Proposal-layer budgets (reference `utils/utils.py:7-12`,
    `nets/rpn.py:20-79`). Fixed-shape on TPU: outputs are padded to
    ``post_nms`` with a validity mask."""

    nms_thresh: float = 0.7
    pre_nms_train: int = 12000
    post_nms_train: int = 600
    pre_nms_test: int = 3000
    post_nms_test: int = 300
    min_size: float = 16.0

    def pre_nms(self, train: bool) -> int:
        return self.pre_nms_train if train else self.pre_nms_test

    def post_nms(self, train: bool) -> int:
        return self.post_nms_train if train else self.post_nms_test


@dataclasses.dataclass(frozen=True)
class RPNTargetConfig:
    """RPN (first-stage) target sampling (reference `utils/utils.py:122-204`,
    `train.py:24-25`)."""

    n_sample: int = 256
    pos_iou_thresh: float = 0.7
    neg_iou_thresh: float = 0.3
    pos_ratio: float = 0.5


@dataclasses.dataclass(frozen=True)
class ROITargetConfig:
    """Second-stage (head) target sampling (reference
    `utils/utils.py:207-276`, `train.py:26`). Output is a deterministic,
    padded ``n_sample`` rois per image (fixing the reference's latent
    variable-length bug, SURVEY.md §2.1 #5)."""

    n_sample: int = 128
    pos_ratio: float = 0.5
    pos_iou_thresh: float = 0.5
    neg_iou_thresh_high: float = 0.5
    neg_iou_thresh_low: float = 0.0
    reg_mean: Tuple[float, float, float, float] = (0.0, 0.0, 0.0, 0.0)
    reg_std: Tuple[float, float, float, float] = (0.1, 0.1, 0.2, 0.2)

    @property
    def n_pos_max(self) -> int:
        return int(round(self.n_sample * self.pos_ratio))


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Network architecture (reference `nets/` — resnet_torch.py:392-409 split,
    rpn.py:82-100, heads.py:7-26)."""

    # any arch from the reference's constructor table (`nets/resnet_torch.py:
    # 271-390`): resnet18/34/50/101/152, resnext50_32x4d, resnext101_32x8d,
    # wide_resnet50_2, wide_resnet101_2
    backbone: str = "resnet18"
    num_classes: int = VOC_NUM_CLASSES
    rpn_mid_channels: int = 256
    roi_size: int = 7
    roi_op: str = "align"  # "align" (bilinear ROIAlign) | "pool" (quantized ROIPool)
    roi_sampling_ratio: int = 2  # ROIAlign samples per bin side
    fpn: bool = False  # FPN neck (BASELINE config #3)
    fpn_channels: int = 256  # P-level width (FPN paper)
    # compute dtype for conv stacks; params/losses stay float32
    compute_dtype: str = "bfloat16"
    # jax.checkpoint each residual block in the trunk: the backward pass
    # recomputes block activations instead of holding them in HBM — ~1/3
    # more FLOPs for large activation-memory savings (bigger batches /
    # deeper backbones at 600x600). Parameter trees are unchanged.
    remat: bool = False
    # mesh axis name for cross-replica (sync) BatchNorm — set ONLY when the
    # model runs inside shard_map (`parallel/spmd.py`); under jit
    # auto-partitioning the global-batch BN reduction happens automatically
    # and a named axis here would be unbound.
    bn_axis: Optional[str] = None
    # freeze BatchNorm STATISTICS during training (the detection-
    # fine-tuning practice torchvision implements as FrozenBatchNorm2d):
    # every BN applies its stored running stats, becoming a fusable
    # affine — no batch-stats reductions in the step. Deliberate
    # deviation from torchvision: the affine scale/bias stay trainable
    # (identical param/opt trees with the flag on or off); torchvision
    # freezes those too. Off by default: the reference trains BN in
    # batch-stats mode (torch modules default to train())
    frozen_bn: bool = False
    # normalization at the backbone's BN sites: "batch" (reference
    # semantics) or "group" (GroupNorm(32), the BN-free structural lever
    # from the MFU attribution — no batch-stats reductions/fusion breaks,
    # shard-invariant, but torch-pretrained BN checkpoints don't convert;
    # see models/resnet.py::_norm). VGG16 has no norm layers; the flag is
    # a no-op there.
    norm: str = "batch"

    def __post_init__(self):
        if self.roi_op not in ("align", "pool"):
            raise ValueError(f"roi_op must be 'align' or 'pool', got {self.roi_op!r}")
        if self.norm not in ("batch", "group"):
            raise ValueError(f"norm must be 'batch' or 'group', got {self.norm!r}")
        if self.norm == "group" and self.frozen_bn:
            raise ValueError(
                "frozen_bn freezes BatchNorm statistics; GroupNorm has none "
                "— the combination is meaningless, pick one"
            )
        if self.norm == "group" and self.bn_axis is not None:
            raise ValueError(
                "bn_axis configures cross-replica sync-BN; GroupNorm "
                "normalizes within each sample and needs no axis"
            )

    @property
    def backbone_channels(self) -> int:
        """Feature channels out of the stride-16 trunk (conv1..layer3, or
        conv5_3 for VGG16). Delegates to the model layer's arch tables so
        unknown names fail fast here (at config time) rather than deep
        inside model init."""
        if self.backbone == "vgg16":
            from replication_faster_rcnn_tpu.models.vgg import VGG16_TRUNK_CHANNELS

            return VGG16_TRUNK_CHANNELS
        from replication_faster_rcnn_tpu.models.resnet import trunk_channels

        return trunk_channels(self.backbone)

    @property
    def head_channels(self) -> int:
        """Channels out of the classifier tail (layer4+avgpool, or fc7)."""
        if self.backbone == "vgg16":
            from replication_faster_rcnn_tpu.models.vgg import VGG16_TAIL_CHANNELS

            return VGG16_TAIL_CHANNELS
        from replication_faster_rcnn_tpu.models.resnet import tail_channels

        return tail_channels(self.backbone)


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Data pipeline (reference `utils/data_loader.py:17-117`)."""

    root_dir: str = "data/voc/VOCdevkit/VOC2012"
    dataset: str = "voc"  # voc | coco | synthetic | tokens (a sequence model's rows)
    # tokens a packed row holds (data/tokens.py); read by the sequence model
    # alone, as `image_size` is by the detectors
    seq_len: int = 8192
    image_size: Tuple[int, int] = (600, 600)
    max_boxes: int = 32
    use_difficult: bool = False
    # ImageNet normalization (reference utils/data_loader.py:38)
    pixel_mean: Tuple[float, float, float] = (0.485, 0.456, 0.406)
    pixel_std: Tuple[float, float, float] = (0.229, 0.224, 0.225)
    # host input pipeline (replaces the reference's torch DataLoader,
    # frcnn.py:19-23): worker count and kind. "thread" scales the
    # GIL-releasing native decode; "process" (fork) scales GIL-bound
    # Python sample work across cores
    # -1 = auto: min(4, host cores). Measured on a 1-core host the
    # 4-thread pool was SLOWER than single-thread ingest (pool overhead
    # with nothing to parallelize: 61-86 vs 108-123 img/s,
    # benchmarks/loader_throughput.json) — worker count must follow the
    # host, not a fixed default
    loader_workers: int = -1
    loader_mode: str = "thread"  # thread | process
    loader_prefetch: int = 2
    # memoize decoded samples in host RAM (data/cache.py): epoch 1 pays
    # the decode, later epochs are memcpy — the single-core host's only
    # route past the decode-bound ingest ceiling
    loader_cache_ram: bool = False
    # ship uint8 images to the device and normalize on-chip (the model's
    # preprocess, fused by XLA into the first conv): 4x less host->device
    # transfer, 4x smaller RAM cache, 4x cheaper collate. Off by default:
    # the f32 path matches the reference bit-for-bit
    device_normalize: bool = False
    # 50% horizontal-flip train augmentation (the original Faster R-CNN
    # recipe's only augmentation; the reference trains with none —
    # utils/data_loader.py:56-79 resizes+normalizes only). Deterministic
    # per (seed, epoch, index): resume replays the same flips.
    augment_hflip: bool = False
    # random scale jitter (lo, hi), e.g. (0.75, 1.25): fixed-canvas
    # zoom in/out with random placement, boxes tracked and collapsed
    # rows masked (data/augment.py::scale_jitter_sample). None = off.
    # Same deterministic (seed, epoch, index) keying as the flip.
    augment_scale: Optional[Tuple[float, float]] = None
    # run the jitter's image resample ON DEVICE (ops/image.py): the host
    # transforms boxes only and ships integer jitter geometry with the
    # batch — removes the ~27 ms/600x600 host resample from ingest
    # (measured 37 samples/s host-side on one core vs the 210 img/s
    # one-chip demand). Requires augment_scale.
    augment_scale_device: bool = False
    # FULLY on-device augmentation (ops/image.py::augment_batch): the
    # host loader ships raw samples plus an int32 [idx, epoch] row, and
    # the compiled train step draws every decision (flip coin, scale
    # geometry, translation offsets) from the splitmix hash of
    # (seed, epoch, idx) and applies flip/translate/scale-jitter as one
    # fused batch transform ahead of the bucket resample — the host
    # stops touching pixels entirely. Supersedes augment_scale_device
    # (which still ran the flip and the box affine on host). Composes
    # with every train backend: the draws are a pure function of
    # per-sample metadata, so all ranks and any resume agree with zero
    # communication. Requires augment_hflip, augment_scale, or
    # augment_translate; incompatible with cache_device (the device
    # cache already augments inside its gather).
    augment_device: bool = False
    # translation jitter amplitude as a fraction of the canvas: each
    # sample's content shifts by integer (dy, dx) drawn uniformly from
    # [-t*h, t*h] x [-t*w, t*w], channel-mean fill, boxes tracked and
    # collapsed rows masked. 0 = off. Device-mode only (augment_device):
    # the legacy host pipeline never had this op, so there is no host
    # path to keep parity with — the numpy oracle lives in
    # data/augment.py::translate_sample.
    augment_translate: float = 0.0
    # device-resident dataset cache (data/device_cache.py): upload every
    # sample to HBM once, then each step ships only indices + augment
    # decisions and the batch is gathered/flipped/jittered INSIDE the
    # jitted step. The route past a transfer-bound feed. Needs the dataset
    # to fit HBM — pair with device_normalize for uint8 samples (VOC
    # trainval ~5.4 GB vs 21.6 GB f32).
    cache_device: bool = False
    # double-buffered DEVICE staging (data/prefetch_device.py): a producer
    # thread assembles batch K+1 (stack + shard + device_put) while
    # dispatch K runs, so the trainer's next dispatch consumes an already
    # device-resident buffer instead of paying collate+transfer on the
    # critical path. Value = number of staged batches/chunks held ahead
    # (2 = classic double buffering; each buffered chunk holds a full
    # batch in HBM, so keep it small). 0 = off (default): staging happens
    # synchronously between dispatches, the pre-PR-4 behavior.
    prefetch_device: int = 0
    # multi-scale bucketed training: 2-3 (h, w) resolution buckets. Each
    # global batch is deterministically assigned one bucket (a splitmix
    # hash of seed/epoch/dispatch-chunk — data/augment.py::bucket_index,
    # so `set_epoch(epoch, start_batch=)` resume replays the identical
    # bucket sequence) and trained through that bucket's own compiled
    # program: the step resamples the base-resolution batch to the bucket
    # shape on device and scales the boxes (ops/image.py), composing with
    # K-step fusion (all K batches of a fused dispatch share a bucket),
    # the DevicePrefetcher, and the on-chip scale jitter. The bucket
    # programs register through the warmup ProgramSpec registry, so
    # `frcnn audit` banks one fingerprint per bucket like the serving
    # buckets. () = off (default): the single-resolution path, bitwise
    # identical to before this knob existed.
    train_resolutions: Tuple[Tuple[int, int], ...] = ()

    def __post_init__(self):
        if self.prefetch_device < 0:
            raise ValueError(
                f"prefetch_device must be >= 0, got {self.prefetch_device}"
            )
        if self.augment_scale is not None:
            lo, hi = self.augment_scale
            # fail at config build, not at the first training epoch
            if not 0.1 <= lo <= hi <= 4.0:
                raise ValueError(
                    "augment_scale must satisfy 0.1 <= lo <= hi <= 4.0, "
                    f"got {self.augment_scale!r}"
                )
            # coerce list inputs (dict/JSON config paths) to a tuple so the
            # frozen dataclass stays hashable like its other tuple fields
            object.__setattr__(self, "augment_scale", (float(lo), float(hi)))
        if self.augment_scale_device and self.augment_scale is None:
            raise ValueError(
                "augment_scale_device requires augment_scale to be set"
            )
        if not 0.0 <= self.augment_translate < 1.0:
            raise ValueError(
                "augment_translate must be in [0, 1), got "
                f"{self.augment_translate!r}"
            )
        if self.augment_translate and not self.augment_device:
            raise ValueError(
                "augment_translate is a device-mode op: set "
                "data.augment_device=True (the host pipeline has no "
                "translation path)"
            )
        if self.augment_device:
            if not (
                self.augment_hflip
                or self.augment_scale is not None
                or self.augment_translate
            ):
                raise ValueError(
                    "augment_device is set but no augmentation op is "
                    "enabled (augment_hflip / augment_scale / "
                    "augment_translate)"
                )
            if self.augment_scale_device:
                raise ValueError(
                    "augment_device supersedes augment_scale_device — "
                    "set only one"
                )
            if self.cache_device:
                raise ValueError(
                    "augment_device is incompatible with cache_device: "
                    "the device cache already flips/jitters inside its "
                    "gather (data/device_cache.py)"
                )
        if self.train_resolutions:
            res = tuple(
                (int(r[0]), int(r[1])) for r in self.train_resolutions
            )
            for h, w in res:
                if h < 1 or w < 1:
                    raise ValueError(
                        "data.train_resolutions entries must be positive "
                        f"(h, w) pairs, got {(h, w)}"
                    )
            if len(set(res)) != len(res):
                raise ValueError(
                    f"data.train_resolutions has duplicates: {res!r}"
                )
            # canonical smallest-area-first order (same rule as
            # serving.bucket_resolutions): bucket INDEX is part of the
            # deterministic assignment, so the order must not depend on
            # how the user happened to spell the list
            object.__setattr__(
                self,
                "train_resolutions",
                tuple(sorted(res, key=lambda r: (r[0] * r[1], r))),
            )
        else:
            # coerce None/[] (JSON round-trips) to the canonical empty tuple
            object.__setattr__(self, "train_resolutions", ())


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Optimization (reference `train.py:139-159`)."""

    lr: float = 1e-4
    # The reference's __main__ uses lr=0.01 with Adam, which diverges in
    # practice; 1e-4 is the stable default. `--lr` restores any value.
    weight_decay: float = 5e-6
    # optimizer family: "adam" (the reference's choice) or "lamb" —
    # Adam preconditioning + per-layer trust-ratio rescaling
    # (arXiv:1904.00962 via the You et al. large-batch line; see
    # train/train_step.py::make_optimizer). Unlike the `lars` flag below,
    # LAMB composes with ZeRO-1 sharded optimizer state on the shard_map
    # backend: its per-layer norms are computed from shard-local partial
    # sums psummed over the data axis (scale_by_sharded_trust_ratio).
    optimizer: str = "adam"  # adam | lamb
    n_epoch: int = 50
    batch_size: int = 8  # per-step global batch (reference default 2)
    smooth_l1_sigma: float = 1.0
    checkpoint_every_epochs: int = 10
    # additional dispatch-boundary scheduled saves every N global steps
    # (0 = off, the default: epoch-granular saves only). Elastic fleets
    # want this tight — a surviving rank resumes from the last verified
    # step, so this knob bounds the re-trained window after a shrink.
    # Step counts are deterministic across ranks, so multi-process saves
    # stay lockstep collectives.
    checkpoint_every_steps: int = 0
    seed: int = 0
    # loss weights: the reference sums the 4 losses unweighted (train.py:123)
    loss_weights: Tuple[float, float, float, float] = (1.0, 1.0, 1.0, 1.0)
    # SPMD backend: "auto" = jit auto-partitioning (XLA places collectives),
    # "spmd" = explicit shard_map step with hand-placed psums + sync-BN
    # (`parallel/spmd.py`); both compute the same update (tested).
    backend: str = "auto"
    # ZeRO-1 / cross-replica weight-update sharding (arXiv:2004.13336,
    # `parallel/zero.py`): shard Adam moments over the data axis; each chip
    # updates 1/N of the weights (reduce-scatter + all-gather — inserted by
    # GSPMD on the auto-partitioning backend, hand-placed in
    # `parallel/spmd.py` on the explicit shard_map backend; both share the
    # per-leaf layout so checkpoints move freely between them).
    shard_opt_state: bool = False
    # large-batch LR recipe ("Extremely Large Minibatch SGD",
    # arXiv:1711.04325). "linear" scales the schedule's peak lr by
    # batch_size / base_batch_size, so scaling out the data axis keeps
    # the per-example update magnitude — set base_batch_size to the batch
    # the configured lr was tuned at. "none" = lr used as-is (default).
    lr_scaling: str = "none"  # none | linear
    base_batch_size: int = 8
    # linear LR warmup over the first warmup_epochs (fractional ok): ramps
    # from ~0 to the (scaled) peak before the cosine schedule takes over —
    # the large-batch stabilizer from arXiv:1711.04325. 0 = off (default).
    warmup_epochs: float = 0.0
    # layer-wise trust-ratio scaling (LARS-style, applied after Adam as in
    # LAMB): each leaf's update is rescaled by |param| / |update|, bounding
    # the per-layer relative step at very large batch. Adds an (empty)
    # optax state entry, so flipping it invalidates optimizer checkpoints.
    lars: bool = False
    # run the mAP evaluator on the val split every N epochs (0 = off)
    eval_every_epochs: int = 0
    # dtype for Adam's first moment (mu). bfloat16 halves the moment
    # buffer traffic in the update phase — the v5e breakdown puts
    # backward+update at >50% of the step (VERDICT r2 weak #2); nu and
    # the params stay float32 (nu's magnitudes underflow bf16)
    adam_mu_dtype: str = "float32"  # float32 | bfloat16
    # fused multi-step dispatch: one jitted call trains K steps via
    # lax.scan over K device-resident batches (train/train_step.py::
    # build_multi_step, parallel/spmd.py), amortizing per-step Python
    # dispatch + pytree flattening. Metrics come back stacked [K, ...];
    # the Trainer reads them on host only at log boundaries, so async
    # dispatch overlaps across the whole chunk. 1 = the plain per-step
    # path (default).
    steps_per_dispatch: int = 1
    # dtype the gradient all-reduce rides in ("Extremely Large Minibatch
    # SGD", arXiv:1711.04325 — half-precision gradient exchange). On the
    # explicit shard_map backend grads are cast to this dtype BEFORE the
    # lax.psum and de-cast for the fp32 optimizer math, halving
    # all-reduce bytes; on the auto-partitioning backend (where XLA's
    # all-reduces live inside the fused backward and cannot be re-dtyped
    # from here) the summed grads take the same bf16 round-trip, keeping
    # the two backends within bf16 rounding of each other (pre- vs
    # post-sum quantization). float32 = off (default).
    grad_allreduce_dtype: str = "float32"  # float32 | bfloat16
    # what the jitted step does with a non-finite gradient tree
    # (train/fault.py::guarded_update): "skip" (default) withholds the
    # optimizer update — params, Adam moments and BN stats carry through
    # bit-identical, the step's metrics carry skipped=1 — so one poisoned
    # batch costs one step instead of NaN'ing Adam's moments for the rest
    # of the run; "halt" gates the same way but the trainer raises on the
    # first skip; "apply" is the unguarded pre-fault-tolerance behavior.
    nonfinite_policy: str = "skip"  # apply | skip | halt
    # consecutive skipped steps before the trainer raises a descriptive
    # error (and records a watchdog incident) instead of free-running on
    # a divergent model: transients cost 1-2 steps, persistent NaNs are
    # a bug to surface, not ride through.
    max_consecutive_skips: int = 10
    # background scheduled checkpointing (train/async_checkpoint.py): a
    # scheduled save snapshots state to host once (the only blocking
    # part), then serialization + CRC manifest + atomic rename run on a
    # single background writer; the epoch loop blocks only if the
    # PREVIOUS save is still in flight. Emergency/final/crash saves stay
    # synchronous, and restore-side manifest verification is unchanged.
    # Single-process runtimes only (the writer hands orbax a host-numpy
    # snapshot, which has no multi-host replica story).
    async_checkpoint: bool = False
    # second-stage region sampling strategy (targets/proposal_targets.py):
    # "random" (default) draws the positive/negative ROI quotas uniformly
    # at random among the eligible candidates — the reference recipe,
    # byte-identical to the pre-knob programs; "topk_iou" ranks the
    # eligible candidates by their max IoU with ground truth and keeps
    # the top-K of each quota deterministically — the biased sampling
    # family of arXiv:1702.02138 ("An Implementation of Faster RCNN with
    # Study for Region Sampling"): highest-overlap positives plus
    # hardest (highest-IoU-below-threshold) negatives.
    sampling_strategy: str = "random"  # random | topk_iou

    def __post_init__(self):
        if self.backend not in ("auto", "spmd"):
            raise ValueError(f"backend must be 'auto' or 'spmd', got {self.backend!r}")
        if self.optimizer not in ("adam", "lamb"):
            raise ValueError(
                f"optimizer must be 'adam' or 'lamb', got {self.optimizer!r}"
            )
        if self.optimizer == "lamb" and self.lars:
            raise ValueError(
                "optimizer='lamb' already applies the per-layer trust "
                "ratio after Adam; combining it with lars=True would "
                "rescale twice — drop one"
            )
        if self.checkpoint_every_steps < 0:
            raise ValueError(
                "checkpoint_every_steps must be >= 0 (0 = off), got "
                f"{self.checkpoint_every_steps}"
            )
        if self.adam_mu_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"adam_mu_dtype must be float32|bfloat16, got {self.adam_mu_dtype!r}"
            )
        if self.grad_allreduce_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                "grad_allreduce_dtype must be float32|bfloat16, got "
                f"{self.grad_allreduce_dtype!r}"
            )
        if self.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got {self.steps_per_dispatch}"
            )
        if self.nonfinite_policy not in ("apply", "skip", "halt"):
            raise ValueError(
                "nonfinite_policy must be apply|skip|halt, got "
                f"{self.nonfinite_policy!r}"
            )
        if self.max_consecutive_skips < 1:
            raise ValueError(
                "max_consecutive_skips must be >= 1, got "
                f"{self.max_consecutive_skips}"
            )
        if self.lr_scaling not in ("none", "linear"):
            raise ValueError(
                f"lr_scaling must be 'none' or 'linear', got {self.lr_scaling!r}"
            )
        if self.base_batch_size < 1:
            raise ValueError(
                f"base_batch_size must be >= 1, got {self.base_batch_size}"
            )
        if self.warmup_epochs < 0:
            raise ValueError(
                f"warmup_epochs must be >= 0, got {self.warmup_epochs}"
            )
        if self.sampling_strategy not in ("random", "topk_iou"):
            raise ValueError(
                "sampling_strategy must be 'random' or 'topk_iou', got "
                f"{self.sampling_strategy!r}"
            )


@dataclasses.dataclass(frozen=True)
class EvalConfig:
    """Inference decode + mAP. The reference never wrote its eval path
    (`test_eval.py` is empty, SURVEY.md §3.2) so these are our own choices."""

    score_thresh: float = 0.05
    nms_thresh: float = 0.3
    max_detections: int = 100
    iou_thresh: float = 0.5  # mAP@0.5
    use_07_metric: bool = False  # area-under-PR by default; True = 11-point
    metric: str = "voc"  # "voc" (mAP@iou_thresh) | "coco" (mAP@[.50:.95])
    # flip test-time augmentation: a second forward on the mirrored
    # image, candidates reflected back and merged before the shared
    # per-class NMS (eval/detect.py::decode_detections_tta). ~2x eval
    # compute for a small mAP gain; off by default
    tta_hflip: bool = False

    def __post_init__(self):
        if self.metric not in ("voc", "coco"):
            raise ValueError(f"metric must be 'voc' or 'coco', got {self.metric!r}")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device mesh for SPMD parallelism (SURVEY.md §2.4). The workload is
    data-parallel; the `model` axis exists so tensor-parallel shardings can
    be introduced without changing the mesh plumbing.

    ``spatial`` turns on spatial partitioning over the ``model`` axis: each
    image's row (H) dimension is sharded across it, the vision analogue of
    sequence/context parallelism (there is no sequence axis in a detector —
    SURVEY.md §5 — the long axis is image extent). GSPMD inserts the halo
    exchanges every conv needs at shard boundaries; one image then spans
    ``num_model`` chips, so images larger than a single chip's HBM budget
    still train. Requires the default jit auto-partitioning backend.

    ``param_sharding`` turns on model parallelism over the same ``model``
    axis: every conv kernel / head weight is sharded on its largest
    mp-divisible dimension (the `parallel/zero.py` ``shard_dim`` rule,
    pointed at the model axis), so each chip holds ~1/num_model of the
    parameters and GSPMD inserts the weight all-gathers / gradient
    reductions the forward/backward needs. The CLI spelling is
    ``--mesh-shape DP,MP`` (sets num_data=DP, num_model=MP and flips this
    flag when MP > 1). Composes with ZeRO-1 (``train.shard_opt_state``)
    over the ``data`` axis; requires the jit auto-partitioning backend,
    and is mutually exclusive with ``spatial`` (one sharding story per
    model axis)."""

    data_axis: str = "data"
    model_axis: str = "model"
    num_data: int = -1  # -1: all available devices
    num_model: int = 1
    spatial: bool = False  # shard image rows over the model axis
    param_sharding: bool = False  # shard weights over the model axis (mp)


@dataclasses.dataclass(frozen=True)
class CompileConfig:
    """Compilation warm start (train/warmup.py).

    JAX's persistent XLA compilation cache is on by default: every compiled
    program is keyed by its HLO + compile options and written under one
    directory, so a SECOND process start for the same config
    deserializes executables instead of re-running XLA (minutes on the
    big presets). ``JAX_COMPILATION_CACHE_DIR`` places the directory and
    nothing here overrides it; where it is unset ``cache_dir`` does, and
    an empty string means the fixed ``.compile_cache/`` in the checkout
    (`train/warmup.py::place_compile_cache` owns the rule; on the CPU
    backend nothing is kept unless the environment asks). The
    ``warmup`` CLI subcommand AOT-compiles the train/eval programs for a
    config to populate the cache ahead of the real run."""

    cache_dir: str = ""  # "" = the fixed in-checkout default

    def __post_init__(self):
        if not isinstance(self.cache_dir, str):
            raise ValueError(
                "compile.cache_dir must be a string path, got "
                f"{self.cache_dir!r}"
            )


@dataclasses.dataclass(frozen=True)
class DebugConfig:
    """Runtime hygiene checks (analysis/strict.py).

    ``strict`` engages jax.transfer_guard("disallow") for the whole
    training session plus a per-program recompile gate around every
    dispatch: after each program's first (warmup) dispatch, any implicit
    host<->device transfer or recompilation raises instead of silently
    eating throughput. Costs nothing per step beyond a counter compare;
    intended for CI and bringup, safe to leave on for real runs.

    ``strict_warmup`` is the number of dispatches per program allowed to
    compile (and stage constants) before the gate arms; ≥ 1.

    ``threadsan`` engages the runtime lock sanitizer
    (analysis/threadsan.py): package-created locks and queues are
    instrumented, lock-order inversions raise, and held-duration /
    queue-depth gauges feed the telemetry watchdog. The runtime half of
    the threadlint static gate; CI-tier cost, not for production serving.
    """

    strict: bool = False
    strict_warmup: int = 1
    threadsan: bool = False
    # seeded fault-injection schedule (faultlib/failpoints.py):
    # "site:kind:prob:seed[:arg[:max_fires[:after]]],..." or a JSON schedule
    # path. Empty = disarmed (the failpoints are zero-overhead no-ops).
    # Armed by the CLI entry points from --chaos-spec.
    chaos_spec: str = ""

    def __post_init__(self):
        if not isinstance(self.strict_warmup, int) or self.strict_warmup < 1:
            raise ValueError(
                "debug.strict_warmup must be an int >= 1, got "
                f"{self.strict_warmup!r}"
            )


@dataclasses.dataclass(frozen=True)
class AnalysisConfig:
    """Static-analysis gates (analysis/hlolint.py).

    ``hbm_budget_bytes`` bounds the HLO auditor's compiled peak-memory
    estimate per program (rule HX004); the default is one v5e chip's
    16 GiB HBM. ``fingerprint_dir`` overrides where `frcnn audit` reads
    and re-banks compiled-program fingerprints; empty string (default)
    uses the committed bank under the package's ``analysis/fingerprints``.

    ``replicated_bytes_threshold`` is shardlint's SL001 floor: an arg
    buffer at least this large, replicated over a >1 model axis despite a
    divisible dim, is a finding (default 1 MiB — batch-norm vectors pass,
    conv kernels and optimizer moments do not). ``comm_budget_bytes``
    caps any one program's statically-priced collective wire bytes per
    device per step (shardlint SL005 / `frcnn audit`); the default is
    ~2x the largest banked CI program, so growth trips the gate before
    it doubles a step's interconnect traffic.
    """

    hbm_budget_bytes: int = 16 << 30
    fingerprint_dir: str = ""
    replicated_bytes_threshold: int = 1 << 20
    comm_budget_bytes: int = 512 << 20

    def __post_init__(self):
        if not isinstance(self.hbm_budget_bytes, int) or self.hbm_budget_bytes <= 0:
            raise ValueError(
                "analysis.hbm_budget_bytes must be a positive int, got "
                f"{self.hbm_budget_bytes!r}"
            )
        if not isinstance(self.fingerprint_dir, str):
            raise ValueError(
                "analysis.fingerprint_dir must be a string path, got "
                f"{self.fingerprint_dir!r}"
            )
        if (
            not isinstance(self.replicated_bytes_threshold, int)
            or self.replicated_bytes_threshold <= 0
        ):
            raise ValueError(
                "analysis.replicated_bytes_threshold must be a positive "
                f"int, got {self.replicated_bytes_threshold!r}"
            )
        if not isinstance(self.comm_budget_bytes, int) or self.comm_budget_bytes <= 0:
            raise ValueError(
                "analysis.comm_budget_bytes must be a positive int, got "
                f"{self.comm_budget_bytes!r}"
            )


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Elastic fleet training (parallel/elastic.py, `frcnn train --elastic`).

    A per-host supervisor process spawns the training child once per fleet
    *generation*; inside the child a heartbeat thread renews this rank's
    lease file every ``heartbeat_interval_s`` and the trainer checks peer
    leases at dispatch boundaries. A peer whose lease is older than
    ``lease_timeout_s`` is declared lost: the survivor exits with
    ``EXIT_FLEET_SHRINK`` (falling back to its last CRC-verified
    checkpoint) and the supervisors re-form the fleet at the surviving
    world size on a bumped coordinator port — resuming INSIDE the same
    epoch via the offset-based feeds.

    ``lease_timeout_s`` must stay well under ~10 s: the JAX coordination
    service force-aborts (SIGABRT) a process whose peers stop heartbeating
    after about that long, and the survivor must detect the loss, persist
    its shrink intent, and exit cleanly BEFORE that abort lands — there is
    no catchable error path once a gloo collective hangs on a dead peer.
    """

    heartbeat_interval_s: float = 0.5
    lease_timeout_s: float = 5.0
    # how long re-forming supervisors wait for survivor claims before the
    # lowest surviving rank writes the generation plan
    settle_s: float = 2.0
    # supervisor gives up after this many re-formations (a fleet that
    # shrinks every few steps has an environment problem, not a rank loss)
    max_generations: int = 8

    def __post_init__(self):
        if self.heartbeat_interval_s <= 0:
            raise ValueError(
                "elastic.heartbeat_interval_s must be > 0, got "
                f"{self.heartbeat_interval_s}"
            )
        if self.lease_timeout_s <= self.heartbeat_interval_s:
            raise ValueError(
                "elastic.lease_timeout_s must exceed heartbeat_interval_s "
                f"({self.heartbeat_interval_s}), got {self.lease_timeout_s}"
            )
        if self.settle_s <= 0:
            raise ValueError(
                f"elastic.settle_s must be > 0, got {self.settle_s}"
            )
        if self.max_generations < 1:
            raise ValueError(
                "elastic.max_generations must be >= 1, got "
                f"{self.max_generations}"
            )


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Bucketed AOT inference serving (serving/engine.py).

    The engine compiles one inference program per (resolution bucket ×
    batch size) at startup, holds the inference params device-resident in
    ``params_dtype``, and coalesces concurrent requests into bucket-sized
    micro-batches (flush on size OR ``max_delay_ms``). Requests larger
    than every bucket follow ``oversize``: "downscale" routes them to the
    largest bucket (the one-shot ``predict_image`` behavior), "reject"
    raises so a front-end can shed them instead of silently degrading.
    """

    # () = derived: the configured train/eval resolution plus its half —
    # two buckets cover "full-size" and "thumbnail" traffic without any
    # per-deployment tuning. Explicit tuples override, smallest-area
    # bucket tried first.
    resolutions: Tuple[Tuple[int, int], ...] = ()
    # compiled batch sizes per bucket; a flush picks the smallest
    # compiled batch >= the number of waiting requests and pads to it
    batch_sizes: Tuple[int, ...] = (1, 8)
    # deadline trigger: a waiting request is never delayed longer than
    # this hoping for batch-mates (0 = flush whenever the queue idles)
    max_delay_ms: float = 10.0
    # bounded submission queue depth — backpressure, same discipline as
    # data/prefetch_device.py (submit blocks/raises rather than queueing
    # unboundedly while the device falls behind)
    queue_depth: int = 64
    # dtype the resident inference params are held in on upload. bf16
    # halves HBM residency (the flax modules cast per-layer anyway);
    # "int8" halves it again: planned layer groups stay device-resident
    # as int8 weights + per-channel scales (quant/ sidecar artifact
    # required, see `frcnn quantize`), the rest fall back to bf16
    params_dtype: str = "bfloat16"  # float32 | bfloat16 | int8
    oversize: str = "downscale"  # downscale | reject
    # per-request deadline, end to end: the HTTP handler's future wait
    # times out to 504 after this many seconds, and an entry whose
    # deadline passes while it waits in the queue is dropped at flush
    # time (never dispatched). 0 disables deadlines (unbounded waits).
    request_timeout_s: float = 0.0
    # SLO-driven micro-batch deadlines (serving/slo.py): when enabled,
    # each bucket's max_delay_ms self-tunes from the observed queue-wait
    # p99 — one bounded multiplicative step (x/÷ adaptive_delay_step) per
    # adaptation, clamped to [delay_floor_ms, delay_ceiling_ms]. Wait p99
    # near adaptive_slo_ms shortens the deadline (stop holding requests
    # the SLO can't afford); a comfortably-met SLO with partial flushes
    # lengthens it (wait for batch-mates, amortize dispatch).
    adaptive_delay: bool = False
    adaptive_slo_ms: float = 100.0  # target queue-wait p99 per request
    delay_floor_ms: float = 1.0
    delay_ceiling_ms: float = 100.0
    adaptive_delay_step: float = 1.25

    def __post_init__(self):
        object.__setattr__(
            self,
            "resolutions",
            tuple(
                (int(r[0]), int(r[1])) for r in self.resolutions
            ),
        )
        object.__setattr__(
            self, "batch_sizes", tuple(int(b) for b in self.batch_sizes)
        )
        for h, w in self.resolutions:
            if h < 1 or w < 1:
                raise ValueError(
                    f"serving.resolutions entries must be positive, got {(h, w)}"
                )
        if not self.batch_sizes or any(b < 1 for b in self.batch_sizes):
            raise ValueError(
                "serving.batch_sizes must be a non-empty tuple of ints >= 1, "
                f"got {self.batch_sizes!r}"
            )
        if self.max_delay_ms < 0:
            raise ValueError(
                f"serving.max_delay_ms must be >= 0, got {self.max_delay_ms}"
            )
        if self.queue_depth < 1:
            raise ValueError(
                f"serving.queue_depth must be >= 1, got {self.queue_depth}"
            )
        if self.params_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                "serving.params_dtype must be float32|bfloat16|int8, got "
                f"{self.params_dtype!r}"
            )
        if self.oversize not in ("downscale", "reject"):
            raise ValueError(
                "serving.oversize must be 'downscale' or 'reject', got "
                f"{self.oversize!r}"
            )
        if self.request_timeout_s < 0:
            raise ValueError(
                "serving.request_timeout_s must be >= 0 (0 = no deadline), "
                f"got {self.request_timeout_s}"
            )
        if self.adaptive_slo_ms <= 0:
            raise ValueError(
                "serving.adaptive_slo_ms must be > 0, got "
                f"{self.adaptive_slo_ms}"
            )
        if not 0 < self.delay_floor_ms <= self.delay_ceiling_ms:
            raise ValueError(
                "serving delay bounds need 0 < delay_floor_ms <= "
                f"delay_ceiling_ms, got floor={self.delay_floor_ms} "
                f"ceiling={self.delay_ceiling_ms}"
            )
        if self.adaptive_delay_step <= 1.0:
            raise ValueError(
                "serving.adaptive_delay_step is multiplicative and must be "
                f"> 1.0, got {self.adaptive_delay_step}"
            )

    def bucket_resolutions(
        self, image_size: Tuple[int, int]
    ) -> Tuple[Tuple[int, int], ...]:
        """The resolved bucket list, smallest area first."""
        if self.resolutions:
            res = set(self.resolutions)
        else:
            h, w = image_size
            res = {(max(1, h // 2), max(1, w // 2)), (h, w)}
        return tuple(sorted(res, key=lambda r: (r[0] * r[1], r)))


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Multi-replica serving fleet (serving/fleet/, `frcnn fleet`).

    A front router owns a health-checked replica registry (periodic
    ``/healthz`` probes with lease-style staleness, the PR 11 heartbeat
    discipline applied to serving), dispatches by consistent hash over
    (content-hash, bucket), and self-heals: per-replica circuit breakers,
    failover re-dispatch, hedged retries after a p99-derived delay, and
    probe-driven drain/rejoin so a restarted replica re-enters rotation
    without dropped traffic.
    """

    # ---- registry / prober
    probe_interval_s: float = 0.5  # /healthz probe cadence per replica
    # a replica whose last successful probe is older than this is DEAD
    # (lease staleness — missing probes age the lease out, exactly like
    # elastic.lease_timeout_s ages out training heartbeats)
    lease_timeout_s: float = 3.0
    # consecutive successful probes a DEAD/JOINING replica needs before
    # it re-enters rotation (a flapping replica can't bounce in and out)
    rejoin_probes: int = 2
    # ---- circuit breaker (per replica)
    breaker_threshold: int = 3  # consecutive dispatch failures to open
    breaker_cooldown_s: float = 1.0  # open -> half-open probe delay
    # ---- dispatch
    max_attempts: int = 3  # primary + failover re-dispatches per request
    request_timeout_s: float = 30.0  # per-attempt replica call deadline
    vnodes: int = 64  # consistent-hash ring points per replica
    # content-hash result cache entries (duplicate images are answered
    # from the router without touching a replica; 0 disables)
    cache_entries: int = 256
    # ---- hedging: after hedge_multiplier x observed p99 (clamped to
    # [hedge_floor_ms, hedge_ceiling_ms]) with no primary response, a
    # second copy goes to the next ring replica; first result wins
    hedge: bool = True
    hedge_multiplier: float = 1.5
    hedge_floor_ms: float = 5.0
    hedge_ceiling_ms: float = 2000.0
    latency_window: int = 128  # per-router latency samples for the p99
    # ---- canary / shadow
    # fraction of requests routed to the canary replica first (decided
    # by content hash, so the split is deterministic per image)
    canary_fraction: float = 0.05
    # ---- replica-side drain: how long a SIGTERMed `frcnn serve
    # --replica-id` advertises draining=true in /healthz (so the router
    # stops routing to it) before it stops accepting connections
    drain_grace_s: float = 1.0
    # ---- SLO error-budget burn-rate (telemetry/slo_burn.py): every
    # dispatch ATTEMPT outcome (not just final request outcomes — with
    # failover a dying replica barely dents request availability, but
    # its failed attempts are the leading indicator) feeds multi-window
    # burn accounting; the alarm (burn > 1 on BOTH windows) surfaces in
    # /stats and auto-demotes an alarming canary back to serving role
    slo_availability_target: float = 0.999  # error budget = 1 - target
    slo_latency_target_ms: float = 0.0  # 0 = availability-only budget
    slo_short_window_s: float = 300.0  # alarm-clearing window (5 m)
    slo_long_window_s: float = 3600.0  # alarm-meaning window (1 h)

    def __post_init__(self):
        if self.probe_interval_s <= 0:
            raise ValueError(
                f"fleet.probe_interval_s must be > 0, got {self.probe_interval_s}"
            )
        if self.lease_timeout_s <= self.probe_interval_s:
            raise ValueError(
                "fleet.lease_timeout_s must exceed probe_interval_s "
                f"({self.probe_interval_s}), got {self.lease_timeout_s}"
            )
        if self.rejoin_probes < 1:
            raise ValueError(
                f"fleet.rejoin_probes must be >= 1, got {self.rejoin_probes}"
            )
        if self.breaker_threshold < 1:
            raise ValueError(
                "fleet.breaker_threshold must be >= 1, got "
                f"{self.breaker_threshold}"
            )
        if self.breaker_cooldown_s <= 0:
            raise ValueError(
                "fleet.breaker_cooldown_s must be > 0, got "
                f"{self.breaker_cooldown_s}"
            )
        if self.max_attempts < 1:
            raise ValueError(
                f"fleet.max_attempts must be >= 1, got {self.max_attempts}"
            )
        if self.request_timeout_s <= 0:
            raise ValueError(
                "fleet.request_timeout_s must be > 0, got "
                f"{self.request_timeout_s}"
            )
        if self.vnodes < 1:
            raise ValueError(f"fleet.vnodes must be >= 1, got {self.vnodes}")
        if self.cache_entries < 0:
            raise ValueError(
                f"fleet.cache_entries must be >= 0, got {self.cache_entries}"
            )
        if self.hedge_multiplier <= 0:
            raise ValueError(
                "fleet.hedge_multiplier must be > 0, got "
                f"{self.hedge_multiplier}"
            )
        if not 0 < self.hedge_floor_ms <= self.hedge_ceiling_ms:
            raise ValueError(
                "fleet hedge bounds need 0 < hedge_floor_ms <= "
                f"hedge_ceiling_ms, got floor={self.hedge_floor_ms} "
                f"ceiling={self.hedge_ceiling_ms}"
            )
        if self.latency_window < 1:
            raise ValueError(
                f"fleet.latency_window must be >= 1, got {self.latency_window}"
            )
        if not 0.0 <= self.canary_fraction <= 1.0:
            raise ValueError(
                "fleet.canary_fraction must be in [0, 1], got "
                f"{self.canary_fraction}"
            )
        if self.drain_grace_s < 0:
            raise ValueError(
                f"fleet.drain_grace_s must be >= 0, got {self.drain_grace_s}"
            )
        if not 0.0 < self.slo_availability_target < 1.0:
            raise ValueError(
                "fleet.slo_availability_target must be in (0, 1), got "
                f"{self.slo_availability_target}"
            )
        if self.slo_latency_target_ms < 0:
            raise ValueError(
                "fleet.slo_latency_target_ms must be >= 0, got "
                f"{self.slo_latency_target_ms}"
            )
        if not 0 < self.slo_short_window_s < self.slo_long_window_s:
            raise ValueError(
                "fleet SLO windows need 0 < slo_short_window_s < "
                f"slo_long_window_s, got short={self.slo_short_window_s} "
                f"long={self.slo_long_window_s}"
            )


@dataclasses.dataclass(frozen=True)
class OpsConfig:
    """Detection-op kernel backend (ops/__init__.py::resolve_backend).

    ``backend`` selects the implementation family for the detection hot
    ops — greedy NMS, ROIAlign, and the IoU/anchor-matching pass:

    * ``"xla"`` (default): the pure-XLA tilings (`ops/nms_tiled.py`,
      `ops/roi_ops.py`, `ops/boxes.py`). Compiled programs are
      byte-identical to every committed fingerprint bank.
    * ``"pallas"``: the Pallas kernels in `ops/pallas/` — interpret-mode
      (pure JAX) off-TPU so the same kernel code is parity-tested on CPU,
      Mosaic-compiled on a real TPU, and only ever compiled on-chip
      through the warmup ProgramSpec registry.

    The env var ``FRCNN_OPS_BACKEND`` overrides this key at process level
    (resolved once, at the first dispatch); `ops.backend_scope` overrides
    it lexically for a single trace.
    """

    backend: str = "xla"

    def __post_init__(self):
        if self.backend not in ("xla", "pallas"):
            raise ValueError(
                f"ops.backend must be 'xla' or 'pallas', got {self.backend!r}"
            )


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Post-training int8 quantization (quant/, `frcnn quantize`).

    Calibration collects per-channel symmetric int8 weight scales plus
    per-layer-group activation ranges from a small sweep through the
    Evaluator inference path, and writes them as a CRC-manifested
    sidecar artifact next to the checkpoint. The optional sensitivity
    sweep (`frcnn quantize --sweep`) quantizes one layer group at a
    time, measures response-reconstruction error (arXiv:1806.00370) and
    the mAP delta on a mini eval set, and records a per-group dtype
    plan: groups whose solo-quantization cost exceeds the thresholds
    fall back to bf16 at serve time instead of int8.
    """

    # sidecar artifact path used by `serving.params_dtype="int8"`; ""
    # means "<checkpoint_dir>/quant_artifact.json" (the default written
    # by `frcnn quantize`)
    artifact: str = ""
    # calibration sweep size: batches x batch_size images drawn in
    # dataset order (deterministic — same order => bit-identical scales)
    calib_batches: int = 2
    calib_batch_size: int = 2
    # sensitivity sweep fallback thresholds, per layer group: a group
    # whose solo-int8 mAP drop exceeds `sensitivity_map_drop_pt` mAP
    # points OR whose response-reconstruction relative error exceeds
    # `sensitivity_recon_rel_err` is planned as bf16, not int8
    sensitivity_map_drop_pt: float = 0.1
    sensitivity_recon_rel_err: float = 0.25

    def __post_init__(self):
        if self.calib_batches < 1:
            raise ValueError(
                f"quant.calib_batches must be >= 1, got {self.calib_batches}"
            )
        if self.calib_batch_size < 1:
            raise ValueError(
                "quant.calib_batch_size must be >= 1, got "
                f"{self.calib_batch_size}"
            )
        if self.sensitivity_map_drop_pt < 0:
            raise ValueError(
                "quant.sensitivity_map_drop_pt must be >= 0, got "
                f"{self.sensitivity_map_drop_pt}"
            )
        if self.sensitivity_recon_rel_err <= 0:
            raise ValueError(
                "quant.sensitivity_recon_rel_err must be > 0, got "
                f"{self.sensitivity_recon_rel_err}"
            )


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Observability layer knobs (telemetry/).

    The serving tiers are instrumented unconditionally through
    ``current_tracer()`` / ``MetricsRegistry`` — these knobs govern the
    cross-process pieces: whether trace context crosses HTTP hops, how
    large a per-process trace buffer may grow, and the latency
    histogram bucket grid both tiers register with.
    """

    # inject/extract the W3C traceparent header across fleet HTTP hops;
    # off = spans still record locally but requests don't correlate
    trace_propagation: bool = True
    # SpanTracer in-memory event bound for serving-tier tracers
    # (overflow drops events and counts them, never grows)
    trace_max_events: int = 200_000
    # latency histogram upper bounds in ms; () = the built-in
    # log-spaced 1 ms .. 60 s grid (telemetry/metrics.py)
    latency_buckets_ms: Tuple[float, ...] = ()

    def __post_init__(self):
        if self.trace_max_events < 1:
            raise ValueError(
                "telemetry.trace_max_events must be >= 1, got "
                f"{self.trace_max_events}"
            )
        b = list(self.latency_buckets_ms)
        if b and (sorted(b) != b or b[0] <= 0):
            raise ValueError(
                "telemetry.latency_buckets_ms must be ascending and "
                f"positive, got {self.latency_buckets_ms}"
            )

    def buckets_s(self) -> Optional[Tuple[float, ...]]:
        """The configured grid in seconds, or ``None`` for the default."""
        if not self.latency_buckets_ms:
            return None
        return tuple(ms / 1000.0 for ms in self.latency_buckets_ms)


@dataclasses.dataclass(frozen=True)
class RolloutConfig:
    """Rolling weight rollout control plane (serving/rollout/).

    The trainer's ``workdir/manifests/`` feed publishes CRC-manifested
    checkpoint versions; the rollout controller validates eligibility
    (manifest CRC + topology + quant sidecar, *before* any replica
    drains), then drives a rolling fleet upgrade through the registry:
    drain one replica (DRAINING keeps the lease), hot-swap its params,
    re-admit on `fleet.rejoin_probes` consecutive OKs at the new
    version. The first upgraded replica lands as CANARY; a windowed
    burn-rate + shadow-diff gate decides promote vs rollback, and
    rollback is a first-class reverse rollout.
    """

    # watcher poll interval over workdir/manifests/
    poll_interval_s: float = 2.0
    # how long the controller waits for a held replica's queues to
    # drain before swapping (simulated clocks make this cheap in tests)
    drain_timeout_s: float = 10.0
    # per-replica budget for the swap RPC itself
    swap_timeout_s: float = 30.0
    # budget for a swapped replica to re-reach HEALTHY at the new
    # version before the wave is declared failed and rolled back
    rejoin_timeout_s: float = 10.0
    # canary gate: minimum routed canary requests before the windowed
    # decision may *promote* (rollback triggers need no minimum)
    canary_min_requests: int = 0
    # how long the new version must hold CANARY before promotion
    canary_hold_s: float = 5.0
    # rollback if shadow_diffs / shadow_requests exceeds this fraction
    # during the hold window (only when shadow traffic exists)
    max_shadow_diff_fraction: float = 0.25
    # require the manifest's config hash to match the serving config
    # (disable when rolling between intentionally different configs)
    require_config_hash: bool = True
    # auto-reverse the wave on canary alarm/demotion; False = hold as
    # CANARY and leave the decision to the operator
    auto_rollback: bool = True

    def __post_init__(self):
        for name in ("poll_interval_s", "drain_timeout_s",
                     "swap_timeout_s", "rejoin_timeout_s",
                     "canary_hold_s"):
            v = getattr(self, name)
            if v <= 0:
                raise ValueError(f"rollout.{name} must be > 0, got {v}")
        if self.canary_min_requests < 0:
            raise ValueError(
                "rollout.canary_min_requests must be >= 0, got "
                f"{self.canary_min_requests}"
            )
        if not (0.0 <= self.max_shadow_diff_fraction <= 1.0):
            raise ValueError(
                "rollout.max_shadow_diff_fraction must be in [0, 1], "
                f"got {self.max_shadow_diff_fraction}"
            )


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The sequence model's sizes (`models/lm.py`) and the share of them
    this chip holds.

    A decoder of pre-norm layers. A layer's mixer by `layer_types`: rotary
    grouped-query attention, windowed or full, a gated delta-rule
    recurrence over a `[linear_key_head_dim, linear_value_head_dim]` state a
    value head (`linear_attention`: `ops/delta_rule.py`), or multi-head latent
    attention (`latent_attention`: q through a rank-`q_lora_rank` latent,
    keys and values through a rank-`kv_lora_rank` one, `num_heads` heads of
    `qk_nope_head_dim + qk_rope_head_dim` for q and k and `v_head_dim` for
    v, one rotary part shared by every head's key). Then a dense SwiGLU
    in the leading `num_dense_layers` and a routed expert layer after them
    (a router over `num_experts`, the top `experts_per_token`, one shared
    expert). `router_score` "sigmoid": the top by score + balance bias, the
    chosen scores normalised and scaled by `route_scale`; "softmax": the top
    by probability, the chosen normalised, no bias and no scale. The other
    switches say what a layer computes, each read at one site of
    `models/lm.py`. With `mtp_layers` 1 a multi-token-prediction module
    (a layer of the last layer's kind over the trunk's output and the next
    token's embedding) predicts the token after next, its loss weighted by
    `mtp_weight`. Empty `layer_types` means the config is no sequence
    model's.

    The share. A layer is divided over `num_experts / experts_held` chips by
    expert parallelism: this chip holds experts `first_expert ..
    first_expert + experts_held - 1` and `vocab_rows` rows of the embedding
    and of the output head. The router still scores all `num_experts`; the
    weighted sum runs over the chosen experts that are held, and that partial
    result goes on. Token ids are drawn from the rows held. With
    `experts_held == num_experts` the model is whole.
    """

    vocab_rows: int = 25_024  # rows of the tables held here
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_size: int = 128
    sliding_window: int = 2048
    layer_types: Tuple[str, ...] = ()  # "sliding_attention" | "full_attention" | "linear_attention" | "latent_attention"
    num_dense_layers: int = 1
    dense_width: int = 6144
    expert_width: int = 1024
    num_experts: int = 128  # the router's width
    experts_per_token: int = 8
    route_scale: float = 2.826
    load_balance_coeff: float = 0.001
    rope_theta: float = 10_000.0
    rms_norm_eps: float = 1e-5
    experts_held: int = 16
    first_expert: int = 0
    # > 0: the experts' buffer holds this many times the pairs that even routing
    # sends here, and the grouped products visit every row of it, the rows past the
    # routed pairs at weight 0: the same work a step whatever the routing
    experts_fixed_rows: float = 0.0
    # a `linear_attention` layer: key heads (q and k), value heads (v and the
    # output gate; a multiple of the key heads, value head j reads key head
    # j // (value / key)), their sizes, the taps of the causal convolution
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel: int = 4
    # a `latent_attention` layer: the ranks of the query's and of the keys' and values' latents,
    # a head's q/k part without and with the rotary embedding, a head's v
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rotary_fraction: float = 1.0  # the leading share of a head that the rotary embedding turns
    rope_interleave: bool = False  # the rotary embedding turns pairs (2i, 2i + 1), not (i, i + r/2)
    mtp_layers: int = 0  # multi-token-prediction modules (0 or 1): each predicts one token further
    mtp_weight: float = 0.1  # the weight of their loss beside the next token's
    qk_norm: bool = False  # an RMSNorm a head on q and on k, before the rotary embedding
    attention_gate: bool = False  # sigmoid gate on the attention's output; wq is twice as wide
    norm_zero_centred: bool = False  # a norm's weight is 1 + w, w made at zero
    router_score: str = "sigmoid"  # | "softmax"
    shared_expert_gate: bool = False  # the shared expert times sigmoid(h w_g)
    embed_scale: bool = True  # x0 = E[t] * sqrt(hidden_size)

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        kinds = ("sliding_attention", "full_attention", "linear_attention", "latent_attention")
        if any(t not in kinds for t in self.layer_types):
            raise ValueError(f"lm.layer_types entries must be of {kinds}, got {self.layer_types!r}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"lm.num_heads={self.num_heads} must be a multiple of lm.num_kv_heads={self.num_kv_heads}"
            )
        if not 0 <= self.first_expert <= self.first_expert + self.experts_held <= self.num_experts:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.experts_held - 1} "
                f"are not among the router's {self.num_experts}"
            )
        if not 1 <= self.experts_per_token <= self.num_experts:
            raise ValueError(f"lm.experts_per_token={self.experts_per_token} of {self.num_experts} experts")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"lm.linear_num_value_heads={self.linear_num_value_heads} must be a multiple of "
                f"lm.linear_num_key_heads={self.linear_num_key_heads}"
            )
        if self.router_score not in ("sigmoid", "softmax"):
            raise ValueError(f"lm.router_score must be 'sigmoid' or 'softmax', got {self.router_score!r}")
        if self.mtp_layers not in (0, 1):
            raise ValueError(f"lm.mtp_layers={self.mtp_layers}: one module or none")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"lm.qk_rope_head_dim={self.qk_rope_head_dim}: the rotary embedding turns pairs")
        if not 0.0 < self.rotary_fraction <= 1.0 or int(self.head_size * self.rotary_fraction) % 2:
            raise ValueError(
                f"lm.rotary_fraction={self.rotary_fraction} of a head of {self.head_size}: want an even part of it"
            )


@dataclasses.dataclass(frozen=True)
class FasterRCNNConfig:
    anchors: AnchorConfig = dataclasses.field(default_factory=AnchorConfig)
    proposals: ProposalConfig = dataclasses.field(default_factory=ProposalConfig)
    rpn_targets: RPNTargetConfig = dataclasses.field(default_factory=RPNTargetConfig)
    roi_targets: ROITargetConfig = dataclasses.field(default_factory=ROITargetConfig)
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    eval: EvalConfig = dataclasses.field(default_factory=EvalConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    compile: CompileConfig = dataclasses.field(default_factory=CompileConfig)
    debug: DebugConfig = dataclasses.field(default_factory=DebugConfig)
    analysis: AnalysisConfig = dataclasses.field(default_factory=AnalysisConfig)
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)
    fleet: FleetConfig = dataclasses.field(default_factory=FleetConfig)
    elastic: ElasticConfig = dataclasses.field(default_factory=ElasticConfig)
    ops: OpsConfig = dataclasses.field(default_factory=OpsConfig)
    quant: QuantConfig = dataclasses.field(default_factory=QuantConfig)
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig
    )
    rollout: RolloutConfig = dataclasses.field(default_factory=RolloutConfig)
    lm: LMConfig = dataclasses.field(default_factory=LMConfig)

    def __post_init__(self):
        if self.is_sequence_model:
            if not self.lm.layer_types:
                raise ValueError("data.dataset='tokens' needs lm.layer_types: the sequence model's layers")
            if self.train.backend != "auto" or self.data.cache_device or self.mesh.param_sharding:
                raise ValueError(
                    "a sequence model trains under train.backend='auto' from the loader, its parameters "
                    "whole on every chip: the spmd backend, the device cache and mesh.param_sharding "
                    "are the detectors'"
                )

    @property
    def is_sequence_model(self) -> bool:
        """Which kind of model the config trains: the decoder of
        `models/lm.py` over rows of tokens, else a detector over images."""
        return self.data.dataset == "tokens"

    def require_detector(self, what: str) -> None:
        """Raise where `what` (eval, predict, serve, ...) is asked of a
        sequence model: only training is written for that kind."""
        if self.is_sequence_model:
            raise ValueError(
                f"{what} is for detectors; this config is a sequence model (data.dataset='tokens'), "
                "which `cli train` trains and nothing else runs yet"
            )

    def feature_size(self, image_size: Optional[Tuple[int, int]] = None) -> Tuple[int, int]:
        """Spatial size of the stride-16 feature map for a given image size.

        The ResNet trunk applies four stride-2 stages, each of which maps
        ``n -> ceil(n / 2)`` under the reference's torch padding
        (conv 7x7/s2/p3, maxpool 3x3/s2/p1, two 3x3/s2/p1 convs) — e.g.
        600 -> 300 -> 150 -> 75 -> 38.
        """
        h, w = image_size if image_size is not None else self.data.image_size
        for _ in range(4):
            h = math.ceil(h / 2)
            w = math.ceil(w / 2)
        return h, w

    def num_anchors(self, image_size: Optional[Tuple[int, int]] = None) -> int:
        fh, fw = self.feature_size(image_size)
        return fh * fw * self.anchors.num_base_anchors

    def replace(self, **kwargs) -> "FasterRCNNConfig":
        return dataclasses.replace(self, **kwargs)


def _cfg(**kw) -> FasterRCNNConfig:
    return FasterRCNNConfig(**kw)


def _voc_data(**kw) -> DataConfig:
    """Shared VOC-preset data pipeline. The 50% horizontal flip is ON by
    default since round 4: measured on the shared 48/256 overfit fixture
    it buys val mAP 0.527 vs 0.407 at train 0.910 vs 0.959
    (benchmarks/map_overfit_result_aug.json) — the original Faster R-CNN
    recipe's augmentation, which the reference omits. Opt out with
    `cli ... --no-augment-hflip`, or in code
    `cfg.replace(data=dataclasses.replace(cfg.data, augment_hflip=False))`.
    """
    kw.setdefault("augment_hflip", True)
    return DataConfig(**kw)


# The five BASELINE.json configs.
CONFIGS = {
    # 1. ResNet18 + RPN + ROIPool on VOC07 (the reference's train.py defaults,
    #    pointed at the VOC2007 devkit per the BASELINE.json metric; the
    #    reference itself hard-codes VOC2012, `frcnn.py:19`)
    "voc_resnet18": _cfg(
        model=ModelConfig(backbone="resnet18", roi_op="pool"),
        data=_voc_data(root_dir="data/voc/VOCdevkit/VOC2007"),
    ),
    # 2. ResNet50 backbone on VOC07
    "voc_resnet50": _cfg(
        model=ModelConfig(backbone="resnet50", roi_op="pool"),
        data=_voc_data(root_dir="data/voc/VOCdevkit/VOC2007"),
    ),
    # 3. FPN neck over ResNet50 + multi-scale anchors
    "voc_resnet50_fpn": _cfg(
        model=ModelConfig(backbone="resnet50", roi_op="align", fpn=True),
        anchors=AnchorConfig(scales=(8.0,)),  # one scale per FPN level
        data=_voc_data(),
    ),
    # 4. ROIAlign head on VOC12
    "voc12_resnet18_align": _cfg(
        model=ModelConfig(backbone="resnet18", roi_op="align"),
        data=_voc_data(root_dir="data/voc/VOCdevkit/VOC2012"),
    ),
    # 5. COCO-2017 80-class, batch 32, data-parallel v5e-8. COCO presets
    #    also flip by default: measured on the COCO-format overfit fixture
    #    val AP50 0.476 vs 0.426, val coco-mAP 0.194 vs 0.177
    #    (benchmarks/coco_overfit_result_aug.json, round 4)
    "coco_resnet50": _cfg(
        model=ModelConfig(backbone="resnet50", num_classes=COCO_NUM_CLASSES, roi_op="align"),
        data=DataConfig(
            dataset="coco", root_dir="data/coco", max_boxes=100,
            augment_hflip=True,
        ),
        train=TrainConfig(batch_size=32),
        eval=EvalConfig(metric="coco"),
    ),
    # 6. The py-faster-rcnn VGG16 COCO net the reference documents via its
    #    checked-in Caffe prototxt (`reference/train_frcnn.prototxt`: VGG16
    #    features, 512-wide RPN conv, 12 anchors = 3 ratios x 4 scales
    #    [num_output 48 = 4*12 at :410-417], RoIPool 7x7, 81 classes).
    "coco_vgg16": _cfg(
        model=ModelConfig(
            backbone="vgg16",
            num_classes=COCO_NUM_CLASSES,
            roi_op="pool",
            rpn_mid_channels=512,
        ),
        anchors=AnchorConfig(scales=(4.0, 8.0, 16.0, 32.0)),
        data=DataConfig(
            dataset="coco", root_dir="data/coco", max_boxes=100,
            augment_hflip=True,
        ),
        eval=EvalConfig(metric="coco"),
    ),
    # 7. One chip's share of Trinity-Mini (arcee-ai, `model_type` afmoe; 26 B
    #    parameters, 3 B active) trained over eight chips by expert
    #    parallelism: 16 of 128 experts and 25,024 of 200,192 vocabulary rows
    #    held here, the router whole; one dense layer and one period of
    #    expert layers (three windowed, one full), the rest on further
    #    pipeline stages. Widths as published (perf/configs/trinity_mini_ep8.json
    #    has the source and every cut). 663.5 M parameters, 10.6 GB trained.
    "trinity_mini_ep8": _cfg(
        data=DataConfig(dataset="tokens", seq_len=8192, root_dir=""),
        # a fine-tuning rate and no L2 term: the catalog row gives no training
        # recipe, and the detectors' 1e-4 / 5e-6 are the reference detector's.
        # At 1e-4 Adam moves every router weight by half a percent a step, all
        # one way: within some forty steps on cycled batches the routers send
        # most tokens to a few experts (PERF.md section 6, PR 31)
        train=TrainConfig(batch_size=2, lr=1e-5, weight_decay=0.0),
        lm=LMConfig(layer_types=("sliding_attention",) * 4 + ("full_attention",)),
    ),
    # 8. The same layer pattern at sizes a CPU test runs: hidden 64, 4 / 2
    #    heads of 16, 16 experts of which 4 are held, top-2, window 8, rows
    #    of 64 tokens over 64 of 256 vocabulary rows.
    "trinity_tiny": _cfg(
        data=DataConfig(dataset="tokens", seq_len=64, root_dir=""),
        train=TrainConfig(batch_size=2, lr=1e-5, weight_decay=0.0),
        lm=LMConfig(
            vocab_rows=64, hidden_size=64, num_heads=4, num_kv_heads=2, head_size=16,
            sliding_window=8, layer_types=("sliding_attention",) * 4 + ("full_attention",),
            dense_width=192, expert_width=32, num_experts=16, experts_per_token=2,
            experts_held=4,
        ),
    ),
    # 9. One chip's share of Qwen3-Next-80B-A3B (`model_type` qwen3_next; 80 B
    #    parameters, 3 B active) trained over sixteen chips by expert
    #    parallelism: 32 of 512 experts and 18,992 of 151,936 vocabulary rows
    #    held here, the router whole; one period of the layer pattern (three
    #    gated delta-rule layers, one gated full-attention layer), the rest on
    #    further pipeline stages. Widths as published
    #    (perf/configs/qwen3_next_ep16.json has the source and every cut).
    #    625.7 M parameters, 10.0 GB trained; one row of 16,384 tokens a step.
    "qwen3_next_ep16": _cfg(
        data=DataConfig(dataset="tokens", seq_len=16384, root_dir=""),
        train=TrainConfig(batch_size=1, lr=1e-5, weight_decay=0.0),
        lm=LMConfig(
            vocab_rows=18_992, num_heads=16, num_kv_heads=2, head_size=256,
            layer_types=("linear_attention",) * 3 + ("full_attention",), num_dense_layers=0,
            expert_width=512, num_experts=512, experts_per_token=10, experts_held=32,
            rope_theta=1e7, rms_norm_eps=1e-6, rotary_fraction=0.25, qk_norm=True,
            attention_gate=True, norm_zero_centred=True, router_score="softmax",
            shared_expert_gate=True, embed_scale=False,
        ),
    ),
    # 10. The same layer pattern at sizes a CPU test runs: hidden 64, 2 key /
    #    4 value heads of 16 in the delta-rule layers, attention 4 / 2 heads of
    #    32, 8 experts of which 4 are held, top-2, rows of 256 tokens.
    "qwen3_next_tiny": _cfg(
        data=DataConfig(dataset="tokens", seq_len=256, root_dir=""),
        train=TrainConfig(batch_size=2, lr=1e-5, weight_decay=0.0),
        lm=LMConfig(
            vocab_rows=64, hidden_size=64, num_heads=4, num_kv_heads=2, head_size=32,
            layer_types=("linear_attention",) * 3 + ("full_attention",), num_dense_layers=0,
            expert_width=32, num_experts=8, experts_per_token=2, experts_held=4,
            linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, rope_theta=1e7, rms_norm_eps=1e-6, rotary_fraction=0.25,
            qk_norm=True, attention_gate=True, norm_zero_centred=True, router_score="softmax",
            shared_expert_gate=True, embed_scale=False,
        ),
    ),
    # 11. One chip's share of JoyAI-LLM-Flash (`model_type` joyai_llm_flash;
    #    48 B parameters, 2.7 B active) trained over sixteen chips by expert
    #    parallelism: 16 of 256 experts and 16,160 of 129,280 vocabulary rows
    #    held here, the router whole; the leading dense layer and four expert
    #    layers, every mixer multi-head latent attention, and the
    #    multi-token-prediction module, which sits with the head; the other 35
    #    layers lie on further pipeline stages. Widths as published
    #    (perf/configs/joyai_flash_ep16.json has the source and every cut).
    #    680.4 M parameters, 10.9 GB trained; one row of 8,192 tokens a step.
    #    At random weights the router sends the held experts a share that
    #    differs from seed to seed, up to three times the even one in a layer:
    #    the experts' buffer is fixed at 15,872 rows (3.875 x the even 4,096;
    #    five buffers at most, so the overflow stays the device loop) and
    #    visited whole, the same work a step for every seed (PERF.md section 6).
    "joyai_flash_ep16": _cfg(
        data=DataConfig(dataset="tokens", seq_len=8192, root_dir=""),
        train=TrainConfig(batch_size=1, lr=1e-5, weight_decay=0.0),
        lm=LMConfig(
            vocab_rows=16_160, num_heads=32, num_kv_heads=32, layer_types=("latent_attention",) * 5,
            dense_width=7168, expert_width=768, num_experts=256, experts_per_token=8, experts_held=16,
            route_scale=2.5, rope_theta=3.2e7, rms_norm_eps=1e-6, rope_interleave=True, mtp_layers=1,
            embed_scale=False, experts_fixed_rows=3.875,
        ),
    ),
    # 12. The same layer pattern at sizes a CPU test runs: hidden 64, 4 heads,
    #    latent ranks 24 (q) and 16 (keys and values), heads of 16 + 8 for q
    #    and k and 16 for v, 16 experts of which 4 are held, top-2, rows of
    #    128 tokens, the multi-token-prediction module.
    "joyai_tiny": _cfg(
        data=DataConfig(dataset="tokens", seq_len=128, root_dir=""),
        train=TrainConfig(batch_size=2, lr=1e-5, weight_decay=0.0),
        lm=LMConfig(
            vocab_rows=64, hidden_size=64, num_heads=4, num_kv_heads=4, layer_types=("latent_attention",) * 3,
            dense_width=96, expert_width=32, num_experts=16, experts_per_token=2, experts_held=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            route_scale=2.5, rope_theta=3.2e7, rms_norm_eps=1e-6, rope_interleave=True, mtp_layers=1,
            embed_scale=False, experts_fixed_rows=2.0,
        ),
    ),
}


def get_config(name: str = "voc_resnet18", **overrides) -> FasterRCNNConfig:
    """Look up a preset config by name, optionally replacing top-level fields."""
    if name not in CONFIGS:
        raise KeyError(f"unknown config {name!r}; choices: {sorted(CONFIGS)}")
    cfg = CONFIGS[name]
    return cfg.replace(**overrides) if overrides else cfg


def config_from_dict(d: dict) -> FasterRCNNConfig:
    """Rebuild a :class:`FasterRCNNConfig` from ``dataclasses.asdict``
    output, e.g. after a JSON round-trip (lists re-become tuples)."""
    import typing

    def deep_tuple(v):
        return tuple(deep_tuple(x) for x in v) if isinstance(v, list) else v

    def build(cls, dd):
        hints = typing.get_type_hints(cls)
        kw = {}
        for f in dataclasses.fields(cls):
            if f.name not in dd:
                continue  # dict from an older binary (e.g. pre-`compile`
                # section): absent fields keep their defaults
            v = dd[f.name]
            t = hints.get(f.name)
            if dataclasses.is_dataclass(t) and isinstance(v, dict):
                v = build(t, v)
            else:
                v = deep_tuple(v)
            kw[f.name] = v
        return cls(**kw)

    return build(FasterRCNNConfig, d)
