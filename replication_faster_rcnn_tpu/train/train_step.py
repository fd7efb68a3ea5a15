"""The jitted train step — the whole reference training iteration
(`train.py:59-127`) as ONE XLA program.

Where the reference crosses the device boundary four times per step (host
anchor generation `nets/rpn.py:127`, per-image NMS loop `nets/rpn.py:131-136`,
host numpy RPN targets `train.py:71-79`, roi.cpu() head targets
`train.py:91-104`), here the entire pipeline — trunk -> RPN -> proposals ->
both target creators -> head -> 4 losses -> grad -> update — is traced once
and compiled. Sharding the batch over the mesh's data axis turns the loss's
global reductions and the gradient sums into XLA allreduces automatically.

Loss structure (reference `train.py:81-123`): rpn_reg (smooth-L1 on anchor
positives), rpn_cls (binary CE, ignore -1), head_reg (smooth-L1 on sampled
positives, class-specific deltas via `train.py:112-117` gather semantics),
head_cls (21-way CE, ignore -1); total is their weighted sum (reference:
unweighted, `train.py:123`).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct

from replication_faster_rcnn_tpu.config import FasterRCNNConfig
from replication_faster_rcnn_tpu.models.faster_rcnn import FasterRCNN
from replication_faster_rcnn_tpu.models.head import select_class_deltas
from replication_faster_rcnn_tpu.targets import (
    batched_anchor_targets,
    batched_proposal_targets,
)
from replication_faster_rcnn_tpu.telemetry import stages
from replication_faster_rcnn_tpu.train import fault, losses

Array = jnp.ndarray


class TrainState(struct.PyTreeNode):
    """Carried training state (params + BN stats + optimizer + step + rng)."""

    step: Array
    params: Any
    batch_stats: Any
    opt_state: Any
    rng: Array


class ModelKind(NamedTuple):
    """What a kind of model gives the train step; everything around it
    (`value_and_grad`, `quantize_grads`, `guarded_update`, `TrainState`,
    donation, the K-step scan) is one writing for every kind.

    ``init(model, config, rng) -> (params, batch_stats)``: the gradient
    leaves, and the state no gradient reaches (BatchNorm statistics, a
    router's balance bias). ``losses(model, config, params, batch_stats,
    batch, rng, train, train_resolution=) -> (total, (metrics,
    new_batch_stats))``. ``batch_keys``: what a batch holds, each array's
    leading axis the batch's."""

    build: Callable[[FasterRCNNConfig], Any]
    init: Callable[..., Tuple[Any, Any]]
    losses: Callable[..., Tuple[Array, Tuple[Dict[str, Array], Any]]]
    batch_keys: Tuple[str, ...]


def _detector_init(model: FasterRCNN, config: FasterRCNNConfig, rng: Array):
    h, w = config.data.image_size
    variables = model.init(
        {"params": rng}, jnp.zeros((1, h, w, 3), jnp.float32), train=False
    )
    return variables["params"], variables.get("batch_stats", {})


def model_kind(config: FasterRCNNConfig) -> ModelKind:
    """The detector's four-loss step, or the sequence model's
    (`models/lm.py`, imported only where a config asks for it)."""
    if config.is_sequence_model:
        from replication_faster_rcnn_tpu.models import lm

        # the model is plain functions of the parameter tree: nothing to build
        return ModelKind(
            build=lambda config: None,
            init=lambda model, config, rng: lm.init(config, rng),
            losses=lm.losses,
            batch_keys=lm.BATCH_KEYS,
        )
    return ModelKind(FasterRCNN, _detector_init, compute_losses, ("image", "boxes", "labels", "mask"))


def create_train_state(
    config: FasterRCNNConfig,
    rng: Array,
    tx: optax.GradientTransformation,
    keep: Optional[Callable[[Any, str], Any]] = None,
) -> Tuple[Any, TrainState]:
    """(model, first state). ``keep(jitted, label)`` wraps the init program
    (the trainer's executable store, `train/warmup.py::keep_compiled`)."""
    kind = model_kind(config)
    model = kind.build(config)
    init_rng, state_rng = jax.random.split(rng)

    @jax.jit
    def init(init_rng):
        # one program, not an eager pass over the model (some 220 small
        # programs and the forward itself, which only the parameters' shapes
        # need): 3 s of every start from the compile cache, 70 s without
        params, batch_stats = kind.init(model, config, init_rng)
        return params, batch_stats, tx.init(params)

    if keep is not None:
        init = keep(init, "init")
    params, batch_stats, opt_state = init(init_rng)
    return model, TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
        rng=state_rng,
    )


def _device_input(config: FasterRCNNConfig, batch: Dict[str, Array], train_resolution):
    """The batch as the trunk sees it: device-side jitter, augmentation and
    bucket resample (each only where the batch or the program asks for
    it). Returns (images, gt_boxes, gt_labels, gt_mask)."""
    images = batch["image"]
    if "jitter" in batch:
        # device-side scale-jitter resample (data.augment_scale_device):
        # the host shipped raw images + integer jitter geometry; the
        # boxes in this batch are already transformed host-side
        from replication_faster_rcnn_tpu.ops.image import batched_scale_jitter

        images = batched_scale_jitter(images, batch["jitter"])
    gt_boxes = batch["boxes"]
    gt_labels = batch["labels"]
    gt_mask = batch["mask"]
    if "aug" in batch:
        # FULLY on-device augmentation (data.augment_device): the host
        # shipped raw samples + int32 (idx, epoch) rows; flip, translate
        # and scale-jitter decisions are splitmix draws of
        # (seed, epoch, idx) computed here, identical on every shard and
        # every resume with zero communication. Runs at the base canvas,
        # ahead of the bucket resample below.
        from replication_faster_rcnn_tpu.ops.image import augment_batch

        images, gt_boxes, gt_labels, gt_mask = augment_batch(
            images,
            gt_boxes,
            gt_labels,
            gt_mask,
            batch["aug"],
            seed=config.train.seed,
            hflip=config.data.augment_hflip,
            scale_range=config.data.augment_scale,
            translate=config.data.augment_translate,
        )
    if train_resolution is not None:
        # multi-scale bucket resample (static shape, per-bucket program)
        from replication_faster_rcnn_tpu.ops.image import (
            resize_batch_with_boxes,
        )

        images, gt_boxes = resize_batch_with_boxes(
            images, gt_boxes, train_resolution
        )
    return images, gt_boxes, gt_labels, gt_mask


def compute_losses(
    model: FasterRCNN,
    config: FasterRCNNConfig,
    params: Any,
    batch_stats: Any,
    batch: Dict[str, Array],
    rng: Array,
    train: bool = True,
    axis_name: str = None,
    positions: Array = None,
    train_resolution=None,
) -> Tuple[Array, Tuple[Dict[str, Array], Any]]:
    """Forward + 4 losses. Returns (total, (metrics, new_batch_stats)).

    ``axis_name``/``positions`` support the explicit shard_map backend
    (`parallel/spmd.py`): loss normalizers psum over the axis, per-image
    sampling keys fold in the global batch position so the objective and
    randomness match the jit auto-partitioned path exactly.

    ``train_resolution`` (STATIC ``(h, w)`` or None) is one multi-scale
    training bucket (data.train_resolutions): the batch arrives at the
    base canvas shape and is resampled to the bucket's shape on device
    (`ops/image.py::resize_batch_with_boxes`, boxes tracked) right after
    the jitter resample — so each bucket is its own compiled program,
    exactly like a serving bucket. None (the default) leaves the program
    byte-identical to the pre-bucket trace.

    Each stage runs under its ``jax.named_scope`` of `telemetry/stages.py`
    (metadata only), which is how a profiler trace is cut by stage.
    """
    with jax.named_scope(stages.INPUT):
        images, gt_boxes, gt_labels, gt_mask = _device_input(
            config, batch, train_resolution
        )
    img_h, img_w = float(images.shape[1]), float(images.shape[2])
    variables = {"params": params, "batch_stats": batch_stats}
    sigma = config.train.smooth_l1_sigma
    if positions is None:
        positions = jnp.arange(images.shape[0], dtype=jnp.int32)

    rng_at, rng_pt, rng_do = jax.random.split(rng, 3)
    if axis_name is not None:
        # decorrelate dropout across shards (rng is replicated; without this
        # every shard would draw the same mask). Sampling rngs stay
        # shard-invariant — their per-image keys fold in global positions.
        rng_do = jax.random.fold_in(rng_do, jax.lax.axis_index(axis_name))

    # trunk + RPN (train mode: BN batch stats update)
    with jax.named_scope(stages.TRUNK):
        feat, mut = model.apply(
            variables, images, train, method="extract_features",
            mutable=["batch_stats"],
        )
    with jax.named_scope(stages.RPN):
        logits, deltas, anchors = model.apply(
            variables, feat, method="rpn_forward"
        )

    # first-stage targets, on device
    with jax.named_scope(stages.ANCHOR_TARGETS):
        reg_t, lab_t = batched_anchor_targets(
            rng_at, gt_boxes, gt_mask, anchors, config.rpn_targets, positions
        )
    with jax.named_scope(stages.RPN):
        rpn_reg_loss = losses.loc_loss(deltas, reg_t, lab_t, sigma, axis_name)
        rpn_cls_loss = losses.ignore_cross_entropy(logits, lab_t, axis_name)

    # proposals (stop-grad, reference detach semantics) + second-stage targets
    with jax.named_scope(stages.PROPOSALS):
        rois, roi_valid = model.apply(
            variables, logits, deltas, anchors, img_h, img_w, train,
            method="propose",
        )
    with jax.named_scope(stages.ROI_TARGETS):
        sample_rois, reg_t2, lab_t2 = batched_proposal_targets(
            rng_pt, rois, roi_valid, gt_boxes, gt_labels, gt_mask,
            config.roi_targets, positions,
            strategy=config.train.sampling_strategy,
        )

    # head on the sampled rois (BN in the tail also updates; the VGG16
    # tail's dropout draws from the 'dropout' rng in train mode)
    with jax.named_scope(stages.BOX_HEAD):
        (cls_out, reg_out), mut2 = model.apply(
            # norm="group" models carry no batch_stats collection — flax
            # then omits the key from the mutated-state dict
            {"params": params, "batch_stats": mut.get("batch_stats", {})},
            feat,
            sample_rois,
            img_h,
            img_w,
            train,
            method="head_forward",
            mutable=["batch_stats"],
            rngs={"dropout": rng_do} if train else None,
        )
        reg_sel = select_class_deltas(reg_out, lab_t2)
        head_reg_loss = losses.loc_loss(
            reg_sel, reg_t2, lab_t2, sigma, axis_name
        )
        head_cls_loss = losses.ignore_cross_entropy(cls_out, lab_t2, axis_name)

    w1, w2, w3, w4 = config.train.loss_weights
    total = (
        w1 * rpn_cls_loss + w2 * rpn_reg_loss + w3 * head_cls_loss + w4 * head_reg_loss
    )
    metrics = {
        "loss": total,
        "rpn_cls_loss": rpn_cls_loss,
        "rpn_reg_loss": rpn_reg_loss,
        "head_cls_loss": head_cls_loss,
        "head_reg_loss": head_reg_loss,
        "n_pos_rpn": (lab_t == 1).sum().astype(jnp.float32),
        "n_pos_head": (lab_t2 > 0).sum().astype(jnp.float32),
    }
    return total, (metrics, mut2.get("batch_stats", {}))


def quantize_grads(grads: Any, dtype_str: str) -> Any:
    """Round-trip the gradient tree through ``dtype_str`` (no-op for
    "float32").

    This is the numerics of `train.grad_allreduce_dtype`: the explicit
    shard_map backend casts before its `lax.psum` so the collective
    itself moves half the bytes (`parallel/spmd.py`); under jit
    auto-partitioning the all-reduces are fused inside the backward where
    their dtype cannot be chosen from here, so the same quantization is
    applied to the summed grads — both backends then apply the optimizer
    to identically-rounded gradients.
    """
    if dtype_str == "float32":
        return grads
    dt = jnp.dtype(dtype_str)
    with jax.named_scope(stages.UPDATE):
        return jax.tree_util.tree_map(
            lambda g: g.astype(dt).astype(g.dtype)
            if jnp.issubdtype(g.dtype, jnp.floating)
            else g,
            grads,
        )


def make_train_step(
    model: Any,
    config: FasterRCNNConfig,
    tx: optax.GradientTransformation,
    train_resolution=None,
):
    """Build the jittable (state, batch) -> (state, metrics) function.

    Jit it with donate_argnums=(0,) and sharded batch inputs; parameters
    stay replicated and gradients allreduce via XLA.

    ``train_resolution`` bakes one multi-scale bucket's static (h, w)
    into the trace (see ``compute_losses``); None is the single-scale
    program, byte-identical to the pre-bucket build.
    """

    losses_of = model_kind(config).losses

    def train_step(state: TrainState, batch: Dict[str, Array]):
        step_rng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(params):
            return losses_of(
                model, config, params, state.batch_stats, batch, step_rng,
                True, train_resolution=train_resolution,
            )

        (_, (metrics, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        grads = quantize_grads(grads, config.train.grad_allreduce_dtype)
        # guarded update: under nonfinite_policy skip|halt a gradient tree
        # with any NaN/Inf withholds the whole update (params, opt state,
        # BN stats carried through bit-identical) and flags skipped=1 in
        # the health scalars, which ride the metrics transfer as before
        new_state, health = fault.guarded_update(
            tx, state, grads, new_stats, config.train.nonfinite_policy
        )
        metrics.update(health)
        return new_state, metrics

    return train_step


def make_cached_train_step(
    model: FasterRCNN,
    config: FasterRCNNConfig,
    tx: optax.GradientTransformation,
    train_resolution=None,
):
    """The device-cache variant: (state, cache, sel) -> (state, metrics).

    ``cache`` is a :class:`data.device_cache.DeviceCache`'s array dict
    (device-resident, replicated); ``sel`` the per-step batch selection
    (indices + augmentation decisions, ~bytes). Batch materialization
    (`data/device_cache.py::materialize_batch`) runs inside the same
    compiled program as the step, so the host->device traffic per step is
    the selection alone — the answer to the measured feed-bound trainer
    (11 vs 215 img/s, `benchmarks/loader_throughput.json`).

    Jit with donate_argnums=(0,) ONLY — the cache must NOT be donated.
    """
    base = make_train_step(model, config, tx, train_resolution=train_resolution)

    def cached_step(state, cache: Dict[str, Array], sel: Dict[str, Array]):
        from replication_faster_rcnn_tpu.data.device_cache import (
            materialize_batch,
        )

        return base(state, materialize_batch(cache, sel))

    return cached_step


def fused_scan_unroll(k: int) -> int:
    """Unroll factor for the fused multi-step `lax.scan`.

    XLA:CPU compiles a while-loop body without the top-level conv/fusion
    treatment — measured 4.5x slower per step than the same step outside
    the loop — so on CPU the scan is fully unrolled into straight-line
    code (compile time grows ~linearly with k). On TPU the loop body
    compiles at full quality and the compact scan keeps the executable
    small and the compile short, so it stays a real loop.
    """
    return k if jax.default_backend() == "cpu" else 1


def build_multi_step(step_fn, k: int):
    """Fuse ``k`` steps of a (state, batch) -> (state, metrics) step into
    ONE jittable call via `lax.scan` over batches stacked on a new leading
    [K] axis.

    One dispatch then trains k steps: the carry (TrainState) stays on
    device between the fused iterations (donate it when jitting) and the
    per-step metrics come back stacked [K, ...], read by the host only at
    log boundaries. The scan body IS the single step — same fold_in(rng,
    step) keying, same optimizer — so a fused run is step-for-step
    identical to k sequential dispatches (pinned by
    tests/test_multi_step.py).
    """
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")

    def multi_step(state: TrainState, batches: Dict[str, Array]):
        def body(s, b):
            return step_fn(s, b)

        return jax.lax.scan(
            body, state, batches, length=k, unroll=fused_scan_unroll(k)
        )

    return multi_step


def make_cached_multi_step(
    model: FasterRCNN,
    config: FasterRCNNConfig,
    tx: optax.GradientTransformation,
    k: int,
    train_resolution=None,
):
    """Fused device-cache variant: (state, cache, sels) -> (state, metrics)
    where ``sels`` holds k per-step selections stacked to [K, B, ...]
    (`data.device_cache.stack_selections`). Each scan iteration gathers +
    augments its batch from the cache and trains one step; the host ships
    only the stacked selection bytes per k steps.

    Jit with donate_argnums=(0,) ONLY — the cache must NOT be donated.
    """
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
    base = make_train_step(model, config, tx, train_resolution=train_resolution)

    def fused(state: TrainState, cache: Dict[str, Array], sels: Dict[str, Array]):
        from replication_faster_rcnn_tpu.data.device_cache import (
            materialize_batch,
        )

        def body(s, sel):
            return base(s, materialize_batch(cache, sel))

        return jax.lax.scan(
            body, state, sels, length=k, unroll=fused_scan_unroll(k)
        )

    return fused


def _schedule_knobs(config: FasterRCNNConfig, steps_per_epoch: int):
    """(peak_lr, warmup_steps) shared by the jnp and host schedules.

    The large-batch recipe of arXiv:1711.04325: under
    ``lr_scaling='linear'`` the peak lr scales by
    ``batch_size / base_batch_size`` (scaling out the data axis keeps the
    per-example update magnitude), and ``warmup_epochs`` ramps linearly
    from ~0 to that peak before the cosine decay takes over.
    """
    tc = config.train
    scale = (
        tc.batch_size / tc.base_batch_size if tc.lr_scaling == "linear" else 1.0
    )
    warmup_steps = int(round(tc.warmup_epochs * max(steps_per_epoch, 1)))
    return tc.lr * scale, warmup_steps


def scale_by_sharded_trust_ratio(
    axis_name=None,
    param_dims=None,
) -> optax.GradientTransformation:
    """LAMB's per-layer trust ratio (arXiv:1904.00962), exact under
    ZeRO-1 weight-update sharding.

    ``optax.scale_by_trust_ratio`` rescales each layer's update by
    |param| / |update| — leaf-global norms, which is why the spmd+ZeRO
    backend rejects LARS (``parallel/mesh.py::validate_parallel``):
    inside the shard_map's per-shard update every sharded leaf is a 1/N
    slice and its local norm is wrong.  This variant computes both norms
    from the local slice's sum of squares and completes them with a
    ``lax.psum`` over ``axis_name`` for the leaves ``param_dims`` marks
    sharded (dim >= 0) — ``|x|^2 == sum_shards |x_s|^2`` exactly, so the
    trust ratio matches the unsharded math while each shard only ever
    touches its own slice.  Replicated leaves (dim == -1) are full on
    every shard and use their local norm directly (a psum there would
    overcount by N).  With ``axis_name=None`` (the default) no psum is
    emitted and the transform is numerically identical to
    ``optax.scale_by_trust_ratio()`` with its default knobs.
    """

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("scale_by_sharded_trust_ratio requires params")

        def _norm(x, dim):
            s = jnp.sum(jnp.square(x.astype(jnp.float32)))
            if axis_name is not None and dim >= 0:
                s = jax.lax.psum(s, axis_name)
            return jnp.sqrt(s)

        def _scale(u, p, dim=-1):
            pn = _norm(p, dim)
            un = _norm(u, dim)
            # zero param (fresh bias) or zero update -> ratio 1 (optax's
            # min_norm=0 convention): never stall a layer on a 0/0.
            ratio = jnp.where((pn == 0.0) | (un == 0.0), 1.0, pn / un)
            return (u.astype(jnp.float32) * ratio).astype(u.dtype)

        if param_dims is None:
            scaled = jax.tree_util.tree_map(_scale, updates, params)
        else:
            scaled = jax.tree_util.tree_map(
                _scale, updates, params, param_dims
            )
        return scaled, state

    return optax.GradientTransformation(init_fn, update_fn)


def lamb_param_dims(config: FasterRCNNConfig, n_shards: int):
    """Per-leaf ZeRO-1 slice dims for the model's parameter tree.

    Derived from abstract shapes only (``jax.eval_shape`` — no FLOPs, no
    parameter memory) with the same ``parallel.zero.shard_dim`` rule the
    spmd backend uses to place its hand-written collectives, so the
    trust ratio's psum'd norms line up leaf-for-leaf with the slices
    ``tx.update`` actually receives inside the per-shard ZeRO update.
    """
    # Deferred import: parallel/__init__ -> spmd -> this module.  At call
    # time (trainer/warmup construction) both are fully imported.
    from replication_faster_rcnn_tpu.parallel.zero import shard_dim

    model = FasterRCNN(config)
    h, w = config.data.image_size

    def _init():
        return model.init(
            {"params": jax.random.PRNGKey(0)},
            jnp.zeros((1, h, w, 3), jnp.float32),
            train=False,
        )

    variables = jax.eval_shape(_init)
    return jax.tree_util.tree_map(
        lambda leaf: shard_dim(leaf.shape, n_shards), variables["params"]
    )


def make_optimizer(
    config: FasterRCNNConfig, steps_per_epoch: int, n_shards: int = 0
):
    """Adam + per-epoch cosine annealing (reference `train.py:139-140`:
    Adam(lr, weight_decay=5e-6) + CosineAnnealingLR(T_max=n_epoch)),
    with the optional large-batch recipe on top (`_schedule_knobs`;
    ``train.lars`` adds LAMB-style layer-wise trust-ratio scaling after
    Adam, ``train.optimizer='lamb'`` selects first-class LAMB whose
    trust ratio stays exact under ZeRO-1 sharding — see
    ``scale_by_sharded_trust_ratio``).

    ``n_shards`` is the size of the data axis the spmd backend's
    per-shard ZeRO update runs over (the trainer passes its mesh size).
    It only matters for LAMB with ``backend='spmd'`` +
    ``shard_opt_state``; every other caller can leave the default and
    gets the plain (unsharded) chain, so existing adam/lars program
    fingerprints are bitwise unchanged.

    The cosine is evaluated per step but changes value once per epoch,
    matching the reference's epoch-granular scheduler.step()
    (`train.py:148`); the warmup ramp, when enabled, is per-step.
    """
    tc = config.train
    peak, warmup_steps = _schedule_knobs(config, steps_per_epoch)

    def schedule(step):
        epoch = jnp.minimum(step // max(steps_per_epoch, 1), tc.n_epoch)
        lr = peak * 0.5 * (1.0 + jnp.cos(jnp.pi * epoch / tc.n_epoch))
        if warmup_steps > 0:
            warm = peak * (jnp.asarray(step, jnp.float32) + 1.0) / warmup_steps
            lr = jnp.where(step < warmup_steps, warm, lr)
        return lr

    # torch Adam's weight_decay is L2-added-to-grad, not decoupled AdamW.
    parts = [
        optax.add_decayed_weights(tc.weight_decay),
        optax.scale_by_adam(mu_dtype=jnp.dtype(tc.adam_mu_dtype)),
    ]
    if tc.lars:
        # trust-ratio AFTER the Adam preconditioner (LAMB's placement):
        # per-leaf |param|/|update| rescaling bounds the relative step.
        # Leaf-global norms — the shard_map ZeRO backend rejects the combo
        # (parallel/mesh.py::validate_parallel) since slices would see
        # partial norms; the jit backend's GSPMD inserts the reductions.
        parts.append(optax.scale_by_trust_ratio())
    if tc.optimizer == "lamb":
        # First-class LAMB: Adam preconditioner + trust ratio.  The
        # sharded variant is used ONLY where tx.update really runs on
        # slices — the spmd backend's per-shard ZeRO update (axis bound
        # inside shard_map).  The auto backend traces full logical
        # shapes (GSPMD inserts the reductions itself) and non-ZeRO spmd
        # updates full replicated leaves, so both get the plain variant.
        if tc.backend == "spmd" and tc.shard_opt_state and n_shards > 1:
            parts.append(
                scale_by_sharded_trust_ratio(
                    axis_name=config.mesh.data_axis,
                    param_dims=lamb_param_dims(config, n_shards),
                )
            )
        else:
            parts.append(scale_by_sharded_trust_ratio())
    parts.append(optax.scale_by_learning_rate(schedule))
    tx = optax.chain(*parts)
    return tx, schedule


def host_schedule(config: FasterRCNNConfig, steps_per_epoch: int):
    """Host-math twin of ``make_optimizer``'s schedule.

    The jnp schedule inside the optimizer is correct under jit, but
    evaluating it on the host (the per-step log path) builds a device
    scalar and ``float()`` then forces an implicit device sync — a
    jaxlint JX001 hit and a transfer-guard violation under strict mode.
    Same formula (cosine + linear warmup + large-batch peak scaling) in
    pure Python for host callers; keep the two in sync.
    """
    tc = config.train
    peak, warmup_steps = _schedule_knobs(config, steps_per_epoch)

    def schedule(step: int) -> float:
        epoch = min(int(step) // max(steps_per_epoch, 1), tc.n_epoch)
        lr = peak * 0.5 * (1.0 + math.cos(math.pi * epoch / tc.n_epoch))
        if warmup_steps > 0 and int(step) < warmup_steps:
            lr = peak * (int(step) + 1.0) / warmup_steps
        return float(lr)

    return schedule
