"""Benchmark: jitted train-step throughput on the flagship config.

(Importable package module; the repo-root ``bench.py`` is a thin shim so
the driver can run it from the checkout root.)

:func:`main` needs an accelerator: when ``jax.devices()[0].platform`` is
``cpu`` it exits non-zero and prints no metric line — there is no path
that measures on the CPU instead. With a chip it prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "device": {"platform",
"kind", "count"}, plus "flops_per_step"/"mfu" and — unless
BENCH_BREAKDOWN=0 — a per-stage "breakdown"}. Every phase either
completes or raises; no line is printed after a phase that failed.

Metric: VOC-shaped (600x600, synthetic tensors — dataset-independent)
training images/sec on the available device(s). ``vs_baseline`` is the
ratio against the measured single-host PyTorch-CPU reference throughput
(BASELINE.md: the reference publishes no numbers, so the baseline is
measured by benchmarks/reference_baseline.py and cached in
benchmarks/baseline_measured.json; target is >= 6x).

MFU: ``achieved_flops / (time x peak_bf16_flops)``. The step's FLOP count
comes from XLA's own HloCostAnalysis of a one-device lowering of the
step, in this process (the lowered module on CPU; the compiled executable
on TPU, whose client analyses nothing else). Peak is per-chip bf16 from the ``device_kind``-keyed table
in telemetry/mfu.py; a TPU that is not in the table is an error.

Stage breakdown (SURVEY.md §5 tracing plan): wall-time of jitted prefixes
of the step — trunk, +RPN heads, +proposal NMS, full forward+loss — whose
successive differences attribute time to trunk / rpn_heads / proposal_nms
/ targets_head_loss / backward_update. Differences of separately-jitted
programs (XLA fuses differently per program), so treat small negative
deltas as noise floors, not measurement bugs.
"""

from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from replication_faster_rcnn_tpu.telemetry.mfu import device_record


def require_accelerator(who: str) -> dict:
    """The :func:`device_record`, or exit non-zero when JAX found no
    accelerator: a measurement entry point never carries on on the CPU."""
    device = device_record()
    if device["platform"] == "cpu":
        raise SystemExit(
            f"{who}: no accelerator — jax.devices()[0].platform is 'cpu' "
            f"({device['count']} device(s)); nothing was measured"
        )
    return device


def main(config=None, profile_dir=None) -> None:
    """Measure the jitted train step of ``config`` (default: the flagship
    voc_resnet18 at 600x600, batch 16/device) on all available devices
    and print the one-line JSON record. ``BENCH_MODE=eval`` measures the
    inference path instead. ``profile_dir`` adds a jax.profiler trace of
    a few warm steps after the timed loop."""
    from replication_faster_rcnn_tpu.train.warmup import place_compile_cache

    device = require_accelerator("bench")
    place_compile_cache(config.compile.cache_dir if config is not None else "")
    if os.environ.get("BENCH_MODE", "train") == "eval":
        record = measure_eval(config, profile_dir)
    else:
        record = measure_train(config, profile_dir)
    print(json.dumps({**record, "device": device}))


def _flagship_cfg(n_dev):
    """The bench default config: voc_resnet18 at 600x600 on synthetic
    tensors, data-parallel over every device. One definition shared by the
    train and eval measurements so the flagship shape cannot drift between
    the two metrics."""
    from replication_faster_rcnn_tpu.config import DataConfig, MeshConfig, get_config

    return get_config("voc_resnet18").replace(
        data=DataConfig(dataset="synthetic", image_size=(600, 600), max_boxes=32),
        mesh=MeshConfig(num_data=n_dev),
    )


def _capture_trace(profile_dir, step, state, device_batch, n_steps=3) -> None:
    """Short jax.profiler capture of an already-warm program, after the
    timed loop so tracing overhead never touches the metric."""
    from replication_faster_rcnn_tpu.utils.profiling import trace

    with trace(profile_dir):
        for _ in range(n_steps):
            state, metrics = step(state, device_batch)
        jax.block_until_ready(metrics)


def measure_train(config=None, profile_dir=None) -> dict:
    """Time the jitted train step; returns the record (not printed —
    :func:`main` owns the output and the accelerator requirement)."""
    import dataclasses

    from replication_faster_rcnn_tpu.config import TrainConfig
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.data.loader import collate
    from replication_faster_rcnn_tpu.parallel import (
        make_mesh,
        shard_batch,
        shard_stacked_batch,
        validate_parallel,
    )
    from replication_faster_rcnn_tpu.train import (
        build_multi_step,
        create_train_state,
        make_optimizer,
        make_train_step,
    )

    n_dev = len(jax.devices())
    if config is None:
        # 16/device is the preset's operating point in every earlier
        # record; BENCH_BATCH overrides per device
        batch_size = int(os.environ.get("BENCH_BATCH", "16")) * n_dev
        cfg = _flagship_cfg(n_dev).replace(
            train=TrainConfig(batch_size=batch_size)
        )
    else:
        # honor the caller's model/image/batch/mesh choices (incl. a model
        # axis and spatial partitioning); force synthetic data
        # (dataset-independent measurement) and fill every device
        n_model = max(1, config.mesh.num_model)
        validate_parallel(config, n_dev)  # descriptive num_model/mesh-fit errors
        n_data = n_dev // n_model
        cfg = config.replace(
            data=dataclasses.replace(config.data, dataset="synthetic"),
            mesh=dataclasses.replace(config.mesh, num_data=n_data),
        )
        batch_size = cfg.train.batch_size
        if batch_size % n_data != 0:
            batch_size = max(1, batch_size // n_data) * n_data
            cfg = cfg.replace(
                train=dataclasses.replace(cfg.train, batch_size=batch_size)
            )
    metric = "train_images_per_sec_{}x{}".format(*cfg.data.image_size)
    validate_parallel(cfg, n_dev)
    mesh = make_mesh(cfg.mesh)
    tx, _ = make_optimizer(cfg, steps_per_epoch=100)
    model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)

    from replication_faster_rcnn_tpu.parallel.zero import (
        place_train_state,
        train_state_shardings,
    )

    shardings = train_state_shardings(
        state, mesh, cfg.mesh, cfg.train.shard_opt_state
    )
    state = place_train_state(state, shardings)

    ds = SyntheticDataset(cfg.data, length=batch_size)
    if cfg.data.augment_scale:
        # --augment-scale[-device] must change what the step RUNS, not
        # just the config label: the view attaches the 'jitter' geometry
        # (device mode — the on-chip resample becomes part of the timed
        # step) or pre-jitters on host (host mode; step unchanged but
        # the batch content matches training)
        from replication_faster_rcnn_tpu.data.augment import AugmentedView

        ds = AugmentedView(
            ds, seed=0, epoch=0, hflip=False,
            scale_range=cfg.data.augment_scale,
            scale_on_device=cfg.data.augment_scale_device,
        )
    batch = collate([ds[i] for i in range(batch_size)])
    device_batch = shard_batch(batch, mesh, cfg.mesh)

    # fused multi-step dispatch (train.steps_per_dispatch > 1): the timed
    # program scans K steps per jitted call. The fed/spmd paths stack the
    # same host batch K times on a new leading axis (identical per-step
    # work, 1/K the dispatches); the cache path pre-stages K distinct
    # selections. `device_batch` stays single-step for the stage breakdown.
    k = max(1, cfg.train.steps_per_dispatch)
    timed_batch = device_batch
    if k > 1 and not cfg.data.cache_device:
        chunk = {kk: np.stack([v] * k) for kk, v in batch.items()}
        timed_batch = shard_stacked_batch(chunk, mesh, cfg.mesh)

    if cfg.train.backend == "spmd":
        # measure the explicit shard_map backend (already jitted + donated)
        from replication_faster_rcnn_tpu.parallel import make_shard_map_train_step

        step, _ = make_shard_map_train_step(
            cfg, tx, mesh, steps_per_dispatch=k
        )
    elif cfg.data.cache_device:
        # --cache-device: the timed step is the CACHED one — on-device
        # gather + flip/jitter + train step; per-step host traffic is the
        # index selection only. (Without this branch the flag would
        # silently bench the plain fed step under a cache_device label.)
        from replication_faster_rcnn_tpu.data.device_cache import (
            CachedSampler,
            DeviceCache,
        )
        from replication_faster_rcnn_tpu.train import make_cached_train_step

        base_ds = SyntheticDataset(cfg.data, length=max(2 * batch_size, 64))
        cache = DeviceCache(base_ds, mesh=mesh)
        sampler = CachedSampler(
            len(base_ds), cache.image_hw, batch_size=batch_size, seed=0,
            hflip=cfg.data.augment_hflip, scale_range=cfg.data.augment_scale,
        )
        if k > 1:
            from replication_faster_rcnn_tpu.data.device_cache import (
                stack_selections,
            )
            from replication_faster_rcnn_tpu.train import (
                make_cached_multi_step,
            )

            sels = stack_selections([
                sampler.selection(
                    (np.arange(batch_size) + i * batch_size) % len(base_ds)
                )
                for i in range(k)
            ])
            sel = shard_stacked_batch(sels, mesh, cfg.mesh)
            cached = jax.jit(
                make_cached_multi_step(model, cfg, tx, k),
                donate_argnums=(0,),
                out_shardings=(shardings, None),
            )
        else:
            sel = shard_batch(
                sampler.selection(np.arange(batch_size) % len(base_ds)),
                mesh, cfg.mesh,
            )
            cached = jax.jit(
                make_cached_train_step(model, cfg, tx),
                donate_argnums=(0,),
                out_shardings=(shardings, None),
            )

        def step(state, _batch, _c=cached, _arrays=cache.arrays, _sel=sel):
            return _c(state, _arrays, _sel)

    else:
        base_step = make_train_step(model, cfg, tx)
        step = jax.jit(
            build_multi_step(base_step, k) if k > 1 else base_step,
            donate_argnums=(0,),
            out_shardings=(shardings, None),
        )

    # warmup (compile) + 2 steps to stabilize
    for _ in range(3):
        state, metrics = step(state, timed_batch)
    jax.block_until_ready((state, metrics))

    # BENCH_STEPS counts TRAIN steps; a fused program runs k per dispatch,
    # so round up to whole dispatches and report per-step throughput
    n_steps = int(os.environ.get("BENCH_STEPS", "10"))
    n_dispatch = max(1, -(-n_steps // k))
    n_steps = n_dispatch * k
    t0 = time.time()
    for _ in range(n_dispatch):
        state, metrics = step(state, timed_batch)
    jax.block_until_ready((state, metrics))
    dt = time.time() - t0
    images_per_sec = n_steps * batch_size / dt

    if profile_dir is not None:
        _capture_trace(profile_dir, step, state, timed_batch)

    baseline_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks",
        "baseline_measured.json",
    )
    vs_baseline = float("nan")
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            baseline = json.load(f)
        ref = baseline.get("torch_cpu_images_per_sec")
        if ref:
            vs_baseline = images_per_sec / ref

    from replication_faster_rcnn_tpu.telemetry.mfu import (
        compute_mfu,
        peak_flops_per_sec,
    )

    flops_per_step = _step_flops(cfg, batch_size)
    peak, mfu_basis = peak_flops_per_sec(n_dev)
    mfu = compute_mfu(flops_per_step, images_per_sec / batch_size, peak)

    out = {
        "metric": metric,
        "value": round(images_per_sec, 3),
        "unit": "images/sec",
        "vs_baseline": round(vs_baseline, 3) if np.isfinite(vs_baseline) else None,
        "flops_per_step": flops_per_step,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "mfu_basis": mfu_basis,
    }
    if k > 1:
        out["steps_per_dispatch"] = k
    if os.environ.get("BENCH_BREAKDOWN", "1") != "0":
        if cfg.data.cache_device:
            # the stage prefixes time the FED graph; under the cached
            # step they would misattribute the gather — skip honestly
            out["breakdown"] = {
                "note": "skipped under --cache-device (stage prefixes "
                "time the fed-step graph)"
            }
        else:
            out["breakdown"] = _stage_breakdown(
                model, cfg, state, device_batch, dt / n_steps * 1e3, tx=tx
            )
    return out


def measure_eval(config=None, profile_dir=None) -> dict:
    """``BENCH_MODE=eval``: jitted inference throughput — forward + fixed-
    shape decode + per-class NMS (`eval/detect.py`), data-parallel over all
    devices — on synthetic 600x600 tensors, images/sec.

    ``vs_baseline`` is null by design: the reference has NO inference/eval
    path to race against (`test_eval.py` is 0 bytes — SURVEY.md §2.1 #15);
    this metric exists because the eval path is new capability whose cost
    still needs a number of record."""
    import dataclasses

    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.data.loader import collate
    from replication_faster_rcnn_tpu.eval import Evaluator
    from replication_faster_rcnn_tpu.train import (
        create_train_state,
        make_optimizer,
    )

    n_dev = len(jax.devices())
    if config is None:
        cfg = _flagship_cfg(n_dev)
    else:
        cfg = config.replace(
            data=dataclasses.replace(config.data, dataset="synthetic")
        )
        if cfg.mesh.num_model > 1 or cfg.mesh.spatial:
            # the eval path is data-parallel only (Evaluator._eval_sharding
            # forces num_model=1): refuse rather than print a number
            # labeled as if the requested model-parallel layout ran
            raise ValueError(
                "BENCH_MODE=eval measures the data-parallel eval path only; "
                "drop --num-model/--spatial (got num_model="
                f"{cfg.mesh.num_model}, spatial={cfg.mesh.spatial})"
            )
        from replication_faster_rcnn_tpu.parallel import validate_parallel

        validate_parallel(cfg, n_dev)
    metric = "eval_images_per_sec_{}x{}".format(*cfg.data.image_size)
    # batch precedence: BENCH_EVAL_BATCH env > the CLI/caller config's
    # train.batch_size > 8 per device; the JSON reports the effective value
    if "BENCH_EVAL_BATCH" in os.environ:
        batch_size = int(os.environ["BENCH_EVAL_BATCH"])
    elif config is not None:
        batch_size = cfg.train.batch_size
    else:
        batch_size = 8 * n_dev
    tx, _ = make_optimizer(cfg, steps_per_epoch=100)
    _, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    ev = Evaluator(cfg)
    img_sharding, rep_sharding = ev._eval_sharding(batch_size)
    if rep_sharding is not None:
        variables = jax.device_put(variables, rep_sharding)
    ds = SyntheticDataset(cfg.data, length=batch_size)
    images = collate([ds[i] for i in range(batch_size)])["image"]
    # same sync discipline as the train measurement: upload once, queue all
    # jitted calls, wait once at the end (the per-call device_put/get
    # inside Evaluator.predict_batch would add a host round-trip per step)
    images_dev = jax.device_put(np.asarray(images), img_sharding)
    for _ in range(3):
        out = ev._jit_infer(variables, images_dev)
    jax.block_until_ready(out)
    n_steps = int(os.environ.get("BENCH_STEPS", "10"))
    t0 = time.time()
    for _ in range(n_steps):
        out = ev._jit_infer(variables, images_dev)
    jax.block_until_ready(out)
    dt = time.time() - t0
    value = round(n_steps * batch_size / dt, 3)
    record = {
        "metric": metric,
        "value": value,
        "unit": "images/sec",
        "vs_baseline": None,
        "batch_size": batch_size,
        "note": "reference has no eval/inference path (empty "
        "test_eval.py); no baseline ratio exists",
    }
    if profile_dir is not None:
        _capture_trace(
            profile_dir,
            lambda v, img: (v, ev._jit_infer(v, img)),
            variables,
            images_dev,
        )
    return record


def _step_flops(cfg, batch_size) -> float:
    """Global FLOPs of one train step (full ``batch_size``), from XLA's
    HloCostAnalysis of the step lowered for ONE device, in this process.

    The lowering only traces abstract values — it allocates no batch
    arrays. The count is *model* FLOPs (1-device graph, no
    halo/collective duplication), the conventional MFU numerator."""
    import dataclasses

    flops = _flops_of_config(
        cfg.replace(
            mesh=dataclasses.replace(
                cfg.mesh, num_data=1, num_model=1, spatial=False
            ),
            train=dataclasses.replace(
                cfg.train, backend="auto", batch_size=batch_size
            ),
        )
    )
    if not flops > 0:
        raise RuntimeError(
            f"HloCostAnalysis reported no FLOPs for the train step ({flops!r})"
        )
    return flops


def abstract_step_inputs(cfg, tx):
    """(model, state_abs, batch_abs): abstract fixtures of one train step
    — shapes/dtypes only, no arrays allocated, no param-init programs run
    (a pure trace). Shared by the bench's FLOPs counter and the static
    cost-attribution script (`benchmarks/backward_analysis.py`) so the
    two can never analyze different shapes."""
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.data.loader import collate
    from replication_faster_rcnn_tpu.models.faster_rcnn import FasterRCNN
    from replication_faster_rcnn_tpu.train import create_train_state

    model = FasterRCNN(cfg)
    state_abs = jax.eval_shape(
        lambda rng: create_train_state(cfg, rng, tx)[1], jax.random.PRNGKey(0)
    )
    sample = collate([SyntheticDataset(cfg.data, length=1)[0]])
    b = cfg.train.batch_size
    batch_abs = {
        k: jax.ShapeDtypeStruct((b,) + v.shape[1:], v.dtype)
        for k, v in sample.items()
    }
    if cfg.data.augment_device and (
        cfg.data.augment_hflip
        or cfg.data.augment_scale
        or cfg.data.augment_translate
    ):
        # device-mode augmentation ships an int32 (idx, epoch) row per
        # sample (data/augment.py::AugmentTagView) — the fixture must
        # carry it so warmup/audit lower the runtime trace, not a twin
        batch_abs["aug"] = jax.ShapeDtypeStruct((b, 2), np.int32)
    return model, state_abs, batch_abs


def lowered_cost_analysis(lowered):
    """{flops, bytes_accessed} of an already-lowered program, from XLA's
    HloCostAnalysis. Shared by the step-profile harness and the HLO
    auditor (analysis/fingerprint.py) so both price programs identically.

    The CPU client analyses the lowered module without compiling it. The
    TPU client has no pre-compile analysis (`Lowered.cost_analysis()` is
    None there — seen on the v5e, libtpu 0.0.34), so there the program is
    compiled and the executable's own analysis is read."""
    ca = lowered.cost_analysis()
    if ca is None:
        ca = lowered.compile().cost_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }


def lowered_cost(fn, *abstract_args):
    """{flops, bytes_accessed} of ``fn`` from HloCostAnalysis of its
    abstract lowering (see :func:`lowered_cost_analysis`)."""
    return lowered_cost_analysis(jax.jit(fn).lower(*abstract_args))


def _flops_of_config(cfg) -> float:
    """HloCostAnalysis FLOPs of one train step of ``cfg`` (abstract
    lowering — no batch arrays)."""
    from replication_faster_rcnn_tpu.train import make_optimizer, make_train_step

    tx, _ = make_optimizer(cfg, steps_per_epoch=100)
    model, state_abs, batch_abs = abstract_step_inputs(cfg, tx)
    return lowered_cost(
        make_train_step(model, cfg, tx), state_abs, batch_abs
    )["flops"]


def _stage_breakdown(model, cfg, state, device_batch, step_ms: float, tx=None):
    """Wall-time attribution across the step's pipeline stages.

    Times six jitted prefixes of the step (each returning a scalar so the
    host sync transfers nothing but still waits on the full computation):
    trunk -> +rpn heads -> +proposal NMS -> +target creators -> full
    forward+loss -> +value_and_grad; successive differences plus the
    already-measured full-step time attribute the device-side label
    makers (`targets_ms`) and head (`head_loss_ms`) inside the old
    targets_head_loss lump, and backward (grad minus forward) vs the
    optimizer update (step minus grad). One more jitted program (not a
    prefix) times the optimizer update directly on materialized
    gradients (`opt_update_direct_ms`). BENCH_BREAKDOWN=0 disables
    (7 extra stage compiles).
    """
    import jax.numpy as jnp
    import optax

    from replication_faster_rcnn_tpu.train.train_step import compute_losses

    h, w = cfg.data.image_size
    has_jitter = "jitter" in device_batch

    def _scalar(feat):
        # FPN's extract_features returns a list of levels
        feats = feat if isinstance(feat, (list, tuple)) else [feat]
        return sum(f.astype(jnp.float32).sum() for f in feats)

    def _images(batch):
        # under --augment-scale-device the real step's first on-device op
        # is the jitter resample gather (train_step.compute_losses); the
        # prefixes must run the same pipeline or the resample cost would
        # silently land in targets_ms while trunk_ms timed a pipeline the
        # step never runs
        if has_jitter:
            from replication_faster_rcnn_tpu.ops.image import (
                batched_scale_jitter,
            )

            return batched_scale_jitter(batch["image"], batch["jitter"])
        return batch["image"]

    def _features(state, batch):
        # train=True to match what the timed step executes (train-mode BN
        # computes batch statistics; eval-mode would misattribute that
        # cost to the forward_fn - propose_fn difference)
        v = {"params": state.params, "batch_stats": state.batch_stats}
        feat, _ = model.apply(
            v, _images(batch), True, method="extract_features",
            mutable=["batch_stats"],
        )
        return v, feat

    @jax.jit
    def jitter_fn(state, batch):
        del state
        return _images(batch).astype(jnp.float32).sum()

    @jax.jit
    def trunk_fn(state, batch):
        _, feat = _features(state, batch)
        return _scalar(feat)

    @jax.jit
    def rpn_fn(state, batch):
        v, feat = _features(state, batch)
        logits, deltas, _ = model.apply(v, feat, method="rpn_forward")
        return logits.astype(jnp.float32).sum() + deltas.astype(jnp.float32).sum()

    @jax.jit
    def propose_fn(state, batch):
        v, feat = _features(state, batch)
        logits, deltas, anchors = model.apply(v, feat, method="rpn_forward")
        rois, valid = model.apply(
            v, logits, deltas, anchors, float(h), float(w), True, method="propose"
        )
        return rois.sum() + valid.sum()

    @jax.jit
    def targets_fn(state, batch):
        # the real step's own prefix (trunk -> RPN -> propose -> both
        # target creators, no head): compute_losses' targets_only mode,
        # so this timed stage can never drift from what the step runs
        rng = jax.random.fold_in(state.rng, state.step)
        probe, _ = compute_losses(
            model, cfg, state.params, state.batch_stats, batch, rng, True,
            targets_only=True,
        )
        return probe

    @jax.jit
    def forward_fn(state, batch):
        rng = jax.random.fold_in(state.rng, state.step)
        total, _ = compute_losses(
            model, cfg, state.params, state.batch_stats, batch, rng, True
        )
        return total

    @jax.jit
    def grad_fn(state, batch):
        rng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(params):
            return compute_losses(
                model, cfg, params, state.batch_stats, batch, rng, True
            )

        (total, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params
        )
        # the norm consumes every gradient (otherwise XLA would DCE the
        # whole backward) and is exactly what the real step computes for
        # its grad_norm metric, so the stage cost matches the step's
        return total + optax.global_norm(grads)

    @jax.jit
    def null_fn(state, grads):
        # near-empty program with the same on-device inputs and a scalar
        # output: times pure dispatch + completion-sync overhead — the
        # floor to read opt_update_direct_ms against
        return jax.tree_util.tree_leaves(grads)[0].ravel()[0] + jnp.float32(
            state.step
        )

    @jax.jit
    def update_fn(state, grads):
        # the optimizer update ALONE, on materialized grads: a direct
        # measurement, unlike the step_ms - t_grad subtraction, whose
        # separately-jitted prefixes fuse differently and can report a
        # (noise-floor) NEGATIVE update cost. The updated trees are jit
        # OUTPUTS on purpose: an update whose results feed only a scalar
        # reduction can be fused into the reduce and never write the
        # params/mu/nu trees to HBM — eliding the very cost this row
        # measures.
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return params, opt_state

    def timed(fn, *args):
        for _ in range(2):  # compile + 1 stabilizing run
            out = fn(*args)
        jax.block_until_ready(out)
        n, t0 = 5, time.time()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.time() - t0) / n * 1e3

    t_jitter = timed(jitter_fn, state, device_batch) if has_jitter else None
    t_trunk = timed(trunk_fn, state, device_batch)
    t_rpn = timed(rpn_fn, state, device_batch)
    t_prop = timed(propose_fn, state, device_batch)
    t_targets = timed(targets_fn, state, device_batch)
    t_fwd = timed(forward_fn, state, device_batch)
    t_grad = timed(grad_fn, state, device_batch)
    t_upd = t_floor = None
    if tx is not None:
        zero_grads = jax.tree_util.tree_map(jnp.zeros_like, state.params)
        t_upd = timed(update_fn, state, zero_grads)
        t_floor = timed(null_fn, state, zero_grads)
    out = {
        **({"jitter_ms": round(t_jitter, 2)} if t_jitter is not None else {}),
        # successive-difference convention: when the jitter stage exists it
        # is the pipeline's first prefix, so trunk gets the difference
        "trunk_ms": round(t_trunk - (t_jitter or 0.0), 2),
        "rpn_heads_ms": round(t_rpn - t_trunk, 2),
        "proposal_nms_ms": round(t_prop - t_rpn, 2),
        "targets_ms": round(t_targets - t_prop, 2),
        "head_loss_ms": round(t_fwd - t_targets, 2),
        "targets_head_loss_ms": round(t_fwd - t_prop, 2),
        "backward_ms": round(t_grad - t_fwd, 2),
        "opt_update_ms": round(step_ms - t_grad, 2),
        "backward_update_ms": round(step_ms - t_fwd, 2),
        "step_ms": round(step_ms, 2),
    }
    if t_upd is not None:
        out["opt_update_direct_ms"] = round(t_upd, 2)
        out["dispatch_floor_ms"] = round(t_floor, 2)
        # the update's cost net of the per-program dispatch/sync floor
        out["opt_update_direct_adj_ms"] = round(max(0.0, t_upd - t_floor), 2)
    return out


if __name__ == "__main__":
    main()
