"""Command-line interface — the config/flag layer the reference never had
(SURVEY.md §5: hyperparameters live in scattered constants and a flagless
``__main__`` at reference `train.py:153-161`; BASELINE.json requires a
``--device=tpu`` path).

Subcommands:
  train      — run the jitted SPMD trainer (--telemetry enables the
               span-trace/health/watchdog observability layer)
  eval       — run inference + VOC mAP over a dataset split
  telemetry  — summarize a --telemetry run dir (phase times + health)

``--config`` selects one of the five BASELINE presets (config.CONFIGS);
individual flags override preset fields.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import List, Optional


def _device_fields() -> dict:
    """The device as JAX reports it, under the names a run logs it by."""
    from replication_faster_rcnn_tpu.telemetry.mfu import device_record

    d = device_record()
    return {
        "platform": d["platform"],
        "device_kind": d["kind"],
        "device_count": d["count"],
    }


def _apply_device(device: str, announce: bool = True) -> None:
    """--device=tpu|cpu: pick the JAX backend before any computation, and
    say on stderr which platform the run landed on (`auto` lands wherever
    JAX does). ``announce=False`` is for the trainer, which must bring up
    jax.distributed BEFORE the backend and logs its device itself."""
    import jax

    if device != "auto":
        jax.config.update("jax_platforms", device)
    if announce:
        fields = " ".join(f"{k}={v}" for k, v in _device_fields().items())
        print(f"[device] {fields}", file=sys.stderr)


def _apply_distributed(args) -> None:
    """--num-processes/--coordinator/--process-id: bring up the multi-host
    runtime BEFORE anything queries the device topology (jax.distributed
    must initialize before the backend does). No-op single-process."""
    n = getattr(args, "num_processes", None)
    if not n or n <= 1:
        return
    from replication_faster_rcnn_tpu.parallel import initialize_distributed

    initialize_distributed(
        coordinator_address=getattr(args, "coordinator", None),
        num_processes=n,
        process_id=getattr(args, "process_id", None),
    )


def _parse_mesh_shape(text):
    """`--mesh-shape DP,MP` -> MeshConfig overrides. MP > 1 turns on
    model-axis parameter sharding (the whole point of naming a 2D mesh);
    `--mesh-shape 8,1` is an explicit dp-only pin."""
    parts = text.split(",")
    try:
        dp, mp = (int(p.strip()) for p in parts)
        if dp < 1 or mp < 1:
            raise ValueError
    except ValueError:
        raise SystemExit(
            f"--mesh-shape expects 'DP,MP' with two positive integers "
            f"(e.g. 2,4), got {text!r}"
        )
    return {"num_data": dp, "num_model": mp, "param_sharding": mp > 1}


def _build_config(args):
    from replication_faster_rcnn_tpu.config import get_config

    cfg = get_config(args.config)
    if args.dataset:
        cfg = cfg.replace(data=dataclasses.replace(cfg.data, dataset=args.dataset))
    if args.data_root:
        cfg = cfg.replace(data=dataclasses.replace(cfg.data, root_dir=args.data_root))
    if args.image_size:
        cfg = cfg.replace(
            data=dataclasses.replace(
                cfg.data, image_size=(args.image_size, args.image_size)
            )
        )
    data_kw = {}
    if getattr(args, "loader_workers", None) is not None:
        data_kw["loader_workers"] = args.loader_workers
    if getattr(args, "loader_mode", None):
        data_kw["loader_mode"] = args.loader_mode
    if getattr(args, "augment_hflip", False):
        data_kw["augment_hflip"] = True
    elif getattr(args, "no_augment_hflip", False):
        data_kw["augment_hflip"] = False
    if getattr(args, "augment_scale", None):
        data_kw["augment_scale"] = tuple(args.augment_scale)
    if getattr(args, "augment_scale_device", False):
        data_kw["augment_scale_device"] = True
    if getattr(args, "augment_device", False):
        data_kw["augment_device"] = True
    if getattr(args, "augment_translate", None) is not None:
        data_kw["augment_translate"] = args.augment_translate
    if getattr(args, "cache_ram", False):
        data_kw["loader_cache_ram"] = True
    if getattr(args, "cache_device", False):
        data_kw["cache_device"] = True
    if getattr(args, "device_normalize", False):
        data_kw["device_normalize"] = True
    if getattr(args, "prefetch_device", None) is not None:
        data_kw["prefetch_device"] = args.prefetch_device
    if getattr(args, "train_resolutions", None):
        try:
            data_kw["train_resolutions"] = tuple(
                tuple(int(x) for x in r.split("x"))
                for r in args.train_resolutions.split(",")
            )
        except ValueError:
            raise SystemExit(
                "--train-resolutions expects 'HxW,HxW' with positive "
                f"integers (e.g. 300x300,600x600), got "
                f"{args.train_resolutions!r}"
            )
    if data_kw:
        cfg = cfg.replace(data=dataclasses.replace(cfg.data, **data_kw))
    train_kw = {}
    if args.lr is not None:
        train_kw["lr"] = args.lr
    if args.batch_size is not None:
        train_kw["batch_size"] = args.batch_size
    if args.epochs is not None:
        train_kw["n_epoch"] = args.epochs
    if args.seed is not None:
        train_kw["seed"] = args.seed
    if getattr(args, "backend", None):
        train_kw["backend"] = args.backend
    if getattr(args, "shard_opt", False):
        train_kw["shard_opt_state"] = True
    if getattr(args, "eval_every", None) is not None:
        train_kw["eval_every_epochs"] = args.eval_every
    if getattr(args, "mu_dtype", None):
        train_kw["adam_mu_dtype"] = args.mu_dtype
    if getattr(args, "steps_per_dispatch", None) is not None:
        train_kw["steps_per_dispatch"] = args.steps_per_dispatch
    if getattr(args, "grad_allreduce_dtype", None):
        train_kw["grad_allreduce_dtype"] = args.grad_allreduce_dtype
    if getattr(args, "nonfinite_policy", None):
        train_kw["nonfinite_policy"] = args.nonfinite_policy
    if getattr(args, "max_consecutive_skips", None) is not None:
        train_kw["max_consecutive_skips"] = args.max_consecutive_skips
    if getattr(args, "async_checkpoint", False):
        train_kw["async_checkpoint"] = True
    if getattr(args, "lr_scaling", None):
        train_kw["lr_scaling"] = args.lr_scaling
    if getattr(args, "base_batch_size", None) is not None:
        train_kw["base_batch_size"] = args.base_batch_size
    if getattr(args, "warmup_epochs", None) is not None:
        train_kw["warmup_epochs"] = args.warmup_epochs
    if getattr(args, "lars", False):
        train_kw["lars"] = True
    if getattr(args, "optimizer", None):
        train_kw["optimizer"] = args.optimizer
    if getattr(args, "checkpoint_every_steps", None) is not None:
        train_kw["checkpoint_every_steps"] = args.checkpoint_every_steps
    if getattr(args, "sampling_strategy", None):
        train_kw["sampling_strategy"] = args.sampling_strategy
    if train_kw:
        cfg = cfg.replace(train=dataclasses.replace(cfg.train, **train_kw))
    if getattr(args, "compile_cache", None):
        cfg = cfg.replace(
            compile=dataclasses.replace(
                cfg.compile, cache_dir=args.compile_cache
            )
        )
    if getattr(args, "strict", False):
        cfg = cfg.replace(
            debug=dataclasses.replace(cfg.debug, strict=True)
        )
    if getattr(args, "threadsan", False):
        cfg = cfg.replace(
            debug=dataclasses.replace(cfg.debug, threadsan=True)
        )
    if getattr(args, "chaos_spec", None):
        cfg = cfg.replace(
            debug=dataclasses.replace(cfg.debug, chaos_spec=args.chaos_spec)
        )
    if (args.backbone or args.roi_op or getattr(args, "remat", False)
            or getattr(args, "frozen_bn", False)
            or getattr(args, "norm", None)):
        model_kw = {}
        if args.backbone:
            model_kw["backbone"] = args.backbone
        if args.roi_op:
            model_kw["roi_op"] = args.roi_op
        if getattr(args, "remat", False):
            model_kw["remat"] = True
        if getattr(args, "frozen_bn", False):
            model_kw["frozen_bn"] = True
        if getattr(args, "norm", None):
            model_kw["norm"] = args.norm
        cfg = cfg.replace(model=dataclasses.replace(cfg.model, **model_kw))
    mesh_kw = {}
    if getattr(args, "mesh_shape", None):
        mesh_kw.update(_parse_mesh_shape(args.mesh_shape))
    if getattr(args, "num_model", None) is not None:
        mesh_kw["num_model"] = args.num_model
    if getattr(args, "spatial", False):
        mesh_kw["spatial"] = True
    if mesh_kw:
        cfg = cfg.replace(mesh=dataclasses.replace(cfg.mesh, **mesh_kw))
    eval_kw = {}
    if getattr(args, "iou_thresh", None) is not None:
        eval_kw["iou_thresh"] = args.iou_thresh
    if getattr(args, "use_07_metric", False):
        eval_kw["use_07_metric"] = True
    if getattr(args, "metric", None):
        eval_kw["metric"] = args.metric
    if getattr(args, "tta_hflip", False):
        eval_kw["tta_hflip"] = True
    if eval_kw:
        cfg = cfg.replace(eval=dataclasses.replace(cfg.eval, **eval_kw))
    return cfg


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--config", "--preset", default="voc_resnet18",
                   help="preset name (see replication_faster_rcnn_tpu.config.CONFIGS)")
    p.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"],
                   help="JAX backend (BASELINE --device flag)")
    p.add_argument("--strict", action="store_true",
                   help="runtime jit-hygiene gate (debug.strict): "
                        "jax.transfer_guard('disallow') for the whole "
                        "session + a per-program recompile check after "
                        "warmup — implicit transfers and silent recompiles "
                        "raise instead of eating throughput")
    p.add_argument("--threadsan", action="store_true",
                   help="runtime lock sanitizer (debug.threadsan): "
                        "package-created locks/queues are instrumented, "
                        "lock-order inversions raise (lightweight lockdep), "
                        "and held-duration + queue-depth gauges feed the "
                        "telemetry watchdog; runtime half of the TL rules "
                        "in 'frcnn check'")
    p.add_argument("--chaos-spec", default=None, metavar="SPEC",
                   help="deterministic fault injection (faultlib): "
                        "'site:kind:prob:seed[:arg[:max_fires[:after]]]' comma "
                        "list, or a JSON schedule file (path or @path); "
                        "sites/kinds in faultlib.failpoints.SITES/KINDS. "
                        "Same spec + seed => identical fault sequence")
    p.add_argument("--dataset", default=None, choices=[None, "voc", "coco", "synthetic"])
    p.add_argument("--data-root", default=None)
    p.add_argument("--image-size", type=int, default=None)
    p.add_argument("--backbone", default=None,
                   choices=[None, "resnet18", "resnet34", "resnet50", "resnet101",
                            "resnet152", "resnext50_32x4d", "resnext101_32x8d",
                            "wide_resnet50_2", "wide_resnet101_2", "vgg16"])
    p.add_argument("--roi-op", default=None, choices=[None, "align", "pool"])
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--backend", default=None, choices=[None, "auto", "spmd"],
                   help="SPMD backend: jit auto-partitioning or explicit "
                        "shard_map collectives (parallel/spmd.py)")
    p.add_argument("--shard-opt", action="store_true",
                   help="ZeRO-1 weight-update sharding: Adam moments shard "
                        "over the data axis (arXiv:2004.13336). Works on "
                        "both backends: jit lets GSPMD place the "
                        "collectives, spmd hand-places reduce-scatter + "
                        "all-gather around a sharded update")
    p.add_argument("--num-processes", type=int, default=None, metavar="N",
                   help="multi-host data parallelism: total process count "
                        "of this run (each process sees only its local "
                        "devices; batch-size stays GLOBAL and must divide "
                        "by N). Pair with --coordinator/--process-id")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="coordinator address for --num-processes > 1 "
                        "(jax.distributed.initialize)")
    p.add_argument("--process-id", type=int, default=None,
                   help="this process's rank in [0, --num-processes) "
                        "(rank 0 is the coordinator: it owns checkpoints, "
                        "manifests and the canonical telemetry files)")
    p.add_argument("--lr-scaling", default=None, choices=[None, "none", "linear"],
                   help="large-batch LR recipe: 'linear' scales the peak "
                        "LR by batch_size / base-batch-size "
                        "(arXiv:1706.02677 via arXiv:1711.04325)")
    p.add_argument("--base-batch-size", type=int, default=None,
                   help="reference batch size the preset LR was tuned at "
                        "(denominator of --lr-scaling linear; default 8)")
    p.add_argument("--warmup-epochs", type=float, default=None,
                   help="linear LR warmup from ~0 to the (scaled) peak "
                        "over this many epochs before the cosine decay "
                        "(large-batch stability; fractions allowed)")
    p.add_argument("--lars", action="store_true",
                   help="layer-wise trust-ratio scaling (LARS, "
                        "arXiv:1708.03888) between Adam and the LR — the "
                        "large-batch optimizer recipe. Incompatible with "
                        "--shard-opt on the spmd backend (per-leaf norms)")
    p.add_argument("--optimizer", default=None, choices=[None, "adam", "lamb"],
                   help="optimizer chain (train.optimizer): 'adam' "
                        "(default) or 'lamb' — Adam plus a per-layer "
                        "trust ratio (arXiv:1904.00962). LAMB composes "
                        "with --shard-opt on BOTH backends: the spmd+ZeRO "
                        "path computes each layer's norms from its local "
                        "shard and completes them with a psum, so the "
                        "trust ratio is exact at 1/N moment memory")
    p.add_argument("--checkpoint-every-steps", type=int, default=None,
                   metavar="N",
                   help="scheduled checkpoint every N optimizer steps, in "
                        "addition to the per-epoch cadence (0 = off). "
                        "Bounds the rollback of an elastic re-formation, "
                        "which resumes from the last verified step "
                        "(train.checkpoint_every_steps)")
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint each trunk block (recompute "
                        "activations in backward; saves HBM)")
    p.add_argument("--frozen-bn", action="store_true",
                   help="freeze BatchNorm statistics during training "
                        "(detection fine-tuning practice; each BN becomes "
                        "a fusable affine. Affine scale/bias stay "
                        "trainable, unlike torchvision's full freeze)")
    p.add_argument("--norm", default=None, choices=[None, "batch", "group"],
                   help="backbone normalization: 'batch' (reference "
                        "semantics) or 'group' (GroupNorm(32), BN-free — "
                        "no batch-stats reductions/fusion breaks; "
                        "torch-pretrained BN weights don't convert)")
    p.add_argument("--mu-dtype", default=None,
                   choices=[None, "float32", "bfloat16"],
                   help="dtype for Adam's first moment (bfloat16 halves "
                        "its HBM traffic in the update)")
    p.add_argument("--steps-per-dispatch", type=int, default=None,
                   help="fuse K train steps into one jitted dispatch "
                        "(lax.scan over K device-resident batches; "
                        "amortizes per-step Python dispatch, metrics "
                        "sync only at log boundaries)")
    p.add_argument("--grad-allreduce-dtype", default=None,
                   choices=[None, "float32", "bfloat16"],
                   help="dtype the gradient all-reduce rides in; "
                        "bfloat16 halves the psum bytes on the shard_map "
                        "backend and de-casts for fp32 optimizer math")
    p.add_argument("--nonfinite-policy", default=None,
                   choices=[None, "apply", "skip", "halt"],
                   help="what the jitted step does with a non-finite "
                        "gradient: skip (default) withholds the update "
                        "(params/opt state/BN stats unchanged, skipped=1 "
                        "in metrics), halt raises on the first skip, "
                        "apply is the unguarded update")
    p.add_argument("--max-consecutive-skips", type=int, default=None,
                   help="consecutive nonfinite-gradient skips before "
                        "training raises instead of free-running on a "
                        "divergent model (nonfinite-policy=skip)")
    p.add_argument("--loader-workers", type=int, default=None,
                   help="host input-pipeline worker count")
    p.add_argument("--loader-mode", default=None,
                   choices=[None, "thread", "process"],
                   help="input workers as GIL-releasing threads (native "
                        "decode) or forked processes (Python-bound work)")
    p.add_argument("--device-normalize", action="store_true",
                   help="ship uint8 images to the device and normalize "
                        "on-chip (4x less host->device transfer)")
    p.add_argument("--cache-ram", action="store_true",
                   help="cache decoded samples in host RAM (epoch 1 pays "
                        "the decode, later epochs are memcpy; bounded by "
                        "FRCNN_CACHE_MAX_BYTES, default 64 GiB)")
    p.add_argument("--cache-device", action="store_true",
                   help="device-resident dataset: upload all samples to "
                        "HBM once, ship only batch indices per step and "
                        "gather/augment inside the jitted step (pair with "
                        "--device-normalize; bounded by "
                        "FRCNN_DEVICE_CACHE_MAX_BYTES, default 8 GiB)")
    p.add_argument("--augment-hflip", action="store_true",
                   help="50%% horizontal-flip train augmentation "
                        "(deterministic per seed/epoch/index; the VOC "
                        "presets default it ON)")
    p.add_argument("--no-augment-hflip", action="store_true",
                   help="disable the flip (reproduces the reference's "
                        "no-augmentation training on VOC presets)")
    p.add_argument("--augment-scale", type=float, nargs=2, default=None,
                   metavar=("LO", "HI"),
                   help="random scale-jitter augmentation, e.g. 0.75 1.25 "
                        "(fixed canvas: zoom-out pads, zoom-in crops; "
                        "deterministic per seed/epoch/index)")
    p.add_argument("--augment-scale-device", action="store_true",
                   help="run the jitter's image resample on device (host "
                        "transforms boxes only; removes the per-sample "
                        "host resample cost from ingest)")
    p.add_argument("--augment-device", action="store_true",
                   help="run ALL enabled augmentations (flip/scale/"
                        "translate) as jitted batch ops inside the "
                        "compiled step; the host loader ships raw pixels "
                        "plus per-row (index, epoch) tags and never "
                        "touches image bytes (data.augment_device)")
    p.add_argument("--augment-translate", type=float, default=None,
                   metavar="FRAC",
                   help="random translation jitter up to FRAC of the "
                        "canvas per axis (device-mode only: requires "
                        "--augment-device; boxes shifted and clamped, "
                        "collapsed rows masked; data.augment_translate)")
    p.add_argument("--train-resolutions", default=None, metavar="HxW,HxW",
                   help="multi-scale bucketed training, e.g. "
                        "'300x300,600x600': each dispatch chunk is "
                        "deterministically hashed to one bucket and "
                        "trained through that bucket's own compiled "
                        "program (on-device resize + box rescale; "
                        "data.train_resolutions)")
    p.add_argument("--sampling-strategy", default=None,
                   choices=[None, "random", "topk_iou"],
                   help="second-stage ROI sampling "
                        "(train.sampling_strategy): 'random' draws the "
                        "pos/neg quotas uniformly (reference recipe); "
                        "'topk_iou' keeps the highest-IoU positives and "
                        "hardest negatives deterministically "
                        "(arXiv:1702.02138 biased sampling)")
    p.add_argument("--prefetch-device", type=int, default=None, metavar="N",
                   help="double-buffered DEVICE staging: a producer thread "
                        "collates and starts the next batch's host->device "
                        "transfer while the current dispatch runs (N = "
                        "buffer depth, 2 = classic double buffering, "
                        "0 = off). Chunk-aware under --steps-per-dispatch; "
                        "works with every feed incl. --cache-device")
    p.add_argument("--async-checkpoint", action="store_true",
                   help="scheduled checkpoints snapshot to host and "
                        "serialize + CRC-manifest on a background writer "
                        "(training blocks only if the previous save is "
                        "still in flight); emergency/final/crash saves "
                        "stay synchronous. Multi-process runs keep the "
                        "snapshot on device and every rank's writer "
                        "thread joins the collective save")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="where the persistent XLA compilation cache "
                        "lives when JAX_COMPILATION_CACHE_DIR is not set "
                        "(the environment wins; default: .compile_cache/ "
                        "in the checkout). Restarts deserialize instead "
                        "of re-running XLA; pair with the 'warmup' "
                        "subcommand to prepopulate")
    p.add_argument("--num-model", type=int, default=None,
                   help="size of the mesh's model axis")
    p.add_argument("--spatial", action="store_true",
                   help="shard image rows over the model axis (spatial "
                        "partitioning; GSPMD conv halo exchange)")
    p.add_argument("--mesh-shape", default=None, metavar="DP,MP",
                   help="2D device mesh as 'DP,MP' (e.g. 2,4): DP-way "
                        "data parallelism x MP-way model parallelism with "
                        "parameters sharded 1/MP over the model axis "
                        "(mesh.param_sharding; requires the jit "
                        "auto-partitioning backend)")


def _threadsan_session(enabled: bool):
    """Context manager installing the runtime lock sanitizer BEFORE the
    threaded subsystems are constructed (their instance locks/queues must
    be created under the patched factories), printing the report on exit."""
    import contextlib

    if not enabled:
        return contextlib.nullcontext(None)

    @contextlib.contextmanager
    def session():
        from replication_faster_rcnn_tpu.analysis.threadsan import (
            ThreadSanitizer,
        )

        san = ThreadSanitizer()
        with san:
            yield san
        rep = san.report()
        print(
            f"threadsan: {len(rep['inversions'])} lock-order inversion(s), "
            f"{rep['locks_tracked']} lock(s) and "
            f"{rep['queues_tracked']} queue(s) tracked",
            file=sys.stderr,
        )

    return session()


def cmd_train(args) -> int:
    if getattr(args, "elastic", False):
        # fleet supervisor mode: this process never touches jax — it
        # spawns the real training child per fleet generation and
        # re-forms the fleet when the child dies of a lost rank
        return _cmd_train_elastic(args)
    with _threadsan_session(getattr(args, "threadsan", False)) as san:
        return _cmd_train_impl(args, san)


def _cmd_train_elastic(args) -> int:
    """--elastic: per-host fleet supervisor (parallel/elastic.py).

    Spawns the training child (this same CLI minus --elastic, plus the
    generation's topology flags) and loops the re-formation protocol:
    a child that exits EXIT_FLEET_SHRINK — its elastic agent detected a
    peer's lease expiring — triggers claim/plan arbitration with the
    other surviving supervisors through the shared fleet dir, and the
    child respawns at the surviving world size with --resume, a bumped
    coordinator port and FRCNN_FLEET_GENERATION exported. Exit 0 and
    EXIT_PREEMPTED propagate; any other child exit means this host is
    the casualty and its supervisor leaves the fleet."""
    import os
    import subprocess

    from replication_faster_rcnn_tpu.config import get_config
    from replication_faster_rcnn_tpu.parallel import elastic

    world = args.num_processes or 1
    rank = args.process_id or 0
    coordinator = args.coordinator or "127.0.0.1:9911"
    host, _, port = coordinator.rpartition(":")
    fleet_dir = os.path.join(args.workdir, "fleet")
    el_cfg = get_config(args.config).elastic
    argv0 = list(getattr(args, "_argv", None) or sys.argv[1:])

    def spawn(generation, rank, world, coordinator):
        child = elastic.child_argv(
            argv0, generation=generation, rank=rank, world=world,
            coordinator=coordinator,
        )
        return subprocess.Popen(
            [sys.executable, "-m", "replication_faster_rcnn_tpu", *child],
            env=elastic.child_env(os.environ, fleet_dir, generation),
        )

    return elastic.run_supervisor(
        spawn,
        fleet_dir=fleet_dir,
        rank=rank,
        world=world,
        host=host or "127.0.0.1",
        base_port=int(port),
        settle_s=el_cfg.settle_s,
        max_generations=el_cfg.max_generations,
    )


def _cmd_train_impl(args, san=None) -> int:
    _apply_device(args.device, announce=False)
    _apply_distributed(args)
    if args.debug_nans:
        from replication_faster_rcnn_tpu.utils.debug import enable_nan_checks

        enable_nan_checks()
    from replication_faster_rcnn_tpu.train import Trainer

    cfg = _build_config(args)
    if cfg.debug.chaos_spec:
        from replication_faster_rcnn_tpu.faultlib import failpoints

        failpoints.configure(cfg.debug.chaos_spec)
    trainer = Trainer(
        cfg,
        workdir=args.workdir,
        telemetry_dir=args.telemetry,
        stall_timeout_s=args.stall_timeout,
    )
    if san is not None and trainer.watchdog is not None:
        san.register_gauges(trainer.watchdog)
    # every run says which platform it is on (stream + metrics.jsonl)
    trainer.logger.event("device", **_device_fields())
    if args.pretrained_backbone:
        trainer.load_pretrained_backbone(args.pretrained_backbone)
    from replication_faster_rcnn_tpu.utils.profiling import trace

    from replication_faster_rcnn_tpu.train.fault import (
        EXIT_FLEET_SHRINK,
        EXIT_PREEMPTED,
        FleetShrink,
        GracefulShutdown,
        Preempted,
        check_step_metrics,
    )

    if args.steps:
        # bounded-step mode (smoke/CI): iterate the feed cyclically
        # (the index sampler in --cache-device mode, the loader otherwise)
        import itertools

        feed = trainer.sampler if trainer.device_cache is not None else trainer.loader
        it = itertools.cycle(iter(feed))

        # honor --resume here too: the preemption message tells the user to
        # restart with it, and bounded-step runs are preemptible as well.
        # --steps N is a global-step target, so a resumed run does the rest.
        start = trainer.restore() if args.resume else 0
        if start:
            print(f"resumed from checkpoint at step {start}", file=sys.stderr)

        def _log(i, metrics, row=None):
            import jax

            with trainer.tracer.span("step/sync", cat="sync"):
                host_metrics = jax.device_get(metrics)
            if row is not None:
                host_metrics = {k: v[row] for k, v in host_metrics.items()}
            trainer.logger.log(i, check_step_metrics(host_metrics, i))
            trainer.skip_monitor.drain()

        k = trainer.steps_per_dispatch
        log_every = max(1, args.log_every)
        try:
            with trainer.telemetry_session(), trainer.strict_session(), \
                    GracefulShutdown() as shutdown:
                with trace(args.profile):
                    done = start
                    while done < args.steps:
                        # full chunks ride the fused dispatch; a remainder
                        # shorter than K falls back to the per-step path
                        fused = k > 1 and args.steps - done >= k
                        take = k if fused else 1
                        with trainer.tracer.span("data/fetch", cat="data"):
                            batches = [next(it) for _ in range(take)]
                        # multi-scale buckets: bounded-step runs have no
                        # epoch loop, so the bucket hash keys off the
                        # global step (deterministic across restarts)
                        bucket = (
                            feed.bucket_of(done)
                            if trainer.jitted_bucket_steps is not None
                            else None
                        )
                        if fused:
                            metrics = trainer.train_chunk(batches, bucket=bucket)
                        else:
                            metrics = trainer.train_one_batch(
                                batches[0], bucket=bucket
                            )
                        if trainer.watchdog is not None:
                            trainer.watchdog.beat(step=done + take, phase="train")
                        # same cadence as the per-step loop: log the first
                        # 0-indexed step i in this dispatch with i % log_every
                        # == 0 (chunk-aware: index into the stacked metrics)
                        for i in range(done, done + take):
                            if i % log_every == 0:
                                _log(i, metrics, row=(i - done) if fused else None)
                                break
                        done += take
                        if shutdown.requested:
                            # same dispatch-boundary semantics as the epoch
                            # loop: emergency checkpoint, then distinct code
                            trainer._fault_incident(
                                "preempted", step=done,
                                reason=shutdown.reason or "signal",
                            )
                            trainer.save(kind="emergency")
                            raise Preempted(done, shutdown.reason or "signal")
                    trainer.skip_monitor.drain()
        except Preempted as p:
            print(f"{p} (exit {EXIT_PREEMPTED})", file=sys.stderr)
            return EXIT_PREEMPTED
        _log_strict_report(trainer)
        trainer.save(kind="final")
        return 0
    try:
        with trace(args.profile):
            trainer.train(resume=args.resume, log_every=args.log_every)
    except Preempted as p:
        print(f"{p} (exit {EXIT_PREEMPTED})", file=sys.stderr)
        return EXIT_PREEMPTED
    except FleetShrink as fs:
        # the elastic agent already wrote the durable shrink intent the
        # supervisor re-forms from, and deliberately saved nothing (a
        # checkpoint save is a cross-process collective — it would hang
        # on the dead peer). Hard-exit: a normal interpreter exit would
        # run jax.distributed's atexit shutdown, which can wedge on the
        # dead peer, and the coordination service SIGABRTs us at ~10s
        # regardless.
        import os

        print(f"{fs} (exit {EXIT_FLEET_SHRINK})", file=sys.stderr)
        sys.stderr.flush()
        sys.stdout.flush()
        os._exit(EXIT_FLEET_SHRINK)
    except BaseException as e:
        if args.on_crash_checkpoint:
            # best-effort: persist whatever state survived the crash; the
            # manifest tags it kind="crash" so restore tooling can tell
            print(
                f"crash ({type(e).__name__}); attempting --on-crash-checkpoint "
                "save",
                file=sys.stderr,
            )
            trainer.save(kind="crash", required=False)
        raise
    _log_strict_report(trainer)
    trainer.save(kind="final")
    return 0


def _log_strict_report(trainer) -> None:
    """--strict: one `strict` event per program with its dispatch and
    post-warmup recompile counts (a recompile already raised; this is the
    record that none happened)."""
    if trainer.strict is None:
        return
    for name, st in trainer.strict.report()["programs"].items():
        trainer.logger.event(
            "strict",
            program=name,
            dispatches=st["dispatches"],
            warm_dispatches=st["warm_dispatches"],
            recompiles_after_warmup=st["recompiles_after_warmup"],
        )


def cmd_eval(args) -> int:
    _apply_device(args.device)
    from replication_faster_rcnn_tpu.data import make_dataset
    from replication_faster_rcnn_tpu.eval import Evaluator
    from replication_faster_rcnn_tpu.train.trainer import load_eval_variables

    cfg = _build_config(args)
    cfg.require_detector("eval")
    from replication_faster_rcnn_tpu.train.warmup import place_compile_cache

    place_compile_cache(cfg.compile.cache_dir)
    model, variables = load_eval_variables(cfg, args.workdir, args.checkpoint_step)
    dataset = make_dataset(cfg.data, args.split)
    ev = Evaluator(cfg, model)
    if cfg.debug.strict:
        from replication_faster_rcnn_tpu.analysis.strict import StrictHarness

        ev.strict = StrictHarness(cfg.debug.strict_warmup)
        with ev.strict.session():
            result = ev.evaluate(
                variables, dataset, batch_size=cfg.train.batch_size,
                max_images=args.max_images,
            )
    else:
        result = ev.evaluate(
            variables, dataset, batch_size=cfg.train.batch_size,
            max_images=args.max_images,
        )
    if cfg.eval.metric == "coco":
        print(
            f"mAP@[.50:.95]: {result['mAP']:.4f} "
            f"(AP50 {result.get('AP50', float('nan')):.4f}, "
            f"AP75 {result.get('AP75', float('nan')):.4f})"
        )
        if "AP_small" in result:
            print(
                f"  area: small {result['AP_small']:.4f}  "
                f"medium {result['AP_medium']:.4f}  "
                f"large {result['AP_large']:.4f}  (-1 = no gt in range)"
            )
    else:
        print(f"mAP@{cfg.eval.iou_thresh}: {result['mAP']:.4f}")
    if args.per_class and "ap_per_class" in result:
        import numpy as np

        from replication_faster_rcnn_tpu.config import COCO_CLASSES, VOC_CLASSES

        names = {len(VOC_CLASSES): VOC_CLASSES, len(COCO_CLASSES): COCO_CLASSES}.get(
            cfg.model.num_classes,
            [str(i) for i in range(cfg.model.num_classes)],
        )
        aps = result["ap_per_class"]
        for c in range(1, cfg.model.num_classes):
            ap = aps[c]
            shown = "   n/a" if not np.isfinite(ap) else f"{ap:6.4f}"
            print(f"  {names[c]:>16s}  AP {shown}")
    return 0


def cmd_quantize(args) -> int:
    """PTQ calibration (+ optional sensitivity sweep) -> sidecar artifact.

    Calibrates per-channel int8 weight scales and activation ranges from
    a small sweep through the inference forward, optionally runs the
    per-layer-group sensitivity sweep (quantize one group at a time;
    groups whose response-reconstruction error or mAP drop crosses the
    `quant.*` budgets fall back to bf16), and writes the CRC-manifested
    sidecar `frcnn serve --params-dtype int8` loads.
    """
    import dataclasses as _dc
    import json

    _apply_device(args.device)
    from replication_faster_rcnn_tpu import quant
    from replication_faster_rcnn_tpu.train.fault import config_hash
    from replication_faster_rcnn_tpu.train.trainer import load_eval_variables

    cfg = _build_config(args)
    cfg.require_detector("quantize")
    q = cfg.quant
    if args.calib_batches is not None:
        q = _dc.replace(q, calib_batches=args.calib_batches)
    if args.calib_batch_size is not None:
        q = _dc.replace(q, calib_batch_size=args.calib_batch_size)
    cfg = cfg.replace(quant=q)
    model, variables = load_eval_variables(cfg, args.workdir, args.checkpoint_step)

    if cfg.data.dataset == "synthetic" or args.synthetic_calib:
        batches = quant.synthetic_calibration_batches(
            cfg, cfg.quant.calib_batches, cfg.quant.calib_batch_size
        )
    else:
        from replication_faster_rcnn_tpu.data import make_dataset

        batches = quant.dataset_calibration_batches(
            make_dataset(cfg.data, args.split),
            cfg.quant.calib_batches,
            cfg.quant.calib_batch_size,
        )
    artifact = quant.calibrate(model, variables, batches, cfg)

    if args.sweep:
        from replication_faster_rcnn_tpu.quant.sensitivity import sweep

        eval_fn = None
        if args.sweep_map_images:
            from replication_faster_rcnn_tpu.data import make_dataset
            from replication_faster_rcnn_tpu.eval import Evaluator

            ev = Evaluator(cfg, model)
            eval_ds = make_dataset(cfg.data, args.eval_split)
            eval_fn = lambda v: ev.evaluate(  # noqa: E731
                v,
                eval_ds,
                batch_size=cfg.train.batch_size,
                max_images=args.sweep_map_images,
            )["mAP"]
        artifact = sweep(model, variables, artifact, batches, cfg, eval_fn)

    path = args.output or quant.default_artifact_path(cfg, args.workdir)
    quant.save_artifact(path, artifact, config_hash=config_hash(cfg))
    print(
        json.dumps(
            {
                "artifact": path,
                "groups": sorted(artifact["groups"]),
                "plan": artifact["plan"],
                "sensitivity": {
                    g: rec
                    for g, rec in artifact.get("sensitivity", {}).items()
                },
                "calib": artifact["calib"],
            },
            indent=2,
        )
    )
    return 0


def cmd_warmup(args) -> int:
    """AOT-compile the train (and optionally eval) programs for a config
    without touching data or parameters, into the persistent compile
    cache, so a later real run (same config/mesh/jaxlib) starts with every
    program already compiled (train/warmup.py)."""
    _apply_device(args.device)
    import json

    from replication_faster_rcnn_tpu.telemetry import spans as tspans
    from replication_faster_rcnn_tpu.train.warmup import (
        place_compile_cache,
        warmup_compile,
    )

    cfg = _build_config(args)
    cfg.require_detector("warmup")
    cache_path = place_compile_cache(cfg.compile.cache_dir)
    tracer = None
    if args.telemetry:
        import os

        os.makedirs(args.telemetry, exist_ok=True)
        tracer = tspans.SpanTracer(
            os.path.join(args.telemetry, "trace.json"),
            max_events=cfg.telemetry.trace_max_events,
        )
        tspans.set_tracer(tracer)
    try:
        times = warmup_compile(
            cfg,
            include_eval=not args.train_only,
            include_serving=args.serving,
        )
    finally:
        if tracer is not None:
            tracer.flush()
    print(
        json.dumps(
            {"compile_seconds": times, "compile_cache": cache_path}, indent=2
        )
    )
    return 0


def cmd_predict(args) -> int:
    _apply_device(args.device)
    import json
    import os

    from replication_faster_rcnn_tpu.eval.predict import (
        draw_detections,
        predict_images,
    )
    from replication_faster_rcnn_tpu.train.trainer import load_eval_variables

    cfg = _build_config(args)
    cfg.require_detector("predict")
    model, variables = load_eval_variables(cfg, args.workdir, args.checkpoint_step)
    paths = list(args.image)
    # all paths go through the serving engine as one submission wave, so
    # same-bucket images share micro-batched dispatches
    dets = predict_images(cfg, model, variables, paths, args.score_thresh)
    if len(paths) == 1:
        print(json.dumps(dets[0], indent=2))
    else:
        print(json.dumps(dict(zip(paths, dets)), indent=2))
    if args.output:
        if len(paths) == 1:
            draw_detections(paths[0], dets[0], args.output)
            print(f"annotated image written to {args.output}")
        else:
            root, ext = os.path.splitext(args.output)
            for i, (path, d) in enumerate(zip(paths, dets)):
                out = f"{root}.{i}{ext or '.jpg'}"
                draw_detections(path, d, out)
                print(f"annotated image written to {out}")
    return 0


def cmd_serve(args) -> int:
    """Bucketed AOT serving (serving/): compile every (resolution x
    batch) bucket program at startup, hold the inference params resident
    on device, and serve HTTP requests through the continuous
    micro-batching engine."""
    with _threadsan_session(getattr(args, "threadsan", False)):
        return _cmd_serve_impl(args)


def _replica_trace_rank(replica_id: str) -> int:
    """Stable nonzero rank for a replica's trace file name. The
    telemetry report merges DIR/trace.json (the fleet front writes it —
    rank 0) with every DIR/trace.rankN.json sibling, so replicas
    sharing the front's DIR need a small stable N >= 1: the digits of
    the conventional r<K> ids shifted by one, else a crc of the id."""
    import re as _re
    import zlib

    m = _re.search(r"(\d+)$", replica_id)
    if m:
        return int(m.group(1)) + 1
    return zlib.crc32(replica_id.encode()) % 9000 + 1000


def _cmd_serve_impl(args) -> int:
    _apply_device(args.device)
    import contextlib
    import dataclasses as _dc
    import json

    from replication_faster_rcnn_tpu.serving.engine import InferenceEngine
    from replication_faster_rcnn_tpu.serving.server import make_server
    from replication_faster_rcnn_tpu.train.trainer import load_eval_variables
    from replication_faster_rcnn_tpu.train.warmup import place_compile_cache

    cfg = _build_config(args)
    cfg.require_detector("serve")
    serving = cfg.serving
    if args.max_delay_ms is not None:
        serving = _dc.replace(serving, max_delay_ms=args.max_delay_ms)
    if args.bucket_batch_sizes:
        serving = _dc.replace(
            serving,
            batch_sizes=tuple(
                int(b) for b in args.bucket_batch_sizes.split(",")
            ),
        )
    if args.resolutions:
        serving = _dc.replace(
            serving,
            resolutions=tuple(
                tuple(int(x) for x in r.split("x"))
                for r in args.resolutions.split(",")
            ),
        )
    if args.params_dtype:
        serving = _dc.replace(serving, params_dtype=args.params_dtype)
    if args.request_timeout_s is not None:
        serving = _dc.replace(serving, request_timeout_s=args.request_timeout_s)
    if args.adaptive_delay:
        serving = _dc.replace(serving, adaptive_delay=True)
    cfg = cfg.replace(serving=serving)
    if cfg.debug.chaos_spec:
        from replication_faster_rcnn_tpu.faultlib import failpoints

        failpoints.configure(cfg.debug.chaos_spec)
    place_compile_cache(cfg.compile.cache_dir)
    tracer = None
    if args.telemetry:
        import os

        from replication_faster_rcnn_tpu.telemetry import spans as tspans

        os.makedirs(args.telemetry, exist_ok=True)
        rank = (
            _replica_trace_rank(args.replica_id) if args.replica_id else None
        )
        name = f"trace.rank{rank}.json" if rank else "trace.json"
        tracer = tspans.SpanTracer(
            os.path.join(args.telemetry, name),
            rank=rank,
            max_events=cfg.telemetry.trace_max_events,
        )
        tspans.set_tracer(tracer)
    model, variables = load_eval_variables(cfg, args.workdir, args.checkpoint_step)
    artifact_path = None
    if cfg.serving.params_dtype == "int8":
        # resolve the sidecar next to the served checkpoint; the engine
        # raises QuantArtifactError (naming `frcnn quantize`) if missing
        from replication_faster_rcnn_tpu.quant import default_artifact_path

        artifact_path = default_artifact_path(cfg, args.workdir)
    engine = InferenceEngine(
        cfg,
        model,
        variables,
        warmup=True,
        artifact_path=artifact_path,
        model_version=(
            str(args.checkpoint_step)
            if args.checkpoint_step is not None
            else "0"
        ),
    )
    stack = contextlib.ExitStack()
    if args.strict or cfg.debug.strict:
        from replication_faster_rcnn_tpu.analysis.strict import StrictHarness

        engine.strict = StrictHarness(
            warmup_dispatches=cfg.debug.strict_warmup
        )
        stack.enter_context(engine.strict.session())
    print(
        json.dumps(
            {
                "buckets": [list(b) for b in engine.buckets],
                "batch_sizes": list(engine.batch_sizes),
                "max_delay_ms": cfg.serving.max_delay_ms,
                "params_dtype": cfg.serving.params_dtype,
                "params_bytes": engine.params_bytes,
                "compile_seconds": engine.compile_seconds,
                "model_version": engine.model_version,
                "strict": engine.strict is not None,
            },
            indent=2,
        )
    )
    def _swap_handler(version: str):
        # POST /swap: load the requested checkpoint step from this
        # replica's workdir and hot-swap the engine. The engine stages +
        # validates the new buffer before flipping, so a bad version
        # errors here and serving continues on the current one.
        prior = engine.model_version
        _, new_vars = load_eval_variables(cfg, args.workdir, int(version))
        engine.swap_params(new_vars, version)
        return prior

    server = make_server(
        engine,
        args.host,
        args.port,
        score_thresh=args.score_thresh,
        replica_id=args.replica_id,
        swap_handler=_swap_handler if args.workdir else None,
    )
    host, port = server.server_address[:2]
    print(
        f"serving on http://{host}:{port}/ "
        "(POST /predict {\"paths\": [...]}, GET /healthz, GET /stats)",
        flush=True,
    )
    # graceful drain on SIGTERM: advertise draining in /healthz first so
    # a fleet router's prober pulls this replica out of rotation, hold
    # the listener open for fleet.drain_grace_s (in-flight + newly routed
    # requests still complete), then stop ACCEPTING (server.shutdown must
    # run off the serve_forever thread or it deadlocks); the finally
    # block below closes the listener and drains the engine
    import signal
    import threading
    import time as _time

    grace_s = cfg.fleet.drain_grace_s if args.replica_id else 0.0

    def _drain(signum, frame):  # noqa: ARG001 - signal signature
        print(
            f"SIGTERM: draining (grace {grace_s}s, then stop accepting)...",
            file=sys.stderr,
        )
        server.draining = True

        def _stop() -> None:
            if grace_s > 0:
                _time.sleep(grace_s)
            server.shutdown()

        threading.Thread(target=_stop, daemon=True).start()

    prev_term = signal.signal(signal.SIGTERM, _drain)
    with stack:
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            signal.signal(signal.SIGTERM, prev_term)
            server.server_close()
            engine.close()
            if tracer is not None:
                tracer.flush()
    return 0


def cmd_fleet(args) -> int:
    """Self-healing multi-replica serving front (serving/fleet/): a
    health-checked registry probes every `frcnn serve` replica's
    /healthz on a lease, and the router consistent-hashes requests over
    the live rotation with per-replica circuit breakers, failover
    re-dispatch, p99-hedged retries, a content-hash result cache, and
    canary/shadow traffic splits. Pure host-side routing — no jax, no
    model; the replicas own the compute."""
    with _threadsan_session(getattr(args, "threadsan", False)):
        return _cmd_fleet_impl(args)


def _cmd_fleet_impl(args) -> int:
    import dataclasses as _dc
    import json
    import os

    from replication_faster_rcnn_tpu.config import FleetConfig
    from replication_faster_rcnn_tpu.serving import fleet as fleet_mod

    if not args.replica:
        print("fleet: need at least one --replica URL", file=sys.stderr)
        return 2
    overrides = {
        k: v
        for k, v in {
            "probe_interval_s": args.probe_interval_s,
            "lease_timeout_s": args.lease_timeout_s,
            "breaker_threshold": args.breaker_threshold,
            "max_attempts": args.max_attempts,
            "request_timeout_s": args.request_timeout_s,
            "cache_entries": args.cache_entries,
            "canary_fraction": args.canary_fraction,
        }.items()
        if v is not None
    }
    if args.no_hedge:
        overrides["hedge"] = False
    fleet_cfg = _dc.replace(FleetConfig(), **overrides)
    if args.chaos_spec:
        from replication_faster_rcnn_tpu.faultlib import failpoints

        failpoints.configure(args.chaos_spec)

    tracer = None
    if args.telemetry:
        from replication_faster_rcnn_tpu.config import TelemetryConfig
        from replication_faster_rcnn_tpu.telemetry import spans as tspans

        os.makedirs(args.telemetry, exist_ok=True)
        tracer = tspans.SpanTracer(
            os.path.join(args.telemetry, "trace.json"),
            max_events=TelemetryConfig().trace_max_events,
        )
        tspans.set_tracer(tracer)

    registry = fleet_mod.ReplicaRegistry(fleet_cfg)
    for url in args.replica:
        registry.add(url, fleet_mod.HTTPReplicaClient(url, url))
    for url in args.canary or []:
        registry.add(url, fleet_mod.HTTPReplicaClient(url, url), role="canary")
    for url in args.shadow or []:
        registry.add(url, fleet_mod.HTTPReplicaClient(url, url), role="shadow")
    router = fleet_mod.FleetRouter(registry, fleet_cfg)
    prober = fleet_mod.Prober(registry, fleet_cfg.probe_interval_s).start()
    server = fleet_mod.make_fleet_server(router, args.host, args.port)
    host, port = server.server_address[:2]
    print(
        json.dumps(
            {
                "replicas": list(args.replica),
                "canaries": list(args.canary or []),
                "shadows": list(args.shadow or []),
                "hedge": fleet_cfg.hedge,
                "probe_interval_s": fleet_cfg.probe_interval_s,
                "lease_timeout_s": fleet_cfg.lease_timeout_s,
            },
            indent=2,
        )
    )
    print(
        f"fleet router on http://{host}:{port}/ "
        "(POST /predict {\"paths\": [...]}, GET /healthz, GET /stats)",
        flush=True,
    )
    # same drain discipline as the replicas: /healthz says draining
    # first, the listener keeps answering for the grace window, then the
    # accept loop stops and the prober/hedge pool are joined
    import signal
    import threading
    import time as _time

    def _drain(signum, frame):  # noqa: ARG001 - signal signature
        print(
            f"SIGTERM: draining fleet front "
            f"(grace {fleet_cfg.drain_grace_s}s)...",
            file=sys.stderr,
        )
        server.draining = True

        def _stop() -> None:
            if fleet_cfg.drain_grace_s > 0:
                _time.sleep(fleet_cfg.drain_grace_s)
            server.shutdown()

        threading.Thread(target=_stop, daemon=True).start()

    prev_term = signal.signal(signal.SIGTERM, _drain)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        signal.signal(signal.SIGTERM, prev_term)
        server.server_close()
        prober.stop()
        router.close()
        if args.telemetry:
            os.makedirs(args.telemetry, exist_ok=True)
            path = os.path.join(args.telemetry, "fleet.jsonl")
            with open(path, "a") as fh:
                fh.write(json.dumps(router.snapshot()) + "\n")
            print(f"fleet telemetry appended to {path}", file=sys.stderr)
            if tracer is not None:
                tracer.flush()
    return 0


def cmd_chaos(args) -> int:
    """Chaos acceptance harness (faultlib/chaos.py): a tiny seeded fault
    schedule exercised against the REAL loader / orbax checkpoint +
    manifest / micro-batcher machinery, asserting the recovery invariants
    (skip-and-substitute, verified-restore walk-back, worker survival)
    and that two runs under the same seed log the identical fault
    sequence. Exit 0 = all invariants held."""
    if not args.smoke:
        print("chaos: pass --smoke (the only implemented mode)", file=sys.stderr)
        return 2
    import json
    import shutil
    import tempfile

    from replication_faster_rcnn_tpu.faultlib import chaos

    workdir = args.workdir
    cleanup = workdir is None
    if workdir is None:
        workdir = tempfile.mkdtemp(prefix="frcnn-chaos-")
    try:
        result = chaos.run_smoke(workdir, seed=args.seed)
    except chaos.ChaosSmokeError as e:
        print(f"chaos smoke FAILED: {e}", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(result, indent=2))
    else:
        print(
            f"chaos smoke ok: seed={result['seed']} "
            f"injected_events={result['injected_events']} "
            f"elapsed_s={result['elapsed_s']}"
        )
        for leg, detail in result["legs"].items():
            print(f"  {leg}: {detail}")
    if cleanup:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


def cmd_rollout(args) -> int:
    """Rolling weight rollout control plane (serving/rollout/): discover
    checkpoint versions the trainer published to WORKDIR/manifests/
    (feed.jsonl + manifest scan), validate eligibility BEFORE any
    replica drains (manifest CRC fields, topology, config hash, int8
    quant sidecar), then drive a rolling fleet upgrade over --replica
    URLs: hold/drain one replica, POST /swap, rejoin-gate at the new
    version, canary-gate the first swapped replica on burn-rate +
    shadow-diff windows, promote the wave or roll it back first-class."""
    import dataclasses as _dc
    import json
    import os
    import time

    from replication_faster_rcnn_tpu.config import get_config
    from replication_faster_rcnn_tpu.serving import fleet as fleet_mod
    from replication_faster_rcnn_tpu.serving.rollout import (
        RolloutController,
        RolloutWatcher,
        VersionFeed,
    )

    cfg = get_config(args.config)
    if args.probe_interval_s is not None:
        cfg = cfg.replace(
            fleet=_dc.replace(
                cfg.fleet, probe_interval_s=args.probe_interval_s
            )
        )
    if args.poll_interval_s is not None:
        cfg = cfg.replace(
            rollout=_dc.replace(
                cfg.rollout, poll_interval_s=args.poll_interval_s
            )
        )
    if args.chaos_spec:
        from replication_faster_rcnn_tpu.faultlib import failpoints

        failpoints.configure(args.chaos_spec)
    feed = VersionFeed(
        args.workdir, config=None if args.no_config_checks else cfg
    )

    if args.validate_only:
        verdicts = [feed.validate(step) for step in feed.poll()]
        print(
            json.dumps(
                {
                    "workdir": feed.workdir,
                    "versions": [
                        {
                            "step": v.step,
                            "eligible": v.eligible,
                            "reasons": v.reasons,
                        }
                        for v in verdicts
                    ],
                },
                indent=2,
            )
        )
        return 0

    if not args.replica:
        print("rollout: need at least one --replica URL", file=sys.stderr)
        return 2
    registry = fleet_mod.ReplicaRegistry(cfg.fleet)
    for url in args.replica:
        registry.add(url, fleet_mod.HTTPReplicaClient(url, url))
    router = fleet_mod.FleetRouter(registry, cfg.fleet)
    prober = fleet_mod.Prober(registry, cfg.fleet.probe_interval_s).start()
    controller = RolloutController(registry, router, cfg, feed=feed)
    try:
        if args.watch:
            log_path = os.path.join(feed.workdir, "rollout.jsonl")
            watcher = RolloutWatcher(feed, controller, log_path=log_path)
            watcher.start()
            print(
                f"watching {feed.workdir} every "
                f"{cfg.rollout.poll_interval_s}s for eligible versions "
                f"(wave log: {log_path}); ctrl-c to stop",
                flush=True,
            )
            try:
                while True:
                    time.sleep(60)
            except KeyboardInterrupt:
                pass
            finally:
                watcher.stop()
            return 0
        # one-shot wave (--once is the default mode)
        if args.step is not None:
            result = controller.rollout(str(args.step))
        else:
            verdict = feed.latest_eligible()
            if verdict is None:
                print(
                    "rollout: no eligible version published under "
                    f"{feed.workdir} (try --validate-only for reasons)",
                    file=sys.stderr,
                )
                return 1
            result = controller.rollout(verdict.version, verdict=verdict)
        print(json.dumps(result.to_dict(), indent=2))
        return 0 if result.outcome in ("promoted", "noop") else 1
    finally:
        prober.stop()
        router.close()


def cmd_viz(args) -> int:
    """Visual sanity artifacts (reference `utils/anchors.py:64-77` anchor
    plot and `utils/data_loader.py:119-134` gt overlay, as a real command)."""
    _apply_device(args.device)
    cfg = _build_config(args)
    cfg.require_detector("viz")
    from replication_faster_rcnn_tpu.utils import viz

    if args.what == "anchors":
        viz.draw_anchor_centers(cfg, args.output)
    else:  # sample
        from replication_faster_rcnn_tpu.data.loader import make_dataset

        ds = make_dataset(cfg.data, args.split)
        viz.draw_gt_overlay(ds[args.index], cfg, args.output)
    print(f"{args.what} visualization written to {args.output}")
    return 0


def cmd_trace_summary(args) -> int:
    """Op-level time table from a captured profiler trace (the dir passed
    to --profile). Pure host-side parsing — no jax import, so it never
    takes the chip from a process that holds it."""
    import json

    from replication_faster_rcnn_tpu.utils.xplane import (
        find_xplane_files,
        format_table,
        op_table,
    )

    if not find_xplane_files(args.trace_dir):
        print(f"no *.xplane.pb under {args.trace_dir}", file=sys.stderr)
        return 1
    rows = op_table(args.trace_dir, plane_filter=args.plane, top=args.top)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"trace_dir": args.trace_dir, "ops": rows}, f, indent=2)
        print(f"op table written to {args.json}")
    print(format_table(rows))
    return 0


def cmd_check(args) -> int:
    """Static lint gate over the package (or explicit paths): jaxlint's
    jit-hygiene rules JX001-JX007, threadlint's host-concurrency rules
    TL001-TL006, obslint's unified-metrics contract OB001, and
    shardlint's sharding & collective-cost rules SL001-SL006 (over the
    committed fingerprint bank — pass bank JSON paths to lint one
    bank), resolved against the shared analysis/baseline.toml. No
    lowering or compilation anywhere — fast enough to gate every PR.
    Exits nonzero on any unsuppressed finding or stale waiver; --rules
    narrows to a comma-separated subset (an analyzer with no selected
    rule is skipped entirely)."""
    import json

    from replication_faster_rcnn_tpu.analysis import (
        jaxlint,
        obslint,
        shardlint,
        threadlint,
    )

    analyzers = [
        ("jaxlint", jaxlint),
        ("threadlint", threadlint),
        ("obslint", obslint),
        ("shardlint", shardlint),
    ]
    selected = None
    if getattr(args, "rules", None):
        selected = {r.strip().upper() for r in args.rules.split(",") if r.strip()}
        known = (
            set(jaxlint.RULES)
            | set(threadlint.RULES)
            | set(obslint.RULES)
            | set(shardlint.RULES)
        )
        unknown = selected - known
        if unknown:
            print(
                f"unknown rule(s): {', '.join(sorted(unknown))} "
                f"(known: {', '.join(sorted(known))})",
                file=sys.stderr,
            )
            return 2
        analyzers = [
            (name, mod) for name, mod in analyzers if selected & set(mod.RULES)
        ]

    def run(mod):
        if args.paths:
            return mod.lint_paths(args.paths, baseline=args.baseline)
        if args.baseline is not None:
            return mod.lint_package(baseline=args.baseline)
        return mod.lint_package()

    def keep(rule):
        return selected is None or rule in selected

    results = [(name, run(mod), mod.RULES) for name, mod in analyzers]
    findings = [
        f for _, r, _ in results for f in r.findings if keep(f.rule)
    ]
    stale = [
        w for _, r, _ in results for w in r.stale_waivers if keep(w.rule)
    ]
    suppressed = [
        (f, reason)
        for _, r, _ in results
        for f, reason in r.suppressed
        if keep(f.rule)
    ]
    excluded_count = sum(
        1 for _, r, _ in results for f in r.excluded if keep(f.rule)
    )
    rules = {
        rule: desc
        for _, _, mod_rules in results
        for rule, desc in mod_rules.items()
        if keep(rule)
    }
    if args.json:
        payload = {
            "rules": rules,
            "findings": [f.to_dict() for f in findings],
            "suppressed": [
                {**f.to_dict(), "reason": reason} for f, reason in suppressed
            ],
            "excluded_count": excluded_count,
            "stale_waivers": [
                dataclasses.asdict(w) for _, r, _ in results
                for w in r.stale_waivers if keep(w.rule)
            ],
            "ok": not findings and not stale,
        }
        print(json.dumps(payload, indent=2))
    else:
        for f in findings:
            print(f)
        baseline_name = args.baseline or "analysis/baseline.toml"
        for w in stale:
            print(
                f"stale waiver ({baseline_name}:{w.line}): {w.rule} "
                f"{w.path} [{w.func}] matched nothing — the violation it "
                f"suppressed (reason: {w.reason!r}) is gone; delete the "
                f"[[waiver]] entry at line {w.line}"
            )
        if args.verbose:
            for f, reason in suppressed:
                print(f"waived: {f}\n    reason: {reason}")
        names = "+".join(name for name, _, _ in results) or "no analyzers"
        print(
            f"{names}: {len(findings)} finding(s), "
            f"{len(suppressed)} waived, "
            f"{excluded_count} excluded, "
            f"{len(stale)} stale waiver(s) "
            f"({len(rules)} rules)"
        )
    return 1 if (findings or stale) else 0


def cmd_audit(args) -> int:
    """HLO program auditor (analysis/hlolint.py): AOT-lower every
    registered (feed × K) train program + eval for the audited config,
    enforce the compiled-artifact contracts HX001-HX004 (donation
    aliasing, dtype, collectives, memory budget), the SL005 comm-byte
    budget (static wire-byte estimate vs analysis.comm_budget_bytes and
    the banked value), and compare against the committed fingerprint
    bank (HX005/HX006). The third static gate next to `frcnn check`
    (AST + bank) and --strict (runtime); exits nonzero on any contract
    violation or unexplained fingerprint drift."""
    import json
    import os

    # the audit's spmd programs need a multi-device mesh; on a CPU-only
    # host ask XLA for virtual devices BEFORE jax initializes (matches
    # the test tier's 8-device topology; no-op when jax is already up)
    if "jax" not in sys.modules and args.device in ("auto", "cpu"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count=8".strip()
            )
    _apply_device(args.device)

    from replication_faster_rcnn_tpu.analysis import hlolint
    from replication_faster_rcnn_tpu.config import get_config

    cfg = hlolint.audit_config() if args.config == "ci" else get_config(args.config)
    programs = [p for p in args.programs.split(",") if p] if args.programs else None
    result = hlolint.run_audit(
        cfg,
        programs=programs,
        update=args.update,
        fingerprint_dir=args.fingerprint_dir,
        hbm_budget_bytes=args.hbm_budget,
    )
    if args.json:
        print(json.dumps(result.to_dict(), indent=2))
    else:
        for v in result.violations:
            print(v)
        verdict = (
            "re-banked" if result.updated and result.ok
            else ("ok" if result.ok else "FAILED")
        )
        print(
            f"audit: {len(result.programs)} program(s), "
            f"{len(result.violations)} violation(s) -> {verdict} "
            f"(bank: {result.bank_file})"
        )
    return 1 if result.violations else 0


def cmd_telemetry(args) -> int:
    """Phase-time + train-health report from a --telemetry run dir. Pure
    host-side parsing (telemetry/report.py) — no jax import, runnable on
    a laptop holding only the artifacts.
    --trace-id narrows to one request's cross-process hop timeline from
    the merged trace (router + replica spans under one trace id)."""
    import json

    from replication_faster_rcnn_tpu.telemetry.report import (
        TRACE_FILE,
        format_report,
        format_trace_timeline,
        load_trace_events,
        rank_variants,
        summarize_run,
        trace_timeline,
    )

    if getattr(args, "trace_id", None):
        events = []
        for _rank, path in rank_variants(args.run_dir, TRACE_FILE):
            events.extend(load_trace_events(path))
        timeline = trace_timeline(events, args.trace_id)
        if timeline is None:
            print(
                f"no spans for trace id {args.trace_id!r} under "
                f"{args.run_dir}",
                file=sys.stderr,
            )
            return 1
        if args.json:
            with open(args.json, "w") as f:
                json.dump(timeline, f, indent=2)
            print(f"timeline written to {args.json}")
        print(format_trace_timeline(timeline))
        return 0

    summary = summarize_run(args.run_dir)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"summary written to {args.json}")
    print(format_report(summary))
    return 0 if summary["artifacts"] else 1


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="replication_faster_rcnn_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_train = sub.add_parser("train", help="train a detector")
    _add_common(p_train)
    p_train.add_argument("--workdir", default="checkpoints")
    p_train.add_argument("--steps", type=int, default=0,
                         help="run exactly N steps instead of the epoch loop")
    p_train.add_argument("--log-every", type=int, default=10)
    p_train.add_argument("--resume", action="store_true")
    p_train.add_argument("--pretrained-backbone", default=None,
                         help="torch resnet .pth to graft (reference readme.md:10-12)")
    p_train.add_argument("--eval-every", type=int, default=None,
                         help="run val mAP every N epochs (0 = never)")
    p_train.add_argument("--profile", default=None, metavar="DIR",
                         help="jax.profiler trace of the training loop")
    p_train.add_argument("--telemetry", default=None, metavar="DIR",
                         help="write run telemetry here: trace.json "
                              "(Chrome-trace spans), metrics.jsonl (step "
                              "metrics + train-health scalars), "
                              "watchdog.jsonl + progress.json (stall "
                              "watchdog); summarize with the 'telemetry' "
                              "subcommand")
    p_train.add_argument("--stall-timeout", type=float, default=300.0,
                         help="seconds without step progress before the "
                              "telemetry watchdog records a stall snapshot "
                              "(needs --telemetry)")
    p_train.add_argument("--on-crash-checkpoint", action="store_true",
                         help="on an unhandled training crash, best-effort "
                              "save a checkpoint (manifest kind 'crash') "
                              "before re-raising; SIGTERM/SIGINT preemption "
                              "always emergency-saves and exits 75")
    p_train.add_argument("--debug-nans", action="store_true",
                         help="enable jax_debug_nans (every jit output "
                              "checked; errors pinpoint the emitting op)")
    p_train.add_argument("--elastic", action="store_true",
                         help="elastic fleet mode: this process becomes a "
                              "per-host supervisor that spawns the real "
                              "training child and survives rank loss — a "
                              "lost rank's lease expiry re-forms the fleet "
                              "at the surviving world size, resuming from "
                              "the last verified checkpoint INSIDE the "
                              "same epoch (parallel/elastic.py; pair with "
                              "--checkpoint-every-steps to bound rollback)")
    p_train.set_defaults(fn=cmd_train)

    p_eval = sub.add_parser("eval", help="evaluate mAP")
    _add_common(p_eval)
    p_eval.add_argument("--workdir", default="checkpoints")
    p_eval.add_argument("--split", default="val")
    p_eval.add_argument("--checkpoint-step", type=int, default=None)
    p_eval.add_argument("--max-images", type=int, default=None)
    p_eval.add_argument("--per-class", action="store_true",
                        help="print the per-class AP table")
    p_eval.add_argument("--iou-thresh", type=float, default=None,
                        help="matching IoU for VOC mAP (default 0.5)")
    p_eval.add_argument("--use-07-metric", action="store_true",
                        help="VOC2007 11-point AP instead of area-under-PR")
    p_eval.add_argument("--metric", default=None, choices=[None, "voc", "coco"],
                        help="voc: mAP@iou-thresh; coco: mAP@[.50:.95]")
    p_eval.add_argument("--tta-hflip", action="store_true",
                        help="flip test-time augmentation: mirrored second "
                             "forward, candidates merged before NMS "
                             "(~2x eval compute for a small mAP gain)")
    p_eval.set_defaults(fn=cmd_eval)

    p_warm = sub.add_parser(
        "warmup",
        help="AOT-compile the train/eval programs for a config into the "
             "persistent compile cache, so later real-run startups are warm",
    )
    _add_common(p_warm)
    p_warm.add_argument("--train-only", action="store_true",
                        help="skip the eval inference program")
    p_warm.add_argument("--serving", action="store_true",
                        help="also AOT-compile the serving engine's bucket "
                             "matrix (serving.resolutions x batch_sizes), "
                             "so a later 'serve' start is warm")
    p_warm.add_argument("--telemetry", default=None, metavar="DIR",
                        help="write compile/* spans to DIR/trace.json")
    p_warm.set_defaults(fn=cmd_warmup)

    p_pred = sub.add_parser("predict", help="detect objects in images")
    _add_common(p_pred)
    p_pred.add_argument("--image", required=True, nargs="+", metavar="PATH",
                        help="image path(s); multiple paths route through "
                             "the serving engine as one micro-batched wave")
    p_pred.add_argument("--workdir", default="checkpoints")
    p_pred.add_argument("--checkpoint-step", type=int, default=None)
    p_pred.add_argument("--score-thresh", type=float, default=0.5)
    p_pred.add_argument("--output", default=None,
                        help="write the image with boxes drawn to this path "
                             "(with multiple inputs: PATH.0.ext, PATH.1.ext, "
                             "...)")
    p_pred.set_defaults(fn=cmd_predict)

    p_serve = sub.add_parser(
        "serve",
        help="bucketed AOT inference serving: pre-compile every "
             "(resolution x batch) bucket program, keep params resident "
             "on device, micro-batch concurrent HTTP requests "
             "(POST /predict)",
    )
    _add_common(p_serve)
    p_serve.add_argument("--workdir", default="checkpoints")
    p_serve.add_argument("--checkpoint-step", type=int, default=None)
    p_serve.add_argument("--host", default="127.0.0.1")
    p_serve.add_argument("--port", type=int, default=8008,
                         help="TCP port (0 = pick a free one)")
    p_serve.add_argument("--score-thresh", type=float, default=0.5)
    p_serve.add_argument("--max-delay-ms", type=float, default=None,
                         help="micro-batch deadline: max ms a request "
                              "waits for batch-mates before a partial "
                              "flush (serving.max_delay_ms)")
    p_serve.add_argument("--bucket-batch-sizes", default=None, metavar="N,M",
                         help="compiled batch sizes per bucket, e.g. '1,8' "
                              "(serving.batch_sizes)")
    p_serve.add_argument("--resolutions", default=None, metavar="HxW,HxW",
                         help="bucket resolutions, e.g. '300x300,600x600' "
                              "(default: image_size and its half)")
    p_serve.add_argument("--params-dtype", default=None,
                         choices=[None, "float32", "bfloat16", "int8"],
                         help="resident inference param dtype "
                              "(serving.params_dtype). float32: the "
                              "checkpoint as-is; bfloat16: halves HBM "
                              "residency (flax casts to compute dtype "
                              "per-layer regardless); int8: ~4x smaller "
                              "residency — quantized weights + scales "
                              "stay device-resident and every bucket "
                              "dispatches its serve_*__int8 program. "
                              "int8 REQUIRES the calibration sidecar "
                              "written by `frcnn quantize` (per-channel "
                              "scales + per-layer int8/bf16 plan) next "
                              "to the checkpoint; startup fails with an "
                              "actionable error without it")
    p_serve.add_argument("--request-timeout-s", type=float, default=None,
                         help="per-request deadline "
                              "(serving.request_timeout_s): handler waits "
                              "time out to 504 and queued entries past "
                              "deadline are dropped at flush time, never "
                              "dispatched (0 = no deadline)")
    p_serve.add_argument("--adaptive-delay", action="store_true",
                         help="SLO-driven micro-batch deadlines "
                              "(serving.adaptive_delay): adapt per-bucket "
                              "max_delay_ms from observed queue-wait p99 "
                              "with bounded multiplicative steps inside "
                              "[delay_floor_ms, delay_ceiling_ms]")
    p_serve.add_argument("--replica-id", default=None, metavar="ID",
                         help="name this replica in /healthz for fleet "
                              "membership; also enables the SIGTERM "
                              "drain-grace window (fleet.drain_grace_s: "
                              "advertise draining, keep serving, then stop "
                              "accepting) so the fleet router rotates the "
                              "replica out without dropped traffic")
    p_serve.add_argument("--telemetry", default=None, metavar="DIR",
                         help="write request hop spans (serve/request, "
                              "serve/queue_wait, serve/dispatch) to a "
                              "Chrome-trace file in DIR: trace.json, or "
                              "trace.rankN.json when --replica-id is set "
                              "so replicas can share the fleet front's DIR "
                              "and `frcnn telemetry DIR --trace-id X` "
                              "merges them into one timeline")
    p_serve.set_defaults(fn=cmd_serve)

    p_quant = sub.add_parser(
        "quantize",
        help="PTQ calibration for int8 serving: per-channel weight "
             "scales + activation ranges from a small calibration "
             "sweep, optional per-layer sensitivity sweep (--sweep) "
             "emitting an int8-vs-bf16 plan, written as a CRC-checked "
             "sidecar artifact `frcnn serve --params-dtype int8` loads",
    )
    _add_common(p_quant)
    p_quant.add_argument("--workdir", default="checkpoints")
    p_quant.add_argument("--checkpoint-step", type=int, default=None)
    p_quant.add_argument("--output", default=None, metavar="PATH",
                         help="artifact path (default: quant.artifact if "
                              "set, else WORKDIR/quant_artifact.json)")
    p_quant.add_argument("--split", default="train",
                         help="dataset split calibration batches are "
                              "drawn from (index order, deterministic)")
    p_quant.add_argument("--eval-split", default="val",
                         help="split for the --sweep-map-images mini "
                              "eval")
    p_quant.add_argument("--calib-batches", type=int, default=None,
                         help="calibration batches (quant.calib_batches)")
    p_quant.add_argument("--calib-batch-size", type=int, default=None,
                         help="images per calibration batch "
                              "(quant.calib_batch_size)")
    p_quant.add_argument("--synthetic-calib", action="store_true",
                         help="force synthetic calibration images even "
                              "for a real dataset config")
    p_quant.add_argument("--sweep", action="store_true",
                         help="per-layer-group sensitivity sweep "
                              "(arXiv:1806.00370): quantize one group at "
                              "a time, measure response-reconstruction "
                              "error (and mAP drop with "
                              "--sweep-map-images); groups crossing the "
                              "quant.sensitivity_* budgets fall back to "
                              "bf16 in the plan")
    p_quant.add_argument("--sweep-map-images", type=int, default=None,
                         metavar="N",
                         help="with --sweep: also measure each group's "
                              "mAP delta on N eval images")
    p_quant.set_defaults(fn=cmd_quantize)

    p_fleet = sub.add_parser(
        "fleet",
        help="self-healing multi-replica serving front: health-checked "
             "replica registry (lease-staleness probes), consistent-hash "
             "routing with a content-hash result cache, per-replica "
             "circuit breakers, failover, p99-hedged retries, canary + "
             "shadow traffic (serving/fleet/)",
    )
    p_fleet.add_argument("--replica", action="append", metavar="URL",
                         help="serving replica base URL (repeatable), e.g. "
                              "http://127.0.0.1:8008 — start each with "
                              "`frcnn serve --replica-id ...`")
    p_fleet.add_argument("--canary", action="append", metavar="URL",
                         help="canary replica URL: a deterministic "
                              "fleet.canary_fraction slice of the "
                              "content-hash space tries it first")
    p_fleet.add_argument("--shadow", action="append", metavar="URL",
                         help="shadow replica URL: mirrored traffic, "
                              "responses diffed (never returned)")
    p_fleet.add_argument("--host", default="127.0.0.1")
    p_fleet.add_argument("--port", type=int, default=8010,
                         help="TCP port (0 = pick a free one)")
    p_fleet.add_argument("--probe-interval-s", type=float, default=None,
                         help="/healthz probe cadence per replica "
                              "(fleet.probe_interval_s)")
    p_fleet.add_argument("--lease-timeout-s", type=float, default=None,
                         help="probe-staleness horizon before a replica "
                              "is declared dead (fleet.lease_timeout_s)")
    p_fleet.add_argument("--breaker-threshold", type=int, default=None,
                         help="consecutive dispatch failures that open a "
                              "replica's circuit breaker "
                              "(fleet.breaker_threshold)")
    p_fleet.add_argument("--max-attempts", type=int, default=None,
                         help="primary + failover attempts per request "
                              "(fleet.max_attempts)")
    p_fleet.add_argument("--request-timeout-s", type=float, default=None,
                         help="per-attempt replica call deadline "
                              "(fleet.request_timeout_s)")
    p_fleet.add_argument("--cache-entries", type=int, default=None,
                         help="content-hash result cache size, 0 disables "
                              "(fleet.cache_entries)")
    p_fleet.add_argument("--canary-fraction", type=float, default=None,
                         help="fraction of the content-hash space routed "
                              "to the canary first (fleet.canary_fraction)")
    p_fleet.add_argument("--no-hedge", action="store_true",
                         help="disable hedged retries (fleet.hedge=False): "
                              "dispatch becomes strictly sequential "
                              "failover")
    p_fleet.add_argument("--chaos-spec", default=None, metavar="SPEC",
                         help="arm failpoints (site:kind:prob:seed[:arg]) "
                              "— the fleet sites are router.dispatch and "
                              "router.probe, plus http.handler on the "
                              "front itself")
    p_fleet.add_argument("--threadsan", action="store_true",
                         help="record runtime thread-interaction traces "
                              "for the router/prober threads "
                              "(analysis/threadsan.py)")
    p_fleet.add_argument("--telemetry", default=None, metavar="DIR",
                         help="append a final router/registry snapshot to "
                              "DIR/fleet.jsonl on shutdown (read by "
                              "`frcnn telemetry`) and write the router's "
                              "request/attempt spans to DIR/trace.json — "
                              "point replicas' `serve --telemetry` at the "
                              "same DIR for the merged cross-process "
                              "`--trace-id` timeline")
    p_fleet.set_defaults(fn=cmd_fleet)

    p_chaos = sub.add_parser(
        "chaos",
        help="deterministic fault-injection acceptance harness "
             "(faultlib): seeded failpoint schedule against the real "
             "loader/checkpoint/micro-batcher machinery; asserts the "
             "fault-tolerance invariants hold and that the same seed "
             "reproduces the identical fault sequence",
    )
    p_chaos.add_argument("--smoke", action="store_true",
                         help="tiny seeded schedule on synthetic data "
                              "(finishes in seconds); currently the only "
                              "mode, so required")
    p_chaos.add_argument("--seed", type=int, default=0,
                         help="schedule seed; the run is a pure function "
                              "of it")
    p_chaos.add_argument("--workdir", default=None, metavar="DIR",
                         help="scratch dir for checkpoint legs (default: "
                              "a fresh temp dir, removed on success)")
    p_chaos.add_argument("--json", action="store_true",
                         help="print the full result record as JSON")
    p_chaos.set_defaults(fn=cmd_chaos)

    p_roll = sub.add_parser(
        "rollout",
        help="rolling weight rollout: validate checkpoint versions "
             "published to WORKDIR/manifests/ (pre-drain eligibility "
             "gate), then drive a rolling fleet upgrade over --replica "
             "URLs — drain → hot-swap (POST /swap) → rejoin-gate → "
             "gated canary promote, with first-class rollback "
             "(serving/rollout/)",
    )
    p_roll.add_argument("--workdir", required=True, metavar="DIR",
                        help="trainer workdir whose manifests/ feed is "
                             "the version source (the replicas must "
                             "serve from the same workdir so POST /swap "
                             "can load the step)")
    p_roll.add_argument("--config", default="voc_resnet18",
                        help="preset the fleet serves (eligibility "
                             "checks the manifest config hash and, for "
                             "int8, the quant sidecar against it)")
    p_roll.add_argument("--no-config-checks", action="store_true",
                        help="skip the config-hash and int8-sidecar "
                             "eligibility checks (manifest integrity + "
                             "topology still judged)")
    p_roll.add_argument("--replica", action="append", metavar="URL",
                        help="serving replica base URL (repeatable); "
                             "each must run `frcnn serve --replica-id "
                             "... --workdir ...` so /swap is enabled")
    p_roll.add_argument("--validate-only", action="store_true",
                        help="print every published version's "
                             "eligibility verdict as JSON and exit — no "
                             "replica is touched")
    p_roll.add_argument("--once", action="store_true",
                        help="run exactly one rollout wave to the "
                             "newest eligible version (or --step) and "
                             "exit; this is the default mode")
    p_roll.add_argument("--step", type=int, default=None,
                        help="with --once: roll to this checkpoint step "
                             "instead of the newest eligible one (still "
                             "validated first)")
    p_roll.add_argument("--watch", action="store_true",
                        help="poll the manifest feed forever "
                             "(rollout.poll_interval_s) and run a wave "
                             "per newly eligible version; wave results "
                             "append to WORKDIR/rollout.jsonl")
    p_roll.add_argument("--probe-interval-s", type=float, default=None,
                        help="/healthz probe cadence "
                             "(fleet.probe_interval_s)")
    p_roll.add_argument("--poll-interval-s", type=float, default=None,
                        help="manifest feed poll cadence for --watch "
                             "(rollout.poll_interval_s)")
    p_roll.add_argument("--chaos-spec", default=None, metavar="SPEC",
                        help="arm failpoints (site:kind:prob:seed[:arg])"
                             " — the rollout sites are rollout.swap "
                             "(before each per-replica swap RPC) and "
                             "rollout.promote (at the promote decision)")
    p_roll.set_defaults(fn=cmd_rollout)

    p_viz = sub.add_parser("viz", help="visual sanity artifacts "
                                       "(anchor centers / gt overlay)")
    _add_common(p_viz)
    p_viz.add_argument("what", choices=["anchors", "sample"])
    p_viz.add_argument("--output", required=True)
    p_viz.add_argument("--split", default="train")
    p_viz.add_argument("--index", type=int, default=0,
                       help="dataset sample index (what=sample)")
    p_viz.set_defaults(fn=cmd_viz)

    p_trace = sub.add_parser(
        "trace-summary",
        help="per-op time table from a --profile trace dir (no TF needed)",
    )
    p_trace.add_argument("trace_dir")
    p_trace.add_argument("--top", type=int, default=25)
    p_trace.add_argument("--plane", default=None,
                         help="substring filter on the plane name "
                              "(default: device planes, else all)")
    p_trace.add_argument("--json", default=None, metavar="PATH",
                         help="also write the table as JSON")
    p_trace.set_defaults(fn=cmd_trace_summary)

    p_tel = sub.add_parser(
        "telemetry",
        help="phase-time + train-health report from a --telemetry run dir",
    )
    p_tel.add_argument("run_dir")
    p_tel.add_argument("--json", default=None, metavar="PATH",
                       help="also write the summary as JSON")
    p_tel.add_argument("--trace-id", default=None, metavar="HEX32",
                       help="print one request's hop timeline (queue-wait/"
                            "compute/network per hop) from the merged trace "
                            "instead of the full report")
    p_tel.set_defaults(fn=cmd_telemetry)

    p_check = sub.add_parser(
        "check",
        help="static lint gate: jit-hygiene (jaxlint JX001-JX007) + "
             "host-concurrency contracts (threadlint TL001-TL006) + "
             "unified-metrics contract (obslint OB001) + sharding/"
             "collective-cost contracts over the fingerprint bank "
             "(shardlint SL001-SL006) against the committed suppression "
             "baseline; exits nonzero on any unsuppressed finding",
    )
    p_check.add_argument("paths", nargs="*",
                         help="files to lint (default: the whole package)")
    p_check.add_argument("--rules", default=None, metavar="R1,R2,...",
                         help="run/report only these rules (e.g. "
                              "'TL001,SL005'; default: all JX + TL + OB "
                              "+ SL rules)")
    p_check.add_argument("--baseline", default=None, metavar="TOML",
                         help="suppression file (default: the committed "
                              "analysis/baseline.toml; pass /dev/null to "
                              "see raw findings)")
    p_check.add_argument("--json", action="store_true",
                         help="machine-readable findings on stdout")
    p_check.add_argument("-v", "--verbose", action="store_true",
                         help="also print waived findings with reasons")
    p_check.set_defaults(fn=cmd_check)

    p_audit = sub.add_parser(
        "audit",
        help="HLO program auditor (rules HX001-HX006 + SL005 comm-byte "
             "budget): donation/dtype/collective/memory contracts + "
             "fingerprint drift over the compiled (feed x K) programs; "
             "third gate next to 'check' and --strict",
    )
    p_audit.add_argument("--config", default="ci",
                         help="'ci' = the small audited-matrix config "
                              "(default; what the committed fingerprints "
                              "were banked with), or any preset name")
    p_audit.add_argument("--device", default="auto",
                         choices=["auto", "tpu", "cpu"],
                         help="JAX backend (cpu/auto gets 8 virtual "
                              "devices for the spmd programs)")
    p_audit.add_argument("--programs", default=None, metavar="A,B,...",
                         help="comma-separated subset of program names to "
                              "lower (default: the full feed x K matrix + "
                              "eval)")
    p_audit.add_argument("--update", action="store_true",
                         help="re-bank: write the collected fingerprints "
                              "to the bank instead of failing on drift")
    p_audit.add_argument("--fingerprint-dir", default=None, metavar="DIR",
                         help="override analysis.fingerprint_dir (default: "
                              "the committed analysis/fingerprints/)")
    p_audit.add_argument("--hbm-budget", type=int, default=None,
                         metavar="BYTES",
                         help="override analysis.hbm_budget_bytes for the "
                              "HX004 peak-memory gate")
    p_audit.add_argument("--json", action="store_true",
                         help="machine-readable result on stdout")
    p_audit.set_defaults(fn=cmd_audit)

    args = parser.parse_args(argv)
    # the elastic supervisor rewrites the EXACT argv this process was
    # invoked with into each generation's child argv
    args._argv = list(argv) if argv is not None else list(sys.argv[1:])
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
