"""COCO-format trained-mAP evidence + the mini gate (VERDICT r3 #7).

Two modes share one synthetic-COCO writer (real COCO-2017 disk layout:
JPEG images + ``annotations/instances_{split}2017.json`` with sparse
category ids, exercising the id remap of `data/coco.py`):

* **full** (default, slow, manual): `cli train --dataset coco` smoke
  leg + a resnet18@128 Trainer run to convergence, reporting the COCO
  metric sweep (mAP@[.50:.95] + mAP@0.5) on train and disjoint val
  splits. Writes benchmarks/coco_overfit_result.json.

* **--mini** (the gated A/B): three small resnet18@64 legs on CPU —
  single-scale random sampling, 2-bucket multi-scale
  (data.train_resolutions), and topk_iou region sampling
  (arXiv:1702.02138) — each writing an mAP@[.50:.95] curve to
  benchmarks/coco_overfit_curve_mini_{leg}.jsonl, plus the ISSUE-17
  quantization A/B on the single leg's checkpoint (f32 eval vs the
  PTQ int8 serving compute; the drop must stay within
  QUANT_MAP_DROP_PT mAP points). Before any training
  the run must pass (a) hand-computed COCO-evaluator oracles *exactly*
  and (b) a per-bucket-program presence check against the committed
  fingerprint bank. The result is compared against the banked record
  (benchmarks/records/coco_overfit_mini_cpu.json): any leg under the
  pinned mAP floor, or 2-bucket throughput more than 15% below the
  single-bucket leg, exits 1. ``--mini --update`` re-banks.

The model is resnet18 at small pixels for CPU tractability — the point
is the COCO data path + COCO metric + the three config axes end to end,
not the backbone (the coco_vgg16/coco_resnet50 presets share every
component downstream of the trunk). Reference: the original COCO
py-faster-rcnn recipe the reference documents but never implements
(`/root/reference/reference/train_frcnn.prototxt:410-417`).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# sparse ids with gaps, like real COCO's 1..90-with-holes
CAT_IDS = [3, 7, 11, 18, 25, 44, 61, 88]

RECORDS_DIR = os.path.join(REPO, "benchmarks", "records")
RECORD_PATH = os.path.join(RECORDS_DIR, "coco_overfit_mini_cpu.json")
BANK_PATH = os.path.join(
    REPO, "replication_faster_rcnn_tpu", "analysis", "fingerprints",
    "ci_cpu.json",
)
# 2-bucket leg must keep >= 85% of the single-bucket leg's images/sec
# (a >15% multi-scale dispatch overhead fails the run)
THROUGHPUT_RATIO_FLOOR = 0.85
MINI_BUCKETS = ((32, 32), (64, 64))
# int8 PTQ may cost at most this many mAP@[.50:.95] points vs the same
# checkpoint's f32 eval (ISSUE-17 acceptance)
QUANT_MAP_DROP_PT = 0.3


def write_synthetic_coco(root: str, split: str, n_images: int,
                         image_size: int, seed: int) -> None:
    """Planted-rectangle JPEGs + COCO instances JSON under ``root``.

    Same object statistics as data/synthetic.py (class-colored blocks on
    dark noise, 1..4 objects of h/8..h/2 extent) so a detector can
    genuinely fit the data; bbox is COCO xywh in original pixel coords.
    """
    import numpy as np
    from PIL import Image

    img_dir = os.path.join(root, split)
    os.makedirs(img_dir, exist_ok=True)
    os.makedirs(os.path.join(root, "annotations"), exist_ok=True)

    images, annotations = [], []
    ann_id = 1
    h = w = image_size
    for idx in range(n_images):
        rng = np.random.RandomState(seed + idx)
        arr = (rng.uniform(0.0, 0.15, (h, w, 3)) * 255).astype("uint8")
        n_obj = rng.randint(1, 5)
        for _ in range(n_obj):
            bh = rng.randint(h // 8, h // 2)
            bw = rng.randint(w // 8, w // 2)
            r1 = rng.randint(0, h - bh)
            c1 = rng.randint(0, w - bw)
            k = rng.randint(0, len(CAT_IDS))
            cls = k + 1  # contiguous label the model sees after remap
            color = 0.3 + 0.7 * np.asarray(
                [(cls % 3) / 2.0, ((cls // 3) % 3) / 2.0,
                 ((cls // 9) % 3) / 2.0]
            )
            block = color * 255 + rng.uniform(-12, 12, (bh, bw, 3))
            arr[r1:r1 + bh, c1:c1 + bw] = np.clip(block, 0, 255).astype(
                "uint8"
            )
            annotations.append({
                "id": ann_id,
                "image_id": idx,
                "category_id": CAT_IDS[k],
                "bbox": [float(c1), float(r1), float(bw), float(bh)],
                "area": float(bw * bh),
                "iscrowd": 0,
            })
            ann_id += 1
        fname = f"{idx:012d}.jpg"
        Image.fromarray(arr).save(
            os.path.join(img_dir, fname), quality=95
        )
        images.append(
            {"id": idx, "file_name": fname, "height": h, "width": w}
        )

    ann = {
        "images": images,
        "annotations": annotations,
        "categories": [
            {"id": cid, "name": f"thing{cid}"} for cid in CAT_IDS
        ],
    }
    with open(
        os.path.join(root, "annotations", f"instances_{split}.json"), "w"
    ) as f:
        json.dump(ann, f)


# ---------------------------------------------------------------- mini gate


def oracle_check() -> list:
    """Hand-computed COCO-protocol oracles the evaluator must hit
    *exactly* (same cases tests/test_eval.py pins; re-run here so a
    gate run can never bank numbers from a drifted evaluator). Returns
    failure strings; empty means exact."""
    import numpy as np

    from replication_faster_rcnn_tpu.eval.coco_eval import coco_summary

    def det(boxes, scores, classes):
        return {"boxes": np.asarray(boxes, float).reshape(-1, 4),
                "scores": np.asarray(scores, float),
                "classes": np.asarray(classes, int)}

    def gt(boxes, labels, ignore=None):
        g = {"boxes": np.asarray(boxes, float).reshape(-1, 4),
             "labels": np.asarray(labels, int)}
        if ignore is not None:
            g["ignore"] = np.asarray(ignore, bool)
        return g

    fails = []

    def expect(name, got, want):
        if not math.isclose(got, want, rel_tol=0, abs_tol=1e-12):
            fails.append(f"oracle {name}: got {got!r}, want {want!r}")

    # 1) perfect detections: a small gt (area 100) and a medium gt
    # (area 1600) each matched exactly -> every aggregate 1.0 except the
    # empty large slice (-1.0)
    r = coco_summary(
        [det([[0, 0, 10, 10]], [0.9], [1]),
         det([[0, 0, 40, 40]], [0.8], [2])],
        [gt([[0, 0, 10, 10]], [1]), gt([[0, 0, 40, 40]], [2])],
        num_classes=3,
    )
    for k, want in [("mAP", 1.0), ("AP50", 1.0), ("AP75", 1.0),
                    ("AP_small", 1.0), ("AP_medium", 1.0),
                    ("AP_large", -1.0)]:
        expect(f"perfect/{k}", float(r[k]), want)

    # 2) IoU exactly 0.6: matches thresholds {.50,.55,.60} only -> 3/10
    r = coco_summary(
        [det([[0, 0, 10, 6]], [0.9], [1])],
        [gt([[0, 0, 10, 10]], [1])],
        num_classes=2,
    )
    expect("iou0.6/mAP", float(r["mAP"]), 3.0 / 10.0)

    # 3) 101-point interpolation: TP(.9), FP(.8), TP(.7) over 2 gts ->
    # envelope 1.0 up to recall .5 (51 grid points), 2/3 after (50)
    r = coco_summary(
        [det([[0, 0, 10, 10], [50, 50, 60, 60], [20, 20, 30, 30]],
             [0.9, 0.8, 0.7], [1, 1, 1])],
        [gt([[0, 0, 10, 10], [20, 20, 30, 30]], [1, 1])],
        num_classes=2, iou_thresholds=[0.5],
    )
    expect("interp/mAP", float(r["mAP"]),
           (51 * 1.0 + 50 * (2.0 / 3.0)) / 101.0)

    # 4) an ignored gt absorbs exactly ONE detection (COCOeval, unlike
    # the VOC-devkit rule): second det on it is a plain FP, the real gt
    # stays unmatched -> AP 0
    r = coco_summary(
        [det([[0, 0, 10, 10], [0, 0, 10, 10]], [0.9, 0.8], [1, 1])],
        [gt([[0, 0, 10, 10], [50, 50, 60, 60]], [1, 1],
            ignore=[True, False])],
        num_classes=2,
    )
    expect("ignored-absorbs-one/mAP", float(r["mAP"]), 0.0)

    # 5) empty inputs -> -1.0 everywhere (JSON-safe no-gt convention)
    r = coco_summary([], [], num_classes=2)
    expect("empty/mAP", float(r["mAP"]), -1.0)
    return fails


def expected_bucket_programs() -> list:
    """The per-bucket train programs the audited config compiles —
    these must all be present in the committed fingerprint bank."""
    from replication_faster_rcnn_tpu.analysis.hlolint import (
        AUDIT_FEEDS, AUDIT_KS, audit_config,
    )
    from replication_faster_rcnn_tpu.train.warmup import (
        bucket_train_program_names,
    )

    return sorted(bucket_train_program_names(
        audit_config(), feeds=AUDIT_FEEDS, ks=AUDIT_KS
    ))


def bank_bucket_check(bank_path: str = BANK_PATH) -> list:
    """Failure strings for bucket programs missing from the committed
    fingerprint bank (empty when the bank covers multi-scale)."""
    if not os.path.exists(bank_path):
        return [f"fingerprint bank missing: {bank_path}"]
    with open(bank_path) as f:
        banked = set(json.load(f).get("programs", {}))
    return [
        f"bucket program not in fingerprint bank: {name}"
        for name in expected_bucket_programs() if name not in banked
    ]


def curve_throughput(curve_path: str) -> float:
    """Steady-state images/sec from a curve's per-epoch rows: median
    over epochs >= 2 (the first epochs pay compiles — the bucketed leg
    compiles one program per resolution as buckets first occur)."""
    import numpy as np

    rates = []
    with open(curve_path) as f:
        for line in f:
            row = json.loads(line)
            if "images_per_sec" in row and row.get("epoch", 0) >= 2:
                rates.append(row["images_per_sec"])
    return float(np.median(rates)) if rates else 0.0


def check_gate(record: dict, banked: dict) -> tuple:
    """Compare a fresh mini record against the banked one. Returns
    (fails, warns) string lists; any fail should exit 1. Pure on dicts
    so tests can drive it with synthetic records."""
    fails, warns = [], []
    if record.get("oracle_fails"):
        fails += [str(s) for s in record["oracle_fails"]]
    if record.get("missing_bucket_programs"):
        fails += [str(s) for s in record["missing_bucket_programs"]]

    floor = float(banked.get("map_floor", 0.0))
    for leg, res in record.get("legs", {}).items():
        if float(res.get("train_mAP", -1.0)) < floor:
            fails.append(
                f"leg {leg}: train mAP@[.50:.95] "
                f"{res.get('train_mAP'):.4f} under banked floor "
                f"{floor:.4f}"
            )

    quant = record.get("quant") or {}
    drop = quant.get("map_drop_pt")
    if drop is None:
        fails.append("record has no quantization mAP A/B (quant leg)")
    elif float(drop) > QUANT_MAP_DROP_PT:
        fails.append(
            f"int8 PTQ costs {float(drop):.3f} mAP points "
            f"(f32 {quant.get('f32_mAP'):.4f} -> int8 "
            f"{quant.get('int8_mAP'):.4f}); budget is "
            f"{QUANT_MAP_DROP_PT} pt"
        )

    legs = record.get("legs", {})
    single = float(legs.get("single", {}).get("images_per_sec", 0.0))
    buckets = float(legs.get("buckets", {}).get("images_per_sec", 0.0))
    if single > 0:
        ratio = buckets / single
        if ratio < THROUGHPUT_RATIO_FLOOR:
            fails.append(
                f"2-bucket throughput {buckets:.3f} img/s is "
                f"{ratio:.2f}x the single-bucket {single:.3f} img/s "
                f"(floor {THROUGHPUT_RATIO_FLOOR})"
            )
    else:
        fails.append("single leg has no throughput measurement")

    for leg, res in legs.items():
        old = banked.get("legs", {}).get(leg, {}).get("images_per_sec")
        new = res.get("images_per_sec")
        if old and new and new < 0.5 * old:
            warns.append(
                f"leg {leg}: {new:.3f} img/s is under half the banked "
                f"{old:.3f} img/s (timing only — not gated)"
            )
    return fails, warns


def _quant_leg(args) -> dict:
    """ISSUE-17 quantization A/B on the single leg's checkpoint: the
    f32 eval vs the quantized serving compute (PTQ calibration on the
    train split, the sensitivity sweep's per-group plan, then
    `quant/apply.py` reconstruction — dequantized weights + the
    QuantDense int8 head GEMMs — through the SAME Evaluator protocol).
    Gated: the mAP@[.50:.95] drop must stay within QUANT_MAP_DROP_PT."""
    from replication_faster_rcnn_tpu import quant
    from replication_faster_rcnn_tpu.data import make_dataset
    from replication_faster_rcnn_tpu.eval import Evaluator
    from replication_faster_rcnn_tpu.serving.engine import _plain_dicts
    from replication_faster_rcnn_tpu.train.trainer import load_eval_variables

    cfg = _mini_config(args)
    model, variables = load_eval_variables(
        cfg, os.path.join(args.workdir, "single")
    )
    variables = _plain_dicts(variables)
    train_ds = make_dataset(cfg.data, "train")
    ev = Evaluator(cfg, model)

    def eval_map(v) -> float:
        return float(ev.evaluate(v, train_ds, batch_size=args.batch)["mAP"])

    batches = quant.dataset_calibration_batches(
        train_ds, batches=cfg.quant.calib_batches,
        batch_size=cfg.quant.calib_batch_size,
    )
    artifact = quant.calibrate(model, variables, batches, cfg)
    artifact = quant.sweep(
        model, variables, artifact, batches, cfg, eval_fn=eval_map
    )
    infer_vars = quant.build_infer_variables(
        quant.quantize_variables(variables, artifact), cfg
    )
    f32_map = eval_map(variables)
    int8_map = eval_map(infer_vars)
    leg = {
        "f32_mAP": f32_map,
        "int8_mAP": int8_map,
        "map_drop_pt": round(100.0 * (f32_map - int8_map), 4),
        "plan": dict(artifact["plan"]),
        "recon_rel_err": {
            g: s["recon_rel_err"]
            for g, s in artifact.get("sensitivity", {}).items()
            if "recon_rel_err" in s
        },
    }
    print(f"leg quant: {json.dumps(leg)}", flush=True)
    return leg


def _mini_config(args, buckets=(), sampling="random"):
    """One mini leg's config: resnet18@64, num_classes=9, COCO metric;
    ``buckets`` sets data.train_resolutions, ``sampling`` the
    train.sampling_strategy axis."""
    import dataclasses

    from replication_faster_rcnn_tpu.config import (
        DataConfig, EvalConfig, MeshConfig, TrainConfig, get_config,
    )

    size = (args.image_size, args.image_size)
    base = get_config("voc_resnet18")
    return base.replace(
        # anchors 8..32 px on the stride-16 trunk, matching the planted
        # h/8..h/2 objects at 64 px (see map_overfit.py for the idiom)
        anchors=dataclasses.replace(
            base.anchors, scales=(0.5, 1.0, 2.0)
        ),
        model=dataclasses.replace(
            base.model, roi_op="align", compute_dtype="float32",
            num_classes=len(CAT_IDS) + 1,
        ),
        # n_sample=16 makes the head sampler genuinely selective: at
        # 64 px the candidate pool (~144 anchors pre-NMS) never fills
        # the default 128-roi budget, so random and topk_iou would keep
        # the SAME mask and the A/B legs would be bitwise identical.
        roi_targets=dataclasses.replace(base.roi_targets, n_sample=16),
        data=DataConfig(
            dataset="coco", root_dir=args.data_root, image_size=size,
            max_boxes=8, train_resolutions=tuple(buckets),
        ),
        eval=EvalConfig(metric="coco"),
        train=TrainConfig(
            batch_size=args.batch, n_epoch=args.epochs, lr=args.lr,
            eval_every_epochs=args.eval_every,
            checkpoint_every_epochs=max(args.epochs, 1),
            sampling_strategy=sampling, seed=0,
        ),
        mesh=MeshConfig(num_data=1),
    )


def _mini_leg(name: str, cfg, args) -> dict:
    """Train one leg from scratch, write its curve jsonl, return the
    leg record: final train-split mAP@[.50:.95] sweep + steady-state
    images/sec."""
    from replication_faster_rcnn_tpu.data import make_dataset
    from replication_faster_rcnn_tpu.eval import Evaluator
    from replication_faster_rcnn_tpu.train.trainer import Trainer

    workdir = os.path.join(args.workdir, name)
    if os.path.exists(workdir):
        shutil.rmtree(workdir)
    curve_path = os.path.join(
        REPO, "benchmarks", f"coco_overfit_curve_mini_{name}.jsonl"
    )
    if os.path.exists(curve_path):
        os.remove(curve_path)

    train_ds = make_dataset(cfg.data, "train")
    trainer = Trainer(cfg, workdir=workdir, dataset=train_ds)
    trainer.logger.jsonl_path = curve_path
    t0 = time.time()
    trainer.train(log_every=5)
    train_s = time.time() - t0

    variables = {
        "params": trainer.state.params,
        "batch_stats": trainer.state.batch_stats,
    }
    res = Evaluator(cfg, trainer.model).evaluate(
        variables, train_ds, batch_size=args.batch
    )
    leg = {
        "train_mAP": float(res["mAP"]),
        "train_AP50": float(res.get("AP50", float("nan"))),
        "train_AP75": float(res.get("AP75", float("nan"))),
        "images_per_sec": curve_throughput(curve_path),
        "train_seconds": round(train_s, 1),
        "curve": os.path.relpath(curve_path, REPO),
    }
    print(f"leg {name}: {json.dumps(leg)}", flush=True)
    return leg


def mini_main(args) -> int:
    """The gated mini A/B: oracle + bank preflight, three legs, record
    vs bank (or --update re-bank). Returns the process exit code."""
    oracle_fails = oracle_check()
    for s in oracle_fails:
        print(f"FAIL {s}", flush=True)
    if oracle_fails:
        # never train (let alone bank) on a drifted evaluator
        return 1
    print("evaluator oracles: exact", flush=True)

    missing = bank_bucket_check()
    for s in missing:
        print(f"FAIL {s}", flush=True)

    import jax

    jax.config.update("jax_platforms", "cpu")

    if os.path.exists(args.data_root):
        shutil.rmtree(args.data_root)
    write_synthetic_coco(
        args.data_root, "train2017", args.images, args.image_size, seed=0
    )
    write_synthetic_coco(
        args.data_root, "val2017", args.images, args.image_size,
        seed=1 << 20,
    )

    legs = {
        "single": _mini_leg("single", _mini_config(args), args),
        "buckets": _mini_leg(
            "buckets", _mini_config(args, buckets=MINI_BUCKETS), args
        ),
        "topk": _mini_leg(
            "topk", _mini_config(args, sampling="topk_iou"), args
        ),
    }
    quant_leg = _quant_leg(args)
    record = {
        "schema": 1,
        "config": "coco-format resnet18@64 mini A/B (num_classes=9): "
                  "single-scale random / 2-bucket multi-scale / "
                  "topk_iou sampling",
        "platform": jax.default_backend(),
        "measured": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "epochs": args.epochs,
        "images": args.images,
        "batch": args.batch,
        "lr": args.lr,
        "buckets": [list(b) for b in MINI_BUCKETS],
        "oracle_fails": oracle_fails,
        "bucket_programs": expected_bucket_programs(),
        "missing_bucket_programs": missing,
        "legs": legs,
        "quant": quant_leg,
    }

    if args.update:
        fails, _ = check_gate(record, {"map_floor": 0.0})
        if fails:
            for s in fails:
                print(f"FAIL {s}", flush=True)
            print("refusing to bank a failing record", flush=True)
            return 1
        # pin the floor at half the worst leg (CPU reruns jitter; the
        # floor catches a broken axis, not a slow machine)
        worst = min(leg["train_mAP"] for leg in legs.values())
        record["map_floor"] = round(0.5 * worst, 4)
        os.makedirs(RECORDS_DIR, exist_ok=True)
        with open(RECORD_PATH, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
        print(f"banked {RECORD_PATH} (map_floor={record['map_floor']})",
              flush=True)
        return 0

    if not os.path.exists(RECORD_PATH):
        print(f"FAIL no banked record at {RECORD_PATH} "
              "(run with --mini --update)", flush=True)
        return 1
    with open(RECORD_PATH) as f:
        banked = json.load(f)
    fails, warns = check_gate(record, banked)
    for s in warns:
        print(f"WARN {s}", flush=True)
    for s in fails:
        print(f"FAIL {s}", flush=True)
    if not fails:
        print("coco_overfit mini gate: OK", flush=True)
    return 1 if fails else 0


# ---------------------------------------------------------------- full mode


def full_main(args) -> None:
    for d in (args.data_root, args.workdir):
        if os.path.exists(d):
            shutil.rmtree(d)

    write_synthetic_coco(
        args.data_root, "train2017", args.images, args.image_size, seed=0
    )
    write_synthetic_coco(
        args.data_root, "val2017", args.val_images, args.image_size,
        seed=1 << 20,
    )

    # leg 1 — the user-facing surface: `cli train --dataset coco` must
    # read the on-disk COCO layout and run real jitted steps
    cli_leg = None
    if not args.skip_cli_leg:
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-m", "replication_faster_rcnn_tpu.cli",
             "train", "--dataset", "coco", "--data-root", args.data_root,
             "--steps", "2", "--image-size", str(args.image_size),
             "--batch-size", "2"],
            cwd=REPO, capture_output=True, text=True, timeout=1800,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if proc.returncode != 0:
            raise RuntimeError(f"cli train leg failed:\n{proc.stderr[-2000:]}")
        cli_leg = {"steps": 2, "seconds": round(time.time() - t0, 1),
                   "ok": True}
        print(f"cli-train-on-coco leg ok ({cli_leg['seconds']}s)")

    # leg 2 — full Trainer to convergence + COCO metric sweep.
    # CPU by design (resnet18@128 exists for CPU tractability, and what
    # this gate reports are mAP counts, not times): pin the CPU backend
    # before any device op. Safe here: no backend has been initialized
    # in-process yet (leg 1 is a subprocess).
    import dataclasses

    import jax

    jax.config.update("jax_platforms", "cpu")

    from replication_faster_rcnn_tpu.config import (
        DataConfig, EvalConfig, MeshConfig, TrainConfig, get_config,
    )
    from replication_faster_rcnn_tpu.data import make_dataset
    from replication_faster_rcnn_tpu.eval import Evaluator
    from replication_faster_rcnn_tpu.train.trainer import Trainer

    size = (args.image_size, args.image_size)
    base = get_config("voc_resnet18")
    cfg = base.replace(
        # (1,2,4) anchor scales: 16..64 px anchors matching the planted
        # h/8..h/2 objects at this small image size (see map_overfit.py)
        anchors=dataclasses.replace(base.anchors, scales=(1.0, 2.0, 4.0)),
        model=dataclasses.replace(
            base.model, roi_op="align", compute_dtype="float32",
            num_classes=len(CAT_IDS) + 1,
        ),
        data=DataConfig(dataset="coco", root_dir=args.data_root,
                        image_size=size, max_boxes=8,
                        augment_hflip=args.augment_hflip),
        eval=EvalConfig(metric="coco"),
        train=TrainConfig(
            batch_size=args.batch, n_epoch=args.epochs, lr=args.lr,
            eval_every_epochs=args.eval_every,
            checkpoint_every_epochs=max(args.epochs // 2, 1), seed=0,
        ),
        mesh=MeshConfig(num_data=1),
    )

    train_ds = make_dataset(cfg.data, "train")
    assert len(train_ds) == args.images
    trainer = Trainer(cfg, workdir=args.workdir, dataset=train_ds)
    t0 = time.time()
    trainer.train(log_every=5)
    train_s = time.time() - t0

    variables = {
        "params": trainer.state.params,
        "batch_stats": trainer.state.batch_stats,
    }
    evaluator = Evaluator(cfg, trainer.model)
    train_res = evaluator.evaluate(
        variables, train_ds, batch_size=args.batch
    )
    val_res = evaluator.evaluate(
        variables, make_dataset(cfg.data, "val"), batch_size=args.batch
    )

    result = {
        "metric": "coco mAP@[.50:.95]",
        "train_coco_mAP": float(train_res["mAP"]),
        "train_AP50": float(train_res.get("AP50", float("nan"))),
        "val_coco_mAP": float(val_res["mAP"]),
        "val_AP50": float(val_res.get("AP50", float("nan"))),
        "val_images": args.val_images,
        "cli_train_on_coco_leg": cli_leg,
        "config": "coco-format resnet18@128 (num_classes=9, sparse cat "
                  "ids remapped)",
        "epochs": args.epochs,
        "images": args.images,
        "batch": args.batch,
        "lr": args.lr,
        "train_seconds": round(train_s, 1),
        "backend": __import__("jax").default_backend(),
        "augment_hflip": args.augment_hflip,
    }
    out = os.path.join(
        REPO, "benchmarks",
        "coco_overfit_result_aug.json" if args.augment_hflip
        else "coco_overfit_result.json",
    )
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mini", action="store_true",
                    help="run the gated three-leg A/B instead of the "
                    "full convergence run")
    ap.add_argument("--update", action="store_true",
                    help="with --mini: re-bank "
                    "benchmarks/records/coco_overfit_mini_cpu.json")
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--images", type=int, default=None)
    ap.add_argument("--val-images", type=int, default=64)
    ap.add_argument("--image-size", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--eval-every", type=int, default=None)
    ap.add_argument("--data-root", default="/tmp/coco_synth")
    ap.add_argument("--workdir", default="/tmp/coco_overfit_ckpts")
    ap.add_argument("--skip-cli-leg", action="store_true")
    ap.add_argument("--augment-hflip", action="store_true",
                    help="train with the 50%% flip; results go to "
                    "coco_overfit_result_aug.json so the aug-off row is "
                    "kept for comparison (COCO-side counterpart of the "
                    "VOC evidence that flipped the preset default)")
    args = ap.parse_args()

    # mode-dependent defaults: the mini A/B is sized for a CPU gate run,
    # the full mode keeps the original convergence recipe
    mini_defaults = dict(epochs=30, images=8, image_size=64, batch=4,
                         lr=1e-3, eval_every=10)
    full_defaults = dict(epochs=30, images=32, image_size=128, batch=8,
                         lr=3e-4, eval_every=5)
    for k, v in (mini_defaults if args.mini else full_defaults).items():
        if getattr(args, k) is None:
            setattr(args, k, v)

    if args.mini:
        sys.exit(mini_main(args))
    full_main(args)


if __name__ == "__main__":
    main()
