"""Is data-parallel training really on every chip of the host?

    python benchmarks/multichip_on_chip.py     # needs >= 2 TPU chips; one process

`__graft_entry__.dryrun_multichip` proves the sharded programs on VIRTUAL
CPU devices. This is its counterpart on real chips, at the full width of
`voc_resnet18` (600x600, batch 16 per chip), through the real `Trainer`:

* the mesh holds one distinct device per chip;
* a staged batch leaf has one shard per chip, each on its own device;
* a parameter leaf is addressable on every chip after a step;
* `memory_stats()["bytes_in_use"]` is non-zero on every chip;
* the default `train.backend="auto"` (jit auto-partitioning) and
  `"spmd"` (hand-placed `shard_map` collectives) agree on the step-0
  loss of the same batch and initial state, and a few more spmd steps
  stay finite.

The agreement is judged at float32 compute, to the tolerance
`__graft_entry__._assert_losses_agree` uses (1e-3 relative): there the two
backends differ only in reduction order. At the preset's bfloat16 the
figure is recorded but not judged — rounding flips a discrete selection
now and then (one sampled ROI turning positive moves the loss by ~1e-3;
PR 21 saw 1.2e-3 at bfloat16 against 1.6e-5 at float32 on a four-chip v5e
host, and 8e-4 against 3e-7 on a CPU mesh), which says nothing about
placement.

    python benchmarks/multichip_on_chip.py [bfloat16] [float32]   # default: both

Exits non-zero if any check fails, and before doing anything on a host
with fewer than two accelerator chips. A correctness record — it times
nothing. Writes chiprun_out/multichip_on_chip.json; the last stdout line
is ``{"ok": true, "device": {...}, ...}``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

PER_CHIP_BATCH = 16
SPMD_STEPS = 3
DTYPES = ("bfloat16", "float32")  # the preset's compute dtype, then the judge


def _leaf_placement(leaf) -> dict:
    shards = leaf.addressable_shards
    return {
        "shape": list(leaf.shape),
        "sharding": str(leaf.sharding.spec),
        "shards": len(shards),
        "shard_shape": list(shards[0].data.shape),
        "devices": sorted(str(s.device) for s in shards),
    }


def _one_backend(cfg, backend: str, batch, workdir: str, steps: int):
    """(record, host batch) of ``steps`` steps on one backend; the first
    call draws the batch every later call reuses."""
    import jax

    from replication_faster_rcnn_tpu.train import Trainer

    cfg = cfg.replace(train=dataclasses.replace(cfg.train, backend=backend))
    trainer = Trainer(cfg, workdir=workdir)
    if batch is None:
        batch = next(iter(trainer.loader))
    staged = trainer._stage_batch(batch)
    rec = {
        "backend": backend,
        "mesh_shape": dict(trainer.mesh.shape),
        "mesh_devices": [str(d) for d in trainer.mesh.devices.flat],
        "batch_image": _leaf_placement(staged["image"]),
    }
    losses = []
    for i in range(steps):
        metrics = jax.device_get(trainer.train_one_batch(staged=staged))
        losses.append(float(metrics["loss"]))
        if i == 0:  # where a step-0 difference comes from
            rec["step0_metrics"] = {
                k: float(v) for k, v in metrics.items()
                if k.endswith("_loss") or k.startswith("n_pos")
            }
    rec["losses"] = losses
    rec["param_leaf"] = _leaf_placement(
        jax.tree_util.tree_leaves(trainer.state.params)[0]
    )
    rec["bytes_in_use"] = {
        str(d): int(d.memory_stats()["bytes_in_use"]) for d in jax.devices()
    }
    return rec, batch


def main() -> int:
    import jax

    import __graft_entry__ as graft
    from replication_faster_rcnn_tpu.telemetry.mfu import require_accelerator
    from replication_faster_rcnn_tpu.config import get_config

    device = require_accelerator("multichip_on_chip")
    n = device["count"]
    if n < 2:
        raise SystemExit(
            f"multichip_on_chip: needs >= 2 chips, found {n}; nothing was run"
        )
    dtypes = [d for d in DTYPES if d in sys.argv[1:]] or list(DTYPES)
    base = get_config("voc_resnet18")
    base = base.replace(
        data=dataclasses.replace(base.data, dataset="synthetic"),
        train=dataclasses.replace(base.train, batch_size=PER_CHIP_BATCH * n),
    )
    workdir = tempfile.mkdtemp(prefix="multichip_ckpt_")
    problems, runs, batch = [], {}, None
    try:
        for dtype in dtypes:
            cfg = base.replace(
                model=dataclasses.replace(base.model, compute_dtype=dtype)
            )
            auto, batch = _one_backend(
                cfg, "auto", batch, f"{workdir}/{dtype}_auto", 1
            )
            spmd, _ = _one_backend(
                cfg, "spmd", batch, f"{workdir}/{dtype}_spmd", SPMD_STEPS
            )
            runs[dtype] = {"auto": auto, "spmd": spmd}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    agreement = {}
    for dtype, pair in runs.items():
        for rec in pair.values():
            tag = f"{dtype}/{rec['backend']}"
            if len(set(rec["mesh_devices"])) != n:
                problems.append(f"{tag}: mesh holds {rec['mesh_devices']}")
            for leaf in ("batch_image", "param_leaf"):
                if len(set(rec[leaf]["devices"])) != n:
                    problems.append(f"{tag}: {leaf} on {rec[leaf]['devices']}")
            if rec["batch_image"]["shard_shape"][0] != PER_CHIP_BATCH:
                problems.append(f"{tag}: batch shard {rec['batch_image']}")
            idle = [d for d, b in rec["bytes_in_use"].items() if b <= 0]
            if idle:
                problems.append(f"{tag}: no memory in use on {idle}")
            if not all(math.isfinite(x) for x in rec["losses"]):
                problems.append(f"{tag}: non-finite loss {rec['losses']}")
        a, b = pair["auto"]["losses"][0], pair["spmd"]["losses"][0]
        agreement[dtype] = {
            "auto": a, "spmd": b, "rel_delta": abs(a - b) / max(1.0, abs(a)),
            "judged": dtype == "float32",
        }
        if dtype == "float32":
            try:
                graft._assert_losses_agree(a, b)
            except ValueError as e:
                problems.append(str(e))

    out = {
        "ok": not problems, "device": device, "jax": jax.__version__,
        "per_chip_batch": PER_CHIP_BATCH, "step0_loss": agreement,
        "problems": problems, "runs": runs,
    }
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "multichip_on_chip.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in (
        "ok", "device", "step0_loss", "problems",
    )}))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
