"""The readings the limits of `correct` are set from, taken on the chip at
a cell's own size, many seeds in one process, each judged as a run judges.

    python3 perf/readings.py --workload <cell> --seeds 12 --control-seeds 3

For each seed: fresh weights and batches from the seed (the data set made by
the configuration's data module), the trainer's first steps through
`train_one_batch`, and the loader's rows against the feed's reference (the LOWER readings: the program against the plain references).
On the first `--control-seeds` seeds also the UPPER readings, each put in
the program's place: the control (the reference in float8; the feed's
reference with pixels rounded to uint8), the half-batch fault (the
reference on the first half of each batch, the mean taken over the rest)
and a nearest-pixel resize in the feed. Every set of numbers goes through
`compare.judge` with the cell's own limits: the program has to come out
correct on every seed, each control and fault not correct on every seed,
and the exit code says whether they did. One JSON line a seed to
`chiprun_out/`, a summary at the end. Not run by the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

FIRST_SEED = 2_100_000_000
OUT = os.path.join(ROOT, "chiprun_out")
EXIT_LIMITS_DO_NOT_HOLD = 4
KINDS = ("program", "control_float8", "half_batch", "control_feed_uint8", "feed_nearest_pixel")


def fresh_loader(trainer, root_dir: str, seed: int):
    """A loader over another data set, with the arguments Trainer.__init__
    gives its own."""
    import dataclasses

    from replication_faster_rcnn_tpu.data.loader import DataLoader, make_dataset

    cfg = trainer.config
    data = dataclasses.replace(cfg.data, root_dir=root_dir)
    return DataLoader(
        make_dataset(data, "train"), batch_size=cfg.train.batch_size, shuffle=True, seed=seed,
        prefetch=data.loader_prefetch, num_workers=data.loader_workers,
        worker_mode=data.loader_mode, augment_hflip=data.augment_hflip,
        augment_scale=data.augment_scale, augment_scale_device=data.augment_scale_device,
        augment_device=data.augment_device, augment_translate=data.augment_translate,
        cache_ram=data.loader_cache_ram,
    )


def reset_state(trainer, init_stats):
    import jax
    import jax.numpy as jnp

    sh = trainer._state_shardings
    trainer.state = trainer.state.replace(
        step=jax.device_put(jnp.zeros((), jnp.int32), sh.step),
        batch_stats=jax.device_put(init_stats, sh.batch_stats),
    )


def judged(nums, limits):
    """The numbers with their limits, and what a run would have said."""
    from perf import compare

    ok = compare.judge(nums, limits)
    return {"correct": ok, "numbers": {k: {"value": v["value"], "limit": v["limit"]} for k, v in nums.items()}}


def take(cell, devices, seeds, control_seeds: int, scratch: str, out_path: str, say=print) -> bool:
    """Read and judge `seeds`; True where every limit held what it has to."""
    import jax

    from perf import compare, harness

    limits, sizes = cell.config["limits"], cell.config["sizes"]
    ref = harness.load_reference(cell)
    feed_ref = harness.load_feed_reference(cell)
    get_config, Trainer = harness.package_program()
    kit = os.path.join(scratch, "data")
    cfg = harness.program_config(cell, seeds[0], feed_ref.overrides(kit), os.path.join(ROOT, ".compile_cache"), get_config)
    batch = cfg.train.batch_size
    mix = dict(cell.mix, n_images=harness.WARM_STEPS * batch)
    feed_ref.make(kit, seeds[0], mix)
    trainer = Trainer(cfg, workdir=os.path.join(scratch, "workdir"), devices=devices)
    init_stats = jax.device_get(trainer.state.batch_stats)
    sz = ref.Sizes(sizes, batch)
    rows = []
    jitted = {}
    with open(out_path, "w") as out:
        for n, seed in enumerate(seeds):
            t0 = time.time()
            feed_ref.make(kit, seed, mix)
            host = list(fresh_loader(trainer, kit, seed % (2**31 - 1)))[: harness.WARM_STEPS]
            harness.inject_weights(trainer, ref, sz, seed)
            reset_state(trainer, init_stats)
            feed = iter([{"batch": b} for b in host])
            program = harness.first_steps(trainer, feed, lambda kw: trainer.train_one_batch(**kw), ref.LOSS_PARTS)
            three = host[: harness.CHECK_STEPS]
            reference = harness.reference_numbers(ref, sz, seed, three, jitted=jitted)
            sound_feed = feed_ref.numbers(kit, three, sizes)
            row = {"seed": seed, "losses_program": program["losses"], "losses_reference": reference["losses"]}
            row["program"] = judged({**compare.numbers(program, reference), **sound_feed}, limits)
            if n < control_seeds:
                # each put in the program's place, the rest of the run sound
                for name, kw in (("control_float8", {"precision": "float8"}), ("half_batch", {"rows": batch // 2})):
                    try:
                        other = harness.reference_numbers(ref, sz, seed, three, jitted=jitted, **kw)
                        row[name] = judged({**compare.numbers(other, reference), **sound_feed}, limits)
                    except Exception as e:  # a control that crashes has failed, and sets no upper reading
                        row[name] = {"correct": False, "error": f"{type(e).__name__}: {e}"[:300]}
                for name, how in (("control_feed_uint8", "uint8"), ("feed_nearest_pixel", "nearest")):
                    planted = feed_ref.numbers(kit, three, sizes, in_place=how)
                    row[name] = judged({**compare.numbers(reference, reference), **planted}, limits)
            row["seconds"] = round(time.time() - t0, 1)
            rows.append(row)
            out.write(json.dumps(row) + "\n")
            out.flush()
            say(json.dumps({"seed": seed, "seconds": row["seconds"],
                            **{k: v["correct"] for k, v in row.items() if isinstance(v, dict)}}), flush=True)
    say("SUMMARY", cell.name, "limits", json.dumps(limits))
    holds = True
    for kind in KINDS:
        have = [r[kind] for r in rows if kind in r]
        if not have:
            continue
        correct = sum(1 for h in have if h["correct"])
        want = len(have) if kind == "program" else 0
        holds = holds and correct == want
        say(f" {kind}: correct on {correct} of {len(have)} seeds (has to be {want})")
        for m in limits:
            vals = sorted(h["numbers"][m]["value"] for h in have if "numbers" in h)
            if vals:
                say(f"   {m:22s} min={vals[0]:.5g} median={vals[len(vals) // 2]:.5g} max={vals[-1]:.5g} limit={limits[m]}")
    return holds


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()

    import jax

    from perf import harness, manifest

    cell = manifest.Cell(ROOT, os.path.join(ROOT, "BENCHMARK.json"), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("no TPU: no readings", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    os.makedirs(OUT, exist_ok=True)
    holds = take(
        cell, devices[: cell.chips], [FIRST_SEED + 7919 * i for i in range(args.seeds)], args.control_seeds,
        os.path.join(ROOT, ".perf_scratch", "readings"), os.path.join(OUT, f"readings_{args.workload}.jsonl"),
    )
    return 0 if holds else EXIT_LIMITS_DO_NOT_HOLD


if __name__ == "__main__":
    sys.exit(main())
