"""Does the system still start on the chip? One process, two legs, ~3 min.

    python chip_smoke.py            # needs a TPU; exits non-zero without one

Drives the main path once through the entry points a user calls, at the
full width of the `voc_resnet18` preset (600x600, 12,996 anchors,
12000->600 proposals, 128 sampled ROIs, bfloat16 compute), with random
weights from the preset's seed:

* **train leg** — `cli.main(["train", "--config", "voc_resnet18",
  "--dataset", "synthetic", "--device", "tpu", "--batch-size", 16 x chips,
  "--steps", "8", "--log-every", "1", "--strict", ...])`: the real
  Trainer, loader, `shard_batch`, jitted step, logger and final
  checkpoint. Checked from its own records (metrics.jsonl, trace.json,
  the checkpoint manifest): rc 0, every loss finite, step-0 loss in the
  band of a fresh 21-class model, nothing non-finite, nothing skipped,
  updates applied, zero recompiles after warm-up, a final checkpoint
  that restores and verifies against its manifest.
* **serve leg** — built the way `cli serve` builds it: that checkpoint
  through `load_eval_variables` into `InferenceEngine(..., warmup=True)`
  at the preset's default buckets (bf16 params), then ten requests of
  mixed sizes through `engine.submit` from three threads. Every answer
  has finite scores and boxes inside its frame; the counters say
  requests = answers, no errors, and at least one flush coalesced.

It never sets `jax_platforms` to `cpu` and never re-executes itself on
another backend; it starts no process that needs the chip. The compile
cache goes where `train/warmup.py::place_compile_cache` puts it
(`JAX_COMPILATION_CACHE_DIR` if set, else `.compile_cache/` here), so a
second run in the same checkout shows warm compile times.

Writes `chiprun_out/chip_smoke.json` (device, compile seconds per
program, losses, step wall time as a SMOKE OBSERVATION — not a metric —
and serving counters; it ends with `"claim": null`) and telemetry under
`chiprun_out/chip_smoke_tel/`. The last stdout line is exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}` —
those keys and no others, the device as JAX reports it.

`--tiny` shrinks every size and skips the platform check so the same file
can be debugged on a CPU (`JAX_PLATFORMS=cpu python chip_smoke.py --tiny`);
nothing selects it but that argument, and its output says so on the line
before the result and as `"tiny": true` in the summary.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")
TEL_DIR = os.path.join(OUT_DIR, "chip_smoke_tel")
PRESET = "voc_resnet18"
STEP0_LOSS_BAND = (3.0, 12.0)  # a fresh 21-class model lands at 6.2-6.3


def _die(message: str) -> "NoReturn":  # noqa: F821
    print(f"chip_smoke: {message}", file=sys.stderr)
    sys.exit(2)


class CompileLog:
    """Sums JAX's own compile / compile-cache monitoring events so the
    summary can tell a cold run from a warm one."""

    def __init__(self) -> None:
        self.seconds: dict = {}
        self.counts: dict = {}

    def install(self) -> None:
        import jax.monitoring as mon

        def wanted(name: str) -> bool:
            return "compil" in name or "cache" in name

        def on_duration(name: str, secs: float, **_) -> None:
            if wanted(name):
                self.seconds[name] = self.seconds.get(name, 0.0) + secs

        def on_event(name: str, **_) -> None:
            if wanted(name):
                self.counts[name] = self.counts.get(name, 0) + 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def take(self) -> dict:
        """The totals since the last take (one block per leg)."""
        out = {
            "seconds": {k: round(v, 3) for k, v in sorted(self.seconds.items())},
            "counts": dict(sorted(self.counts.items())),
        }
        self.seconds, self.counts = {}, {}
        return out


def result_line(device: dict) -> str:
    """The last stdout line, to the driver's contract: exactly the keys
    `ok` and `device`, and in `device` exactly `platform`, `kind`, `count`.
    Everything else (claim, tiny, timings) goes to chip_smoke.json."""
    keys = ("platform", "kind", "count")
    return json.dumps({"ok": True, "device": {k: device[k] for k in keys}})


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def train_leg(args, n_dev: int, workdir: str) -> dict:
    from replication_faster_rcnn_tpu import cli
    from replication_faster_rcnn_tpu.train import fault

    batch = args.per_chip_batch * n_dev
    argv = [
        "train", "--config", PRESET, "--dataset", "synthetic",
        "--device", "auto" if args.tiny else "tpu",
        "--batch-size", str(batch), "--steps", str(args.steps),
        "--log-every", "1", "--strict",
        "--workdir", workdir, "--telemetry", TEL_DIR,
    ]
    if args.tiny:
        argv += ["--image-size", "64"]
    t0 = time.perf_counter()
    rc = cli.main(argv)
    wall = time.perf_counter() - t0
    _check(rc == 0, f"cli train returned {rc}")

    with open(os.path.join(TEL_DIR, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    steps = [r for r in rows if "step" in r]
    strict = [r for r in rows if r.get("event") == "strict"]
    device = [r for r in rows if r.get("event") == "device"]
    _check(len(steps) == args.steps, f"{len(steps)} logged steps, want {args.steps}")
    _check([r["step"] for r in steps] == list(range(args.steps)), "step order")
    losses = [r["loss"] for r in steps]
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    lo, hi = STEP0_LOSS_BAND
    _check(lo <= losses[0] <= hi, f"step-0 loss {losses[0]} outside [{lo}, {hi}]")
    for r in steps:
        _check(r["nonfinite_count"] == 0, f"step {r['step']}: non-finite grads")
        _check(r["skipped"] == 0, f"step {r['step']}: update skipped")
        _check(
            math.isfinite(r["update_norm"]) and r["update_norm"] > 0,
            f"step {r['step']}: update_norm {r['update_norm']}",
        )
    _check(len(device) == 1, "the run did not log its device")
    _check(bool(strict), "no strict report: was --strict honoured?")
    for r in strict:
        _check(
            r["recompiles_after_warmup"] == 0 and r["warm_dispatches"] > 0,
            f"strict: {r}",
        )

    # the final checkpoint: manifest says final at the last step, and the
    # serve leg's explicit-step restore below re-verifies every leaf
    manifest = fault.load_manifest(workdir, args.steps)
    _check(manifest is not None, f"no manifest for step {args.steps}")
    _check(manifest["kind"] == "final", f"manifest kind {manifest['kind']!r}")

    with open(os.path.join(TEL_DIR, "trace.json")) as f:
        trace = json.load(f)
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    dispatch = [
        e["dur"] / 1e6 for e in events
        if e.get("name") == "step/dispatch" and e.get("ph") == "X"
    ]
    gaps = [b["t"] - a["t"] for a, b in zip(steps[1:], steps[2:])]
    return {
        "argv": argv,
        "batch_size": batch,
        "wall_s": round(wall, 2),
        "losses": [round(x, 4) for x in losses],
        "update_norm": [round(r["update_norm"], 5) for r in steps],
        "strict": [
            {k: r[k] for k in (
                "program", "dispatches", "warm_dispatches",
                "recompiles_after_warmup",
            )}
            for r in strict
        ],
        # first dispatch = trace + lower + compile (or cache load) + run
        "train_step_first_dispatch_s": round(dispatch[0], 3),
        "smoke_observation_not_a_metric": {
            "step_wall_s_logged_every_step": [round(g, 4) for g in gaps],
        },
        "final_checkpoint": {
            "step": manifest["step"], "kind": manifest["kind"],
            "leaves": len(manifest.get("leaves", {})),
        },
    }


def serve_leg(args, n_dev: int, workdir: str) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from replication_faster_rcnn_tpu.analysis.strict import StrictHarness
    from replication_faster_rcnn_tpu.config import get_config
    from replication_faster_rcnn_tpu.serving.engine import InferenceEngine
    from replication_faster_rcnn_tpu.train.trainer import load_eval_variables

    cfg = get_config(PRESET)
    cfg = cfg.replace(data=dataclasses.replace(cfg.data, dataset="synthetic"))
    if args.tiny:
        cfg = cfg.replace(
            data=dataclasses.replace(cfg.data, image_size=(64, 64))
        )
    # an explicit step: a missing or torn checkpoint raises here instead
    # of quietly serving a fresh init
    model, variables = load_eval_variables(cfg, workdir, args.steps)
    t0 = time.perf_counter()
    engine = InferenceEngine(
        cfg, model, variables, warmup=True, model_version=str(args.steps)
    )
    startup = time.perf_counter() - t0
    leaf = jax.tree_util.tree_leaves(engine._variables)[0]
    serve_devices = sorted(str(d) for d in leaf.devices())

    h, w = cfg.data.image_size
    rng = np.random.default_rng(0)
    # a few sizes per bucket (the derived default is full size + its half)
    sizes = [
        (h * 5 // 8, w * 5 // 6), (h, w), (h // 3, w * 7 // 15),
        (h // 2, w // 2), (h * 3 // 4, w),
    ]
    images = [
        rng.integers(0, 256, (*sizes[i % len(sizes)], 3), dtype=np.uint8)
        for i in range(args.requests)
    ]
    answers: list = [None] * len(images)
    errors: list = []

    def client(idxs) -> None:
        try:
            futures = [(i, engine.submit(images[i])) for i in idxs]
            for i, fut in futures:
                answers[i] = fut.result(timeout=300)
        except Exception as e:  # noqa: BLE001 - reported by the main thread
            errors.append(f"{type(e).__name__}: {e}")

    engine.strict = StrictHarness(warmup_dispatches=cfg.debug.strict_warmup)
    threads = [
        threading.Thread(target=client, args=(range(k, len(images), 3),))
        for k in range(3)
    ]
    try:
        with engine.strict.session():
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            serve_wall = time.perf_counter() - t0
        _check(not any(t.is_alive() for t in threads), "a client thread hung")
        _check(not errors, f"request errors: {errors}")
        stats = engine.stats
    finally:
        engine.close()

    n_det = 0
    for img, ans in zip(images, answers):
        _check(ans is not None, "a request got no answer")
        ih, iw = img.shape[:2]
        for key in ("boxes", "scores", "classes", "valid"):
            _check(key in ans, f"answer lacks {key!r}")
        _check(ans["boxes"].shape[1:] == (4,), f"boxes shape {ans['boxes'].shape}")
        _check(np.all(np.isfinite(ans["scores"])), "non-finite scores")
        _check(np.all(np.isfinite(ans["boxes"])), "non-finite boxes")
        valid = np.asarray(ans["valid"], bool)
        b = ans["boxes"][valid]
        n_det += int(valid.sum())
        eps = 1e-3 * max(ih, iw)
        _check(
            bool(np.all(b >= -eps) and np.all(b[:, [0, 2]] <= ih + eps)
                 and np.all(b[:, [1, 3]] <= iw + eps)),
            f"boxes outside the {ih}x{iw} frame",
        )
        _check(np.all((ans["scores"][valid] >= 0) & (ans["scores"][valid] <= 1)),
               "scores outside [0, 1]")
    _check(stats["requests"] == len(images), f"stats {stats}")
    for key in ("flush_errors", "shed", "deadline_expired", "timeouts"):
        _check(stats[key] == 0, f"{key} = {stats[key]}")
    _check(
        0 < stats["flushes"] < len(images),
        f"no flush coalesced: {stats['flushes']} flushes for {len(images)} requests",
    )
    engine.strict.check()
    return {
        "buckets": [list(b) for b in engine.buckets],
        "batch_sizes": list(engine.batch_sizes),
        "params_dtype": engine.params_dtype,
        "params_bytes": engine.params_bytes,
        "compile_seconds": engine.compile_seconds,
        "startup_s": round(startup, 2),
        "devices_used": serve_devices,
        "devices_present": n_dev,
        "note": (
            "serving placed on one chip; a replica per chip is R-W3's"
            if n_dev > 1 else "one chip"
        ),
        "requests": len(images),
        "answers": sum(a is not None for a in answers),
        "detections": n_det,
        "stats": stats,
        "recompiles_after_warmup": sum(
            st["recompiles_after_warmup"]
            for st in engine.strict.report()["programs"].values()
        ),
        "smoke_observation_not_a_metric": {"serve_wall_s": round(serve_wall, 3)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--tiny", action="store_true",
        help="CPU debug of this file: 64x64, batch 2, 3 steps, no platform "
             "check; never evidence about a chip",
    )
    args = ap.parse_args(argv)
    args.steps = 3 if args.tiny else 8
    args.per_chip_batch = 2 if args.tiny else 16
    args.requests = 10

    try:
        import jax

        import replication_faster_rcnn_tpu  # noqa: F401
    except ImportError as e:
        _die(f"cannot import the program next to this file ({e})")
    from replication_faster_rcnn_tpu.telemetry.mfu import device_record
    from replication_faster_rcnn_tpu.train.warmup import place_compile_cache

    device = device_record()
    if device["platform"] != "tpu" and not args.tiny:
        _die(
            f"no TPU: jax.devices()[0].platform is {device['platform']!r} "
            f"({device['count']} device(s)); nothing was run"
        )
    cache_dir = place_compile_cache()
    print(
        f"chip_smoke: platform={device['platform']} "
        f"device_kind={device['kind']!r} devices={device['count']} "
        f"compile_cache={cache_dir}",
        flush=True,
    )
    compiles = CompileLog()
    compiles.install()
    shutil.rmtree(TEL_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    # checkpoints are ~100 MB: they live outside chiprun_out and go away
    workdir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    summary = {
        "ok": False, "tiny": args.tiny, "device": device, "preset": PRESET,
        "jax": jax.__version__, "compile_cache": cache_dir,
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    t0 = time.perf_counter()
    try:
        summary["train"] = train_leg(args, device["count"], workdir)
        summary["train"]["jax_compile_events"] = compiles.take()
        summary["serve"] = serve_leg(args, device["count"], workdir)
        summary["serve"]["jax_compile_events"] = compiles.take()
        summary["ok"] = True
    except BaseException as e:  # noqa: BLE001 - recorded, then re-raised
        summary["error"] = f"{type(e).__name__}: {e}"[:2000]
        raise
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        summary["wall_s"] = round(time.perf_counter() - t0, 1)
        summary["claim"] = None
        with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
            json.dump(summary, f, indent=1)
    if args.tiny:
        print("chip_smoke: tiny=True, a CPU debug run, not chip evidence")
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
