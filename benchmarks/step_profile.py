"""Step-profile regression harness: per-phase time + cost records, banked.

One command measures a config's train step, attributes wall time to the
pipeline phases (``dispatch`` floor, ``fwd``, ``bwd``, ``update``) via
the telemetry span tracer (`telemetry/spans.py`), attaches the analytic
per-phase FLOPs/bytes from XLA's HloCostAnalysis of the same lowered
programs (`analysis.fingerprint.lowered_cost`), computes MFU against the
measured host peak (`telemetry/mfu.py`), and checks the result against the
committed record for the same (config, backend, platform) under
``benchmarks/records/``:

    python benchmarks/step_profile.py --preset tiny            # check
    python benchmarks/step_profile.py --preset tiny --update   # re-bank

A run whose throughput lands >15% below the banked value on the SAME
backend+platform exits nonzero with a loud report — a perf regression
fails like a test failure instead of rotting silently in a JSON nobody
rereads. Cross-platform comparisons are skipped (a CPU run can never
"regress" a TPU record). This file owns the per-phase shape of a step.

Why spans and not bare ``time.time()``: the trainer's own hot loop is
instrumented with the same tracer (``step/dispatch``, ``step/sync``), so
profiling through spans keeps one timing vocabulary across the trainer,
the telemetry report CLI, and this harness — the record's ``spans``
table is exactly `telemetry.report.phase_table` output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

RECORDS_DIR = os.path.join(_REPO, "benchmarks", "records")
SCHEMA = "step_profile/v1"
OPS_SCHEMA = "ops_profile/v1"
DEFAULT_TOL = 0.15

# throughput is the hard gate; phase means on a shared CPU jitter well
# past 15%, so per-phase regressions are reported but only fail under
# --strict-phases
GATE_KEY = "images_per_sec"

# on-device augmentation gate (ISSUE 19): host staging must stay flat
# (≤1.1×) as the augment op count scales 0→3 — the transforms run inside
# the jitted step, so their cost lands in device dispatch, never on the
# host feed thread. The absolute floor keeps a sub-ms CPU staging
# baseline from turning quotient-of-noise into a failure.
AUGMENT_STAGE_TOL = 0.10
AUGMENT_STAGE_FLOOR_MS = 0.3


# ---------------------------------------------------------------------------
# pure record logic (no jax): unit-testable without timing anything


def record_key(config_token: str, backend: str, platform: str, k: int = 1) -> str:
    """Identity of a banked record: what must match for a comparison to
    be meaningful. ``k`` is train.steps_per_dispatch — a fused-dispatch
    profile is a different record, not a regression of the k=1 one."""
    token = f"{config_token}_{backend}_{platform}"
    if k > 1:
        token += f"_k{k}"
    return token


def record_path(key: str, records_dir: str = RECORDS_DIR) -> str:
    return os.path.join(records_dir, f"step_profile_{key}.json")


def check_regression(current, banked, tol: float = DEFAULT_TOL,
                     strict_phases: bool = False):
    """Compare a fresh profile against its banked record.

    Returns (failures, warnings): lists of human-readable strings. A
    failure means the harness must exit nonzero. Only records with the
    same key are comparable — the caller guarantees that by construction
    (the banked record is looked up BY key)."""
    failures, warnings = [], []
    if banked.get("schema") != SCHEMA:
        warnings.append(
            f"banked record has schema {banked.get('schema')!r}, "
            f"expected {SCHEMA!r}; skipping comparison"
        )
        return failures, warnings

    old = banked.get(GATE_KEY)
    new = current.get(GATE_KEY)
    if old and new:
        drop = 1.0 - new / old
        if drop > tol:
            failures.append(
                f"{GATE_KEY} regressed {drop:+.1%}: {new:.3f} vs banked "
                f"{old:.3f} (tolerance {tol:.0%})"
            )
        elif drop > tol / 2:
            warnings.append(
                f"{GATE_KEY} within tolerance but slipping {drop:+.1%}: "
                f"{new:.3f} vs banked {old:.3f}"
            )
    # overlap gate (PR 4): the double-buffered device feed must keep
    # hiding staging behind dispatch. overlap_fraction is "how much of the
    # synchronous staging cost the stager hid" — a >tol relative drop means
    # the producer thread stopped overlapping and fails like a throughput
    # regression. Only enforced when the banked fraction is substantial:
    # where staging is a millisecond or two (CPU feeds), the fraction is
    # quotient-of-noise and a relative rule would flap. Records from
    # before the overlap section skip the check entirely.
    old_ov = ((banked.get("overlap") or {}).get("overlap_fraction"))
    new_ov = ((current.get("overlap") or {}).get("overlap_fraction"))
    if old_ov and old_ov >= 0.3 and new_ov is not None:
        ov_drop = 1.0 - new_ov / old_ov
        if ov_drop > tol:
            failures.append(
                f"overlap_fraction regressed {ov_drop:+.1%}: {new_ov:.3f} vs "
                f"banked {old_ov:.3f} (tolerance {tol:.0%})"
            )
    # absolute arm of the same gate — the acceptance number itself: feed
    # time paid on the dispatch thread must stay under 10% of dispatch
    # wall (with tol headroom over the banked value for noisy hosts)
    old_frac = ((banked.get("overlap") or {}).get("host_blocked_frac_of_dispatch"))
    new_frac = ((current.get("overlap") or {}).get("host_blocked_frac_of_dispatch"))
    if old_frac is not None and new_frac is not None:
        ceiling = max(old_frac * (1.0 + tol), 0.10)
        if new_frac > ceiling:
            failures.append(
                f"host_blocked_frac_of_dispatch {new_frac:.3f} exceeds "
                f"{ceiling:.3f} (banked {old_frac:.3f} + {tol:.0%}, floor 0.10)"
            )
    for phase, row in (banked.get("phases") or {}).items():
        old_ms = (row or {}).get("mean_ms")
        new_ms = ((current.get("phases") or {}).get(phase) or {}).get("mean_ms")
        if not old_ms or not new_ms:
            continue
        growth = new_ms / old_ms - 1.0
        if growth > tol:
            msg = (
                f"phase {phase!r} slowed {growth:+.1%}: {new_ms:.2f} ms vs "
                f"banked {old_ms:.2f} ms"
            )
            (failures if strict_phases else warnings).append(msg)
    # augmentation flatness gate (ISSUE 19). Two arms: the in-run one
    # (every level's host stage vs this run's own 0-op baseline) is the
    # acceptance number itself; the vs-banked one catches a slow creep
    # where every level degrades together. Records banked before the
    # augment section simply skip the second arm.
    aug_levels = (current.get("augment") or {}).get("levels") or []
    if len(aug_levels) >= 2:
        base_ms = aug_levels[0].get("host_stage_ms") or 0.0
        worst = max(lv.get("host_stage_ms") or 0.0 for lv in aug_levels)
        ceiling = (
            base_ms * (1.0 + AUGMENT_STAGE_TOL) + AUGMENT_STAGE_FLOOR_MS
        )
        if worst > ceiling:
            failures.append(
                f"augment host_stage_ms not flat: worst level {worst:.3f} ms"
                f" vs 0-op baseline {base_ms:.3f} ms (ceiling {ceiling:.3f}"
                f" = baseline × {1.0 + AUGMENT_STAGE_TOL:.2f} + "
                f"{AUGMENT_STAGE_FLOOR_MS} ms floor)"
            )
        banked_levels = (banked.get("augment") or {}).get("levels") or []
        if banked_levels:
            old_worst = max(
                lv.get("host_stage_ms") or 0.0 for lv in banked_levels
            )
            b_ceiling = (
                old_worst * (1.0 + AUGMENT_STAGE_TOL) + AUGMENT_STAGE_FLOOR_MS
            )
            if worst > b_ceiling:
                failures.append(
                    f"augment host_stage_ms {worst:.3f} exceeds banked worst "
                    f"{old_worst:.3f} × {1.0 + AUGMENT_STAGE_TOL:.2f} + "
                    f"{AUGMENT_STAGE_FLOOR_MS} ms floor ({b_ceiling:.3f})"
                )
    return failures, warnings


def load_record(path: str):
    with open(path) as f:
        return json.load(f)


def save_record(record, path: str) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# measurement


def tiny_config(batch_size: int = 2, image_size: int = 64, backend: str = "auto",
                steps_per_dispatch: int = 1):
    """The trimmed-budget profile config: same shape family the fast test
    tier compiles (64x64 synthetic, pre_nms 128 / post_nms 32 / n_sample
    8), so a committed CPU record prices the exact graphs CI exercises."""
    from replication_faster_rcnn_tpu.config import (
        DataConfig,
        FasterRCNNConfig,
        MeshConfig,
        ModelConfig,
        ProposalConfig,
        ROITargetConfig,
        TrainConfig,
    )

    return FasterRCNNConfig(
        model=ModelConfig(
            backbone="resnet18", roi_op="align", compute_dtype="float32"
        ),
        data=DataConfig(
            dataset="synthetic", image_size=(image_size, image_size), max_boxes=8
        ),
        train=TrainConfig(
            batch_size=batch_size,
            n_epoch=4,
            backend=backend,
            steps_per_dispatch=steps_per_dispatch,
        ),
        mesh=MeshConfig(num_data=1),
        proposals=ProposalConfig(pre_nms_train=128, post_nms_train=32),
        roi_targets=ROITargetConfig(n_sample=8),
    )


def _phase_fns(model, cfg, tx):
    """The four jitted phase programs: fwd and grad are the step's own
    `compute_losses`, without and under `value_and_grad`; update/null run
    on materialized grads."""
    import jax
    import jax.numpy as jnp
    import optax

    from replication_faster_rcnn_tpu.train.train_step import compute_losses

    @jax.jit
    def fwd_fn(state, batch):
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

        (total, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        return total + optax.global_norm(grads)

    @jax.jit
    def update_fn(state, grads):
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        return optax.apply_updates(state.params, updates), opt_state

    @jax.jit
    def null_fn(state, grads):
        # dispatch + completion-sync floor: same inputs, near-empty program
        return jax.tree_util.tree_leaves(grads)[0].ravel()[0] + jnp.float32(
            state.step
        )

    return fwd_fn, grad_fn, update_fn, null_fn


def _measure_overlap(step, state, batch, n_dispatches: int = 8,
                     prefetch_depth: int = 2):
    """Host-blocked time per dispatch, with and without the device stager.

    Two loops over identical host batches through the SAME compiled step:

    * synchronous — collate copy + ``device_put`` + wait on the consumer
      thread before every dispatch (the pre-PR-4 feed), giving
      ``host_stage_ms``;
    * overlapped — a :class:`DevicePrefetcher` producer thread stages
      batch K+1 while dispatch K runs; the consumer's only feed cost is
      the queue wait, giving ``host_blocked_ms``.

    ``overlap_fraction`` = share of the synchronous staging cost the
    stager hid; ``host_blocked_frac_of_dispatch`` is the acceptance
    number (host-blocked time as a fraction of dispatch wall)."""
    import jax
    import numpy as np

    from replication_faster_rcnn_tpu.data.prefetch_device import (
        DevicePrefetcher,
    )

    feed = [batch for _ in range(n_dispatches)]
    wait_transfer = jax.default_backend() != "cpu"

    def stage(bs):
        # the trainer's feed work per dispatch: the collate/stack host
        # copy (fresh arrays — an already-resident buffer would
        # short-circuit the transfer) plus the device_put. Only off-CPU
        # do we wait for the transfer itself: XLA:CPU retires transfer
        # completion on the compute stream, so block_until_ready there
        # measures whatever dispatches are in flight, not the feed.
        collated = {key: np.array(v) for key, v in bs[0].items()}
        staged = jax.device_put(collated)
        if wait_transfer:
            for leaf in jax.tree_util.tree_leaves(staged):
                leaf.block_until_ready()
        return staged

    def drain(out):
        jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[:1])

    # synchronous baseline
    stage_s = 0.0
    out = None
    t_wall = time.perf_counter()
    for b in feed:
        t0 = time.perf_counter()
        staged = stage([b])
        stage_s += time.perf_counter() - t0
        out = step(state, staged)
    drain(out)
    sync_wall_s = time.perf_counter() - t_wall

    # overlapped: consumer pays only the queue wait
    stager = DevicePrefetcher(
        iter(feed), stage, depth=prefetch_depth, chunk=1
    )
    blocked_s = 0.0
    out = None
    t_wall = time.perf_counter()
    try:
        while True:
            t0 = time.perf_counter()
            try:
                item = next(stager)
            except StopIteration:
                break
            blocked_s += time.perf_counter() - t0
            out = step(state, item[1])
    finally:
        stager.close()
    drain(out)
    overlap_wall_s = time.perf_counter() - t_wall

    n = float(n_dispatches)
    host_stage_ms = stage_s / n * 1e3
    host_blocked_ms = blocked_s / n * 1e3
    dispatch_wall_ms = overlap_wall_s / n * 1e3
    overlap_fraction = (
        max(0.0, 1.0 - host_blocked_ms / host_stage_ms)
        if host_stage_ms > 0 else None
    )
    return {
        "prefetch_depth": prefetch_depth,
        "n_dispatches": n_dispatches,
        "host_stage_ms": round(host_stage_ms, 3),
        "host_blocked_ms": round(host_blocked_ms, 3),
        "overlap_fraction": (
            round(overlap_fraction, 4) if overlap_fraction is not None else None
        ),
        "sync_wall_ms": round(sync_wall_s / n * 1e3, 3),
        "dispatch_wall_ms": round(dispatch_wall_ms, 3),
        "host_blocked_frac_of_dispatch": (
            round(host_blocked_ms / dispatch_wall_ms, 4)
            if dispatch_wall_ms > 0 else None
        ),
    }


def _measure_augment(cfg, n_dispatches: int = 12, n_steps: int = 5):
    """Host-stage flatness as on-device augmentation ops scale 0→3.

    With ``data.augment_device`` the host loader ships pixels untouched
    plus a 2-int32 ``aug`` tag per row; every transform (hflip, scale
    jitter, translation jitter) runs inside the jitted train step. So
    the host staging cost — the same collate copy + device_put the
    trainer pays per dispatch — must stay FLAT as the op count grows,
    and the augmentation milliseconds must show up in the device step
    wall instead. One level per op count, each compiling the step that
    traces exactly that level's transforms."""
    import dataclasses

    import jax
    import numpy as np

    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.data.loader import collate
    from replication_faster_rcnn_tpu.train.train_step import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )

    batch_size = cfg.train.batch_size
    tx, _ = make_optimizer(cfg, steps_per_epoch=100)
    model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
    ds = SyntheticDataset(cfg.data, length=batch_size)
    base = collate([ds[i] for i in range(batch_size)])
    # the loader's AugmentTagView tag: (dataset idx, epoch) per row
    aug_tag = np.stack(
        [np.asarray([i, 0], np.int32) for i in range(batch_size)]
    )

    LEVELS = (
        (),
        ("hflip",),
        ("hflip", "scale"),
        ("hflip", "scale", "translate"),
    )
    wait_transfer = jax.default_backend() != "cpu"
    levels = []
    for ops_on in LEVELS:
        dcfg = dataclasses.replace(
            cfg.data,
            augment_device=bool(ops_on),
            augment_hflip="hflip" in ops_on,
            augment_scale=((0.75, 1.25) if "scale" in ops_on else None),
            augment_translate=(0.1 if "translate" in ops_on else 0.0),
        )
        vcfg = cfg.replace(data=dcfg)
        batch = dict(base)
        if ops_on:
            batch["aug"] = aug_tag
        step = jax.jit(make_train_step(model, vcfg, tx))

        # the trainer's per-dispatch feed work (same stage as
        # _measure_overlap): fresh collate copy + device_put. Median, not
        # mean — a single scheduler hiccup must not fake a slope.
        stage_ms = []
        staged = None
        for _ in range(n_dispatches):
            t0 = time.perf_counter()
            collated = {key: np.array(v) for key, v in batch.items()}
            staged = jax.device_put(collated)
            if wait_transfer:
                for leaf in jax.tree_util.tree_leaves(staged):
                    leaf.block_until_ready()
            stage_ms.append((time.perf_counter() - t0) * 1e3)

        out = step(state, staged)  # compile + stabilize
        jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[:1])
        t0 = time.perf_counter()
        for _ in range(n_steps):
            out = step(state, staged)
            jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[:1])
        step_ms = (time.perf_counter() - t0) / n_steps * 1e3

        levels.append({
            "n_ops": len(ops_on),
            "ops": list(ops_on),
            "host_stage_ms": round(float(np.median(stage_ms)), 3),
            "step_ms": round(step_ms, 3),
        })

    base_stage = levels[0]["host_stage_ms"]
    ratio = (
        max(lv["host_stage_ms"] / base_stage for lv in levels)
        if base_stage > 0
        else None
    )
    return {
        "levels": levels,
        "host_stage_ratio_max": (
            round(ratio, 4) if ratio is not None else None
        ),
        # the transforms' cost, attributed where it belongs: the device
        # step wall of the 3-op level over the 0-op level (raw — small
        # negatives are CPU timing noise, not a speedup claim)
        "device_augment_ms": round(
            levels[-1]["step_ms"] - levels[0]["step_ms"], 3
        ),
    }


def _measure_async_save(step, state, batch_staged, n_saves: int = 3):
    """Trainer-side checkpoint cost, synchronous vs background writer.

    The "save" is the manifest half of the real pipeline (host snapshot +
    per-leaf CRC + atomic manifest rename via ``fault.write_manifest`` —
    the same function the trainer's writer runs); orbax serialization is
    skipped to keep the harness's disk footprint tiny, so these numbers
    are a floor on the real win, not the whole of it. ``save_blocked_ms``
    is what the trainer pays per scheduled save with the writer on: the
    device_get snapshot plus the submit (a dispatch runs between saves,
    so the previous write has compute to hide behind, as in training)."""
    import shutil
    import tempfile

    import jax

    from replication_faster_rcnn_tpu.train import fault
    from replication_faster_rcnn_tpu.train.async_checkpoint import (
        AsyncCheckpointWriter,
    )

    tmp = tempfile.mkdtemp(prefix="step_profile_ckpt_")
    try:
        def work(i, host):
            fault.write_manifest(
                tmp, i, host, None, kind="scheduled", writer="profile"
            )

        def run_between_saves():
            # the dispatches that separate two checkpoint boundaries in a
            # real run — drained, so each timed save starts from the same
            # quiescent point and a background write has the same compute
            # wall to hide behind that it gets in training
            out = step(state, batch_staged)
            jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[:1])

        sync_s = 0.0
        for i in range(n_saves):
            run_between_saves()
            t0 = time.perf_counter()
            work(i, jax.device_get(state))
            sync_s += time.perf_counter() - t0

        writer = AsyncCheckpointWriter()
        blocked_s = 0.0
        for i in range(n_saves):
            run_between_saves()
            t0 = time.perf_counter()
            host = jax.device_get(state)
            writer.submit(100 + i, lambda i=i, h=host: work(100 + i, h))
            blocked_s += time.perf_counter() - t0
        writer.wait()
        return {
            "n_saves": n_saves,
            "save_sync_ms": round(sync_s / n_saves * 1e3, 3),
            "save_blocked_ms": round(blocked_s / n_saves * 1e3, 3),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def profile(cfg, config_token: str, n_steps: int = 5):
    """Measure one config's step profile; returns the record dict."""
    import jax
    import numpy as np

    from replication_faster_rcnn_tpu.analysis.fingerprint import lowered_cost
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.data.loader import collate
    from replication_faster_rcnn_tpu.telemetry.mfu import (
        compute_mfu,
        peak_flops_per_sec,
    )
    from replication_faster_rcnn_tpu.telemetry.report import phase_table
    from replication_faster_rcnn_tpu.telemetry.spans import SpanTracer
    from replication_faster_rcnn_tpu.train.train_step import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from replication_faster_rcnn_tpu.train.warmup import abstract_step_inputs

    batch_size = cfg.train.batch_size
    k = max(1, cfg.train.steps_per_dispatch)
    tx, _ = make_optimizer(cfg, steps_per_epoch=100)
    model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
    ds = SyntheticDataset(cfg.data, length=batch_size)
    batch = collate([ds[i] for i in range(batch_size)])

    step = make_train_step(model, cfg, tx)
    if k > 1:
        from replication_faster_rcnn_tpu.train.train_step import build_multi_step

        step = build_multi_step(step, k)
        batch = {key: np.stack([v] * k) for key, v in batch.items()}
    step = jax.jit(step)

    fwd_fn, grad_fn, update_fn, null_fn = _phase_fns(model, cfg, tx)
    phase_batch = collate([ds[i] for i in range(batch_size)])

    # materialized grads for the update/null programs: one grad_fn's worth
    # of real values, shaped like params
    grads = jax.tree_util.tree_map(lambda p: jax.numpy.ones_like(p), state.params)

    tracer = SpanTracer()

    def timed(name, fn, *args):
        for _ in range(2):  # compile + stabilize, outside any span
            out = fn(*args)
        jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[:1])
        for _ in range(n_steps):
            with tracer.span(f"profile/{name}", cat="profile"):
                out = fn(*args)
                jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[:1])

    timed("dispatch", null_fn, state, grads)
    timed("fwd", fwd_fn, state, phase_batch)
    timed("grad", grad_fn, state, phase_batch)
    timed("update", update_fn, state, grads)
    timed("step", step, state, batch)

    table = {row["name"]: row for row in phase_table(tracer.to_dict()["traceEvents"])}

    def mean_ms(name):
        row = table.get(f"profile/{name}")
        return float(row["mean_ms"]) if row else None

    dispatch_ms = mean_ms("dispatch")
    fwd_ms = mean_ms("fwd")
    grad_ms = mean_ms("grad")
    update_ms = mean_ms("update")
    step_ms = mean_ms("step") / k  # per TRAIN step under fused dispatch
    bwd_ms = max(0.0, grad_ms - fwd_ms) if grad_ms and fwd_ms else None

    images_per_sec = batch_size / (step_ms / 1e3)

    # analytic per-phase cost: HloCostAnalysis of the SAME programs,
    # lowered on abstract inputs
    _, state_abs, batch_abs = abstract_step_inputs(cfg, tx)
    grads_abs = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), state_abs.params
    )
    fwd_cost = lowered_cost(fwd_fn, state_abs, batch_abs)
    grad_cost = lowered_cost(grad_fn, state_abs, batch_abs)
    update_cost = lowered_cost(update_fn, state_abs, grads_abs)
    analytic = {
        "fwd": fwd_cost,
        "bwd": {
            key: max(0.0, grad_cost[key] - fwd_cost[key]) for key in fwd_cost
        },
        "update": update_cost,
    }
    flops_per_step = grad_cost["flops"] + update_cost["flops"]

    # critical-path overlap: feed-blocked + checkpoint-blocked host time
    # through the PR 4 machinery (data/prefetch_device.py,
    # train/async_checkpoint.py), same compiled step as the timings above
    overlap = _measure_overlap(step, state, batch)
    overlap.update(_measure_async_save(step, state, jax.device_put(batch)))

    # on-device augmentation flatness: host staging vs augment op count
    augment = _measure_augment(cfg)

    peak, basis = peak_flops_per_sec(jax.device_count())
    mfu = compute_mfu(flops_per_step, images_per_sec / batch_size, peak)
    if mfu is None or basis is None:
        raise SystemExit(
            "step_profile: could not derive a non-null MFU "
            f"(flops={flops_per_step}, peak={peak}, basis={basis}) — "
            "refusing to bank a record with an MFU hole"
        )

    dev = jax.devices()[0]
    record = {
        "schema": SCHEMA,
        "config": config_token,
        "backend": cfg.train.backend,
        "steps_per_dispatch": k,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", None),
        "n_dev": jax.device_count(),
        "batch_size": batch_size,
        "image_size": list(cfg.data.image_size),
        "n_steps_timed": n_steps,
        "step_ms": round(step_ms, 3),
        "images_per_sec": round(images_per_sec, 3),
        "phases": {
            "dispatch": {"mean_ms": round(dispatch_ms, 3)},
            "fwd": {"mean_ms": round(fwd_ms, 3)},
            "bwd": {"mean_ms": round(bwd_ms, 3)},
            "update": {"mean_ms": round(update_ms, 3)},
        },
        "analytic": analytic,
        "overlap": overlap,
        "augment": augment,
        "flops_per_step": flops_per_step,
        "mfu": round(mfu, 4),
        "mfu_basis": basis,
        "spans": sorted(table.values(), key=lambda r: r["name"]),
        "measured": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    return record


# ---------------------------------------------------------------------------
# per-op backend profile (ISSUE 13): the detection hot ops, timed through
# the SAME dispatch seams the train/serve programs use, once per ops
# backend. On CPU the pallas rows run in interpret mode — structurally
# faithful (the exact kernels tier 1 gates) but not a perf signal, so the
# banked record is a coverage artifact there, never a regression gate;
# on a real TPU the same command prices the Mosaic kernels for real.


def ops_profile_path(config_token: str, platform: str,
                     records_dir: str = RECORDS_DIR) -> str:
    return os.path.join(
        records_dir, f"ops_profile_{config_token}_{platform}.json"
    )


def ops_profile(cfg, config_token: str, n_reps: int = 10):
    """Per-op (nms / roi_align / iou_match) × backend (xla / pallas)
    timings on this config's shapes; returns the ``ops_profile/v1``
    record. Each row names the backend it REQUESTED and the path that
    actually executed (`executed`), so a silent pallas→xla fallback is
    visible in the banked artifact."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from replication_faster_rcnn_tpu import ops as ops_pkg
    from replication_faster_rcnn_tpu.ops import boxes as box_ops
    from replication_faster_rcnn_tpu.ops import roi_ops
    from replication_faster_rcnn_tpu.ops.nms_tiled import nms_fixed_tiled

    rng = np.random.default_rng(0)
    h, w = cfg.data.image_size
    pre_nms = cfg.proposals.pre_nms_train
    post_nms = cfg.proposals.post_nms_train
    n_sample = cfg.roi_targets.n_sample
    n_gt = cfg.data.max_boxes
    # the RPN's anchor count at trunk stride 16, K=9 — same grid the
    # target-assignment seam matches against
    n_anchor = (h // 16) * (w // 16) * 9
    fh, fw, c = h // 16, w // 16, 256

    def boxes_of(n):
        tl = rng.uniform(0, 0.7 * h, (n, 2)).astype(np.float32)
        wh = rng.uniform(1.0, 0.3 * h, (n, 2)).astype(np.float32)
        return jnp.asarray(np.concatenate([tl, tl + wh], axis=1))

    nms_boxes = boxes_of(pre_nms)
    nms_scores = jnp.asarray(rng.uniform(size=pre_nms).astype(np.float32))
    anchors = boxes_of(n_anchor)
    gt = boxes_of(n_gt)
    gt_mask = jnp.asarray(np.arange(n_gt) < max(1, n_gt // 2))
    feat = jnp.asarray(rng.standard_normal((fh, fw, c)).astype(np.float32))
    rois = boxes_of(n_sample) * (min(fh, fw) / float(h))

    interpret = ops_pkg.interpret_mode()

    def xla_match(a, g, m):
        ious = jnp.where(m[None, :], box_ops.iou(a, g), -1.0)
        return ious, jnp.argmax(ious, 1), jnp.max(jnp.maximum(ious, 0.0), 1)

    def build(op, backend):
        """(callable, args, executed-path label) for one (op, backend)
        cell — pallas cells go through the real kernels (a kernel
        package that cannot import raises)."""
        if op == "nms":
            if backend == "pallas" and ops_pkg.require_pallas("nms"):
                from replication_faster_rcnn_tpu.ops.pallas import (
                    nms_fixed_pallas,
                )

                fn = jax.jit(
                    lambda b, s: nms_fixed_pallas(
                        b, s, 0.7, post_nms, interpret=interpret
                    )
                )
                return fn, (nms_boxes, nms_scores), _pallas_label(interpret)
            fn = jax.jit(lambda b, s: nms_fixed_tiled(b, s, 0.7, post_nms))
            return fn, (nms_boxes, nms_scores), "xla"
        if op == "roi_align":
            if backend == "pallas" and ops_pkg.require_pallas("roi_align"):
                fn = jax.jit(
                    lambda f, r: roi_ops.roi_align(f, r, method="pallas")
                )
                return fn, (feat, rois), _pallas_label(interpret)
            fn = jax.jit(lambda f, r: roi_ops.roi_align(f, r, method="einsum"))
            return fn, (feat, rois), "xla"
        if op == "iou_match":
            if backend == "pallas" and ops_pkg.require_pallas("anchor_match"):
                from replication_faster_rcnn_tpu.ops.pallas import (
                    match_boxes_pallas,
                )

                fn = jax.jit(
                    lambda a, g, m: match_boxes_pallas(
                        a, g, m, interpret=interpret
                    )
                )
                return fn, (anchors, gt, gt_mask), _pallas_label(interpret)
            return jax.jit(xla_match), (anchors, gt, gt_mask), "xla"
        raise ValueError(op)

    shapes = {
        "nms": {"n_boxes": pre_nms, "max_out": post_nms},
        "roi_align": {"feat": [fh, fw, c], "n_rois": n_sample, "out": 7},
        "iou_match": {"n_anchors": n_anchor, "n_gt": n_gt},
    }
    ops: dict = {}
    for op in ("nms", "roi_align", "iou_match"):
        ops[op] = dict(shapes[op])
        for backend in ("xla", "pallas"):
            fn, args, executed = build(op, backend)
            out = fn(*args)
            jax.tree_util.tree_map(
                lambda x: x.block_until_ready(), out
            )  # compile
            t0 = time.perf_counter()
            for _ in range(n_reps):
                out = fn(*args)
            jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
            ops[op][backend] = {
                "mean_ms": round(
                    (time.perf_counter() - t0) / n_reps * 1e3, 4
                ),
                "executed": executed,
            }

    dev = jax.devices()[0]
    return {
        "schema": OPS_SCHEMA,
        "config": config_token,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", None),
        "interpret": interpret,
        "n_reps": n_reps,
        "ops": ops,
        "measured": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def _pallas_label(interpret: bool) -> str:
    return "pallas_interpret" if interpret else "pallas"


def check_ops_record(current, banked):
    """Structural gate over the banked ops record: same schema, same
    (op × backend) matrix, and every pallas row still executes a pallas
    path (a row that silently degraded to 'xla' means the kernels
    stopped importing — that fails like a regression). Timings are never
    compared: the pallas rows are interpret-mode on CPU."""
    failures = []
    if banked.get("schema") != OPS_SCHEMA:
        failures.append(
            f"banked ops record has schema {banked.get('schema')!r}, "
            f"expected {OPS_SCHEMA!r}"
        )
        return failures
    cur_ops, bank_ops = current.get("ops", {}), banked.get("ops", {})
    if sorted(cur_ops) != sorted(bank_ops):
        failures.append(
            f"ops matrix changed: {sorted(cur_ops)} vs banked "
            f"{sorted(bank_ops)}"
        )
        return failures
    for op, row in sorted(cur_ops.items()):
        for backend in ("xla", "pallas"):
            if backend not in row:
                failures.append(f"ops.{op} lost its {backend} row")
                continue
            executed = row[backend].get("executed", "")
            if backend == "pallas" and not executed.startswith("pallas"):
                failures.append(
                    f"ops.{op} pallas row executed {executed!r} — the "
                    "pallas kernels fell back to xla"
                )
    return failures


# ---------------------------------------------------------------------------
# CLI


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--preset",
        default="tiny",
        help="'tiny' (trimmed CI-shape config) or a name from config.CONFIGS",
    )
    p.add_argument("--batch-size", type=int, default=2)
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--backend", default="auto", choices=["auto", "spmd"])
    p.add_argument("--steps-per-dispatch", type=int, default=1)
    p.add_argument("--steps", type=int, default=5, help="timed reps per phase")
    p.add_argument(
        "--update", action="store_true", help="write/overwrite the banked record"
    )
    p.add_argument(
        "--no-check", action="store_true", help="measure + print only"
    )
    p.add_argument(
        "--strict-phases",
        action="store_true",
        help="per-phase slowdowns >tol fail too (default: warn)",
    )
    p.add_argument("--tol", type=float, default=DEFAULT_TOL)
    p.add_argument("--records-dir", default=RECORDS_DIR)
    args = p.parse_args(argv)

    if args.preset == "tiny":
        cfg = tiny_config(
            batch_size=args.batch_size,
            image_size=args.image_size,
            backend=args.backend,
            steps_per_dispatch=args.steps_per_dispatch,
        )
        token = f"tiny{args.image_size}b{args.batch_size}"
    else:
        import dataclasses

        from replication_faster_rcnn_tpu.config import CONFIGS

        if args.preset not in CONFIGS:
            p.error(f"unknown preset {args.preset!r}; have {sorted(CONFIGS)}")
        cfg = CONFIGS[args.preset]
        cfg = cfg.replace(
            data=dataclasses.replace(
                cfg.data,
                dataset="synthetic",
                image_size=(args.image_size, args.image_size),
            ),
            train=dataclasses.replace(
                cfg.train,
                batch_size=args.batch_size,
                backend=args.backend,
                steps_per_dispatch=args.steps_per_dispatch,
            ),
        )
        token = f"{args.preset}{args.image_size}b{args.batch_size}"

    record = profile(cfg, token, n_steps=args.steps)
    key = record_key(
        token, record["backend"], record["platform"], record["steps_per_dispatch"]
    )
    path = record_path(key, args.records_dir)
    print(json.dumps(record, indent=1, sort_keys=True))

    ops_record = ops_profile(cfg, token)
    ops_path = ops_profile_path(token, record["platform"], args.records_dir)
    print(json.dumps(ops_record, indent=1, sort_keys=True))

    if args.update:
        save_record(record, path)
        save_record(ops_record, ops_path)
        print(f"step_profile: banked {path}", file=sys.stderr)
        print(f"step_profile: banked {ops_path}", file=sys.stderr)
        return 0
    if args.no_check:
        return 0
    if not os.path.exists(path):
        print(
            f"step_profile: no banked record at {path} — run with --update "
            "to create one (not checking)",
            file=sys.stderr,
        )
        return 0
    failures, warnings = check_regression(
        record, load_record(path), tol=args.tol, strict_phases=args.strict_phases
    )
    if os.path.exists(ops_path):
        failures.extend(
            f"ops: {m}"
            for m in check_ops_record(ops_record, load_record(ops_path))
        )
    for w in warnings:
        print(f"step_profile: WARN {w}", file=sys.stderr)
    for f in failures:
        print(f"step_profile: FAIL {f}", file=sys.stderr)
    if failures:
        print(
            f"step_profile: REGRESSION vs {path} — if intentional, re-bank "
            "with --update",
            file=sys.stderr,
        )
        return 1
    print(f"step_profile: OK vs {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
