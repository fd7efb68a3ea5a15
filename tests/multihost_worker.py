"""Worker script for the multi-host distributed test (launched as a
subprocess by tests/test_multihost.py, twice).

Each process initializes jax.distributed against a shared coordinator,
contributes its local virtual CPU devices to the global mesh, and runs a
psum over the full device set — the cross-process allreduce path
(`parallel.initialize_distributed`, SURVEY.md §2.4 DCN equivalent).
"""

import os
import sys


def main() -> int:
    coordinator = sys.argv[1]
    process_id = int(sys.argv[2])
    num_processes = int(sys.argv[3])

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()

    mode = sys.argv[4] if len(sys.argv) > 4 else ""
    if mode == "elastic":
        # the elastic supervisor never initializes jax: it outlives its
        # training children across fleet generations and owns no devices
        return _elastic_supervisor(
            coordinator, process_id, num_processes, sys.argv[5]
        )
    if mode == "elastic-child":
        return _elastic_child(
            coordinator, process_id, num_processes, sys.argv[5], sys.argv[6]
        )

    import jax

    from replication_faster_rcnn_tpu.parallel import initialize_distributed

    initialize_distributed(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n_global = len(jax.devices())
    n_local = len(jax.local_devices())
    assert n_global == 4 * num_processes, (n_global, n_local)

    mesh = Mesh(jax.devices(), ("data",))
    sharding = NamedSharding(mesh, P("data"))

    # each global device contributes its (global) index + 1
    import numpy as np

    local_vals = np.asarray(
        [jax.devices().index(d) + 1 for d in jax.local_devices()], np.float32
    )
    arr = jax.make_array_from_process_local_data(
        sharding, local_vals, (n_global,)
    )

    @jax.jit
    def total(x):
        return jnp.sum(x)  # cross-process reduction under the hood

    result = float(total(arr))
    expect = n_global * (n_global + 1) / 2
    assert result == expect, (result, expect)
    print(f"proc {process_id}: global devices={n_global} allreduce={result} OK")

    if len(sys.argv) > 4 and sys.argv[4] == "preempt":
        return _preempt_zero_spmd(process_id, sys.argv[5])
    if len(sys.argv) > 4 and sys.argv[4] == "buckets":
        return _buckets_augment_spmd(process_id, sys.argv[5])
    if len(sys.argv) > 4 and sys.argv[4] == "trainstep":
        _train_step_across_processes(process_id, n_global)
        # default workdir is scoped to the coordinator address AND cleaned
        # by process 0: ephemeral ports get reused, and a stale dir +
        # Trainer.save()'s latest_step dedup would silently restore a
        # PREVIOUS invocation's checkpoint. (Safe to clean here: the save
        # both processes participate in happens long after this point, and
        # process 1 never reads the dir before that barrier.)
        if len(sys.argv) > 5:
            workdir = sys.argv[5]
        else:
            workdir = f"/tmp/multihost_zero_ckpt_{coordinator.replace(':', '_')}"
            if process_id == 0 and os.path.exists(workdir):
                import shutil

                shutil.rmtree(workdir)
        _zero_checkpoint_across_processes(process_id, workdir)
    return 0


def _elastic_supervisor(
    coordinator: str, process_id: int, num_processes: int, workdir: str
) -> int:
    """Per-host side of the elastic acceptance leg: the REAL
    ``elastic.run_supervisor`` generation loop, spawning this same script
    in ``elastic-child`` mode once per fleet generation.

    The chaos spec arms a seeded ``heartbeat.beat`` drop that kills rank 1
    on its 21st lease renewal (~4 s into steady-state training, well past
    the first dispatch and well before the 16-step run can finish). Rank
    1's supervisor then leaves the fleet without claiming; rank 0's child
    exits ``EXIT_FLEET_SHRINK`` and its supervisor re-forms a 1-host
    generation 1 that resumes from the last CRC-verified step and
    finishes the run — so rank 0's supervisor returns 0 and rank 1's
    returns the casualty's own exit code.
    """
    import subprocess

    from replication_faster_rcnn_tpu.parallel import elastic

    host, _, port = coordinator.rpartition(":")
    fleet_dir = os.path.join(workdir, "fleet")
    # seeded drop: rank 1 (arg), 21st hit (after=20), exactly once. The
    # landing step is time-based, so the pytest assertions are
    # step-agnostic; same seed replays the same decision stream.
    chaos = "heartbeat.beat:drop:1.0:20260807:1:1:20"
    script = os.path.abspath(__file__)

    def spawn(generation, rank, world, coordinator):
        # children inherit this supervisor's stdout/stderr, so their
        # stage markers land in the harness-captured stream
        return subprocess.Popen(
            [
                sys.executable, "-u", script, coordinator or "-",
                str(rank), str(world), "elastic-child", workdir, chaos,
            ],
            env=elastic.child_env(os.environ, fleet_dir, generation),
        )

    rc = elastic.run_supervisor(
        spawn,
        fleet_dir=fleet_dir,
        rank=process_id,
        world=num_processes,
        host=host or "127.0.0.1",
        base_port=int(port),
        settle_s=1.0,
        max_generations=4,
    )
    print(f"proc {process_id}: elastic supervisor rc={rc}", flush=True)
    return rc


def _elastic_child(
    coordinator: str,
    process_id: int,
    num_processes: int,
    workdir: str,
    chaos_spec: str,
) -> int:
    """One fleet generation of the elastic acceptance run: the plain
    Trainer on the preempt-leg config plus the elastic knobs (fast
    heartbeats, 2-step checkpoint interval). Generation 0 arms the seeded
    rank-drop chaos; re-formed generations run clean and resume. A
    watchdog-detected shrink surfaces as ``FleetShrink`` at a dispatch
    boundary — or, when the main thread is wedged in the dead fleet's
    collective, as the agent's own hard ``EXIT_FLEET_SHRINK`` exit."""
    import jax

    from replication_faster_rcnn_tpu.config import (
        DataConfig,
        ElasticConfig,
        FasterRCNNConfig,
        MeshConfig,
        ModelConfig,
        ProposalConfig,
        ROITargetConfig,
        TrainConfig,
    )
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.faultlib import failpoints
    from replication_faster_rcnn_tpu.parallel import (
        elastic,
        initialize_distributed,
    )
    from replication_faster_rcnn_tpu.train import fault
    from replication_faster_rcnn_tpu.train.trainer import Trainer

    _, generation = elastic.fleet_env()

    def mark(msg: str) -> None:
        print(
            f"proc {process_id}: elastic-leg gen {generation} {msg}",
            flush=True,
        )

    if generation == 0 and chaos_spec and chaos_spec != "-":
        failpoints.configure(chaos_spec)
    if num_processes > 1:
        initialize_distributed(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    cfg = FasterRCNNConfig(
        model=ModelConfig(
            backbone="resnet18", roi_op="align", compute_dtype="float32"
        ),
        data=DataConfig(dataset="synthetic", image_size=(64, 64), max_boxes=4),
        train=TrainConfig(
            batch_size=8,
            n_epoch=2,
            backend="spmd",
            shard_opt_state=True,
            grad_allreduce_dtype="bfloat16",
            checkpoint_every_steps=2,
        ),
        # num_data=-1: each generation's mesh fits whatever devices its
        # world has (gen 0: 2 procs x 4 = 8; re-formed gen 1: 4)
        mesh=MeshConfig(),
        proposals=ProposalConfig(pre_nms_train=128, post_nms_train=32),
        roi_targets=ROITargetConfig(n_sample=8),
        elastic=ElasticConfig(heartbeat_interval_s=0.2, lease_timeout_s=1.5),
    )
    # 64 synthetic images / global batch 8 -> 8 steps per epoch, 16 total:
    # long enough that the ~4 s drop always lands mid-run
    ds = SyntheticDataset(cfg.data, length=64)
    trainer = Trainer(
        cfg,
        workdir=workdir,
        dataset=ds,
        telemetry_dir=os.path.join(workdir, "telemetry"),
    )
    mark(f"trainer built shards={trainer.mesh.shape[cfg.mesh.data_axis]}")
    try:
        trainer.train(log_every=1, resume=generation > 0)
    except fault.FleetShrink as exc:
        mark(f"shrink at step {exc.step}: lost {exc.lost}")
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(fault.EXIT_FLEET_SHRINK)
    mark(f"done step={int(jax.device_get(trainer.state.step))}")
    return 0


def _preempt_zero_spmd(process_id: int, workdir: str) -> int:
    """The scale-out acceptance leg: a REAL 2-process ZeRO-1 run on the
    shard_map backend, SIGTERM-preempted mid-epoch.

    Both ranks run the full Trainer loop (loader feed, per-process batch
    shards, sharded Adam update with reduce_scatter/all_gather) for 5
    global steps, then deliver a real SIGTERM to themselves at the SAME
    dispatch boundary — step count is deterministic and identical on both
    ranks, so the collective emergency save runs in lockstep. Exit code
    is ``fault.EXIT_PREEMPTED``; the pytest side then resumes the
    emergency checkpoint on a DIFFERENT topology (1 process x 8 devices)
    and checks trajectory parity against an uninterrupted run.
    """
    import signal
    import time

    import jax

    from replication_faster_rcnn_tpu.config import (
        DataConfig,
        FasterRCNNConfig,
        MeshConfig,
        ModelConfig,
        ProposalConfig,
        ROITargetConfig,
        TrainConfig,
    )
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.train import fault
    from replication_faster_rcnn_tpu.train.trainer import Trainer

    def mark(msg: str) -> None:
        print(f"proc {process_id}: preempt-leg {msg}", flush=True)

    n_global = len(jax.devices())
    cfg = FasterRCNNConfig(
        model=ModelConfig(
            backbone="resnet18", roi_op="align", compute_dtype="float32"
        ),
        data=DataConfig(dataset="synthetic", image_size=(64, 64), max_boxes=4),
        train=TrainConfig(
            batch_size=n_global,
            n_epoch=2,
            backend="spmd",
            shard_opt_state=True,
            grad_allreduce_dtype="bfloat16",
        ),
        mesh=MeshConfig(num_data=n_global),
        proposals=ProposalConfig(pre_nms_train=128, post_nms_train=32),
        roi_targets=ROITargetConfig(n_sample=8),
    )
    # 32 synthetic images / global batch 8 -> 4 steps per epoch; the
    # preemption at step 5 lands mid-epoch-2, exercising the replay path
    ds = SyntheticDataset(cfg.data, length=32)
    trainer = Trainer(
        cfg,
        workdir=workdir,
        dataset=ds,
        telemetry_dir=os.path.join(workdir, "telemetry"),
    )
    mark("trainer built")

    orig_check = trainer._check_preemption

    def check(step: int) -> None:
        sd = trainer._shutdown
        if step >= 5 and sd is not None and not sd.requested:
            os.kill(os.getpid(), signal.SIGTERM)  # real delivery, real handler
            deadline = time.time() + 10.0
            while not sd.requested and time.time() < deadline:
                time.sleep(0.01)
        orig_check(step)

    trainer._check_preemption = check
    try:
        trainer.train(log_every=1)
    except fault.Preempted as exc:
        mark(f"preempted step={exc.step} emergency saved")
        return fault.EXIT_PREEMPTED
    raise AssertionError("run completed without being preempted")


def _buckets_augment_spmd(process_id: int, workdir: str) -> int:
    """The multi-scale acceptance leg: the coco_overfit bucketed recipe
    (coco-format synthetic data, 2 train buckets) on a REAL 2-process
    gloo fleet with the shard_map backend AND fully on-device
    augmentation (hflip + scale + translation jitter), reproduced
    BITWISE across a SIGTERM kill-and-resume mid-epoch.

    Three phases in one process, same global mesh throughout:

    1. baseline — train 8 global steps uninterrupted, hash the params;
    2. preempt  — fresh workdir, SIGTERM at step 5 (mid-epoch-2), the
       collective emergency save lands on both ranks;
    3. resume   — restore the emergency checkpoint on the SAME topology
       and finish.

    Same reduction topology + f32 grad exchange + counter-keyed bucket
    and augmentation streams (`bucket_index`, `augment_draws` on (seed,
    epoch, dataset idx)) ⇒ the resumed trajectory must equal the
    baseline bit for bit — tolerance here would hide a replay bug.
    """
    import hashlib
    import signal
    import time

    import jax
    import numpy as np

    from benchmarks.coco_overfit import MINI_BUCKETS, write_synthetic_coco
    from replication_faster_rcnn_tpu.config import (
        DataConfig,
        FasterRCNNConfig,
        MeshConfig,
        ModelConfig,
        ProposalConfig,
        ROITargetConfig,
        TrainConfig,
    )
    from replication_faster_rcnn_tpu.data import make_dataset
    from replication_faster_rcnn_tpu.train import fault
    from replication_faster_rcnn_tpu.train.trainer import Trainer

    def mark(msg: str) -> None:
        print(f"proc {process_id}: buckets-leg {msg}", flush=True)

    n_global = len(jax.devices())
    # rank-local copy of the coco-format synthetic set: the writer is
    # seed-deterministic, so both ranks hold identical data without any
    # cross-process filesystem coordination
    data_root = os.path.join(workdir, f"coco_rank{process_id}")
    write_synthetic_coco(data_root, "train2017", 32, 64, seed=0)
    cfg = FasterRCNNConfig(
        model=ModelConfig(
            backbone="resnet18", roi_op="align", compute_dtype="float32",
            num_classes=9,
        ),
        data=DataConfig(
            dataset="coco", root_dir=data_root, image_size=(64, 64),
            max_boxes=8,
            train_resolutions=tuple(MINI_BUCKETS),
            augment_device=True, augment_hflip=True,
            augment_scale=(0.75, 1.25), augment_translate=0.1,
        ),
        train=TrainConfig(
            batch_size=n_global,
            n_epoch=2,
            backend="spmd",
            # f32 grad exchange: the bitwise contract must not depend on
            # bf16 rounding staying reassociation-stable
            grad_allreduce_dtype="float32",
        ),
        mesh=MeshConfig(num_data=n_global),
        proposals=ProposalConfig(pre_nms_train=128, post_nms_train=32),
        roi_targets=ROITargetConfig(n_sample=8),
    )
    # 32 images / global batch 8 -> 4 steps per epoch, 8 total; the
    # kill at step 5 lands mid-epoch-2 so the resume replays a bucketed,
    # augmented epoch from a nonzero start_batch offset
    ds = make_dataset(cfg.data, "train")

    def params_hash(trainer) -> str:
        host = jax.device_get(trainer._host_state())
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(
            {"p": host.params, "bn": host.batch_stats}
        ):
            h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
        return h.hexdigest()

    # phase 1: uninterrupted baseline
    base = Trainer(cfg, workdir=os.path.join(workdir, "base"), dataset=ds)
    mark("baseline trainer built")
    base.train(log_every=1)
    assert int(jax.device_get(base.state.step)) == 8
    base_hash = params_hash(base)
    mark(f"baseline done hash={base_hash}")
    del base

    # phase 2: fresh run, SIGTERM at the step-5 dispatch boundary
    pre_dir = os.path.join(workdir, "pre")
    pre = Trainer(cfg, workdir=pre_dir, dataset=ds)
    orig_check = pre._check_preemption

    def check(step: int) -> None:
        sd = pre._shutdown
        if step >= 5 and sd is not None and not sd.requested:
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.time() + 10.0
            while not sd.requested and time.time() < deadline:
                time.sleep(0.01)
        orig_check(step)

    pre._check_preemption = check
    try:
        pre.train(log_every=1)
    except fault.Preempted as exc:
        mark(f"preempted step={exc.step} emergency saved")
        assert exc.step == 5, exc.step
    else:
        raise AssertionError("run completed without being preempted")
    del pre

    # phase 3: resume the emergency checkpoint on the SAME topology
    resumed = Trainer(cfg, workdir=pre_dir, dataset=ds)
    resumed.train(log_every=1, resume=True)
    assert int(jax.device_get(resumed.state.step)) == 8
    resume_hash = params_hash(resumed)
    mark(f"resume done hash={resume_hash}")
    assert resume_hash == base_hash, (
        f"bucketed+augmented resume diverged: {resume_hash} != {base_hash}"
    )
    mark("bitwise parity OK")
    return 0


def _train_step_across_processes(process_id: int, n_global: int) -> None:
    """One REAL sharded train step over the cross-process global mesh:
    each process feeds only its local batch shard
    (`make_array_from_process_local_data`, the multi-host loader pattern);
    the compiled step's loss normalizers and gradient reductions then span
    the process boundary — the framework's actual DCN path, not a toy psum.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from replication_faster_rcnn_tpu.config import (
        DataConfig,
        FasterRCNNConfig,
        MeshConfig,
        ModelConfig,
        TrainConfig,
    )
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.data.loader import collate
    from replication_faster_rcnn_tpu.parallel import make_mesh, replicate_tree
    from replication_faster_rcnn_tpu.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )

    cfg = FasterRCNNConfig(
        model=ModelConfig(backbone="resnet18", roi_op="align", compute_dtype="float32"),
        data=DataConfig(dataset="synthetic", image_size=(64, 64), max_boxes=4),
        train=TrainConfig(batch_size=n_global),
        mesh=MeshConfig(num_data=n_global),
    )
    mesh = make_mesh(cfg.mesh)
    tx, _ = make_optimizer(cfg, steps_per_epoch=1)
    model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
    state = replicate_tree(state, mesh)

    # every process builds the SAME global batch, then contributes only the
    # rows its local devices own
    ds = SyntheticDataset(cfg.data, length=n_global)
    global_batch = collate([ds[i] for i in range(n_global)])
    sharding = NamedSharding(mesh, P(cfg.mesh.data_axis))
    n_local = len(jax.local_devices())
    lo = process_id * n_local
    device_batch = {
        k: jax.make_array_from_process_local_data(
            sharding, np.ascontiguousarray(v[lo : lo + n_local]), v.shape
        )
        for k, v in global_batch.items()
    }

    step = jax.jit(make_train_step(model, cfg, tx))
    new_state, metrics = step(state, device_batch)
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss), loss
    assert int(jax.device_get(new_state.step)) == 1
    print(f"proc {process_id}: trainstep loss={loss:.4f} OK")

    # ZeRO-1 across the process boundary: Adam moments shard over a data
    # axis that spans both processes; the update must still match the
    # replicated step (each process holds only its moment shards)
    from replication_faster_rcnn_tpu.parallel.zero import (
        place_train_state,
        train_state_shardings,
    )

    _, zstate0 = create_train_state(cfg, jax.random.PRNGKey(0), tx)
    shardings = train_state_shardings(zstate0, mesh, cfg.mesh, shard_opt=True)
    zstate = place_train_state(zstate0, shardings)
    zstep = jax.jit(
        make_train_step(model, cfg, tx), out_shardings=(shardings, None)
    )
    _, zmetrics = zstep(zstate, device_batch)
    zloss = float(jax.device_get(zmetrics["loss"]))
    assert abs(zloss - loss) < 1e-5, (zloss, loss)
    print(f"proc {process_id}: zero1 loss={zloss:.4f} OK")


def _zero_checkpoint_across_processes(process_id: int, workdir: str) -> None:
    """Trainer.save/restore of a ZeRO-sharded state ACROSS the process
    boundary (ADVICE r1 #4: `_host_state`'s cross-process all-gather —
    device_put of cross-host-sharded Adam moments to a replicated sharding
    before the orbax save — was exercised only single-process before).

    Both processes run the full Trainer on the global 2-process mesh with
    ``shard_opt_state=True``: one real batch makes the moments nonzero,
    save gathers the cross-process shards, and a FRESH Trainer restoring
    the checkpoint must reproduce the optimizer moments bitwise.
    """
    import jax
    import numpy as np

    from replication_faster_rcnn_tpu.config import (
        DataConfig,
        FasterRCNNConfig,
        MeshConfig,
        ModelConfig,
        TrainConfig,
    )
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.data.loader import collate
    from replication_faster_rcnn_tpu.train.trainer import Trainer

    n_global = len(jax.devices())
    cfg = FasterRCNNConfig(
        model=ModelConfig(
            backbone="resnet18", roi_op="align", compute_dtype="float32"
        ),
        data=DataConfig(dataset="synthetic", image_size=(64, 64), max_boxes=4),
        train=TrainConfig(batch_size=n_global, shard_opt_state=True, n_epoch=1),
        mesh=MeshConfig(num_data=n_global),
    )
    def mark(msg: str) -> None:
        # stdout to the harness is a block-buffered PIPE: flush each stage
        # marker so a hang is attributable from partial output
        print(f"proc {process_id}: ckpt-leg {msg}", flush=True)

    ds = SyntheticDataset(cfg.data, length=n_global)
    trainer = Trainer(cfg, workdir=workdir, dataset=ds)
    mark("trainer built")
    batch = collate([ds[i] for i in range(n_global)])
    trainer.train_one_batch(batch)
    mark("stepped")
    # gather BEFORE save so a hang distinguishes the cross-process
    # all-gather (_host_state) from the orbax write barrier
    want = trainer._host_state()
    mark("gathered")
    trainer.save()
    mark("saved")

    trainer2 = Trainer(cfg, workdir=workdir, dataset=ds)
    assert trainer2.restore() == 1
    mark("restored")
    got = trainer2._host_state()

    flat_w, tree_w = jax.tree_util.tree_flatten(want.opt_state)
    flat_g, tree_g = jax.tree_util.tree_flatten(got.opt_state)
    assert tree_w == tree_g
    for a, b in zip(flat_w, flat_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a restored moment tree that is all zeros would pass equality only if
    # the step never ran; make the check meaningful
    assert any(np.abs(np.asarray(x)).max() > 0 for x in flat_g)
    print(f"proc {process_id}: zero1 ckpt roundtrip OK")


if __name__ == "__main__":
    sys.exit(main())
