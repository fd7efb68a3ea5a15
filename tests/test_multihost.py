"""Multi-host distributed smoke test: two REAL processes, a shared
jax.distributed coordinator, and a global-mesh reduction across the process
boundary — the framework's DCN-path equivalent of the reference's absent
NCCL/MPI backend (SURVEY.md §2.4)."""

import json
import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_workers(mode: str, workdir: str):
    """Start two multihost_worker.py subprocesses against a fresh
    coordinator and return (procs, outs) after both exit. The workers pin
    themselves to the CPU backend with their own virtual devices, so the
    parent's XLA_FLAGS (8 devices) must not leak in."""
    port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    script = os.path.join(os.path.dirname(__file__), "multihost_worker.py")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(script)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = os.pathsep.join(
        [repo_root]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )

    procs = [
        subprocess.Popen(
            [sys.executable, "-u", script, coordinator, str(pid), "2",
             mode, workdir],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=repo_root,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            # generous: two jax processes compile concurrently on one core
            out, _ = p.communicate(timeout=1500)
        except subprocess.TimeoutExpired:
            partial = []
            for q in procs:
                q.kill()
                try:
                    partial.append(q.communicate(timeout=10)[0] or "")
                except Exception:
                    partial.append("<unreadable>")
            pytest.fail(
                "multi-host worker timed out; partial output:\n"
                + "\n---\n".join(partial)
            )
        outs.append(out)
    return procs, outs


@pytest.mark.slow
def test_two_process_allreduce(tmp_path):
    workdir = str(tmp_path / "zero_ckpt")
    procs, outs = _launch_workers("trainstep", workdir)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out}"
    assert "global devices=8" in outs[0]
    assert "OK" in outs[0] and "OK" in outs[1]
    # the full sharded train step ran across the process boundary
    assert "trainstep loss=" in outs[0] and "trainstep loss=" in outs[1]
    assert "zero1 loss=" in outs[0] and "zero1 loss=" in outs[1]
    # Trainer.save/restore of cross-process ZeRO-sharded moments (ADVICE #4)
    assert "zero1 ckpt roundtrip OK" in outs[0]
    assert "zero1 ckpt roundtrip OK" in outs[1]


def _preempt_cfg():
    """The EXACT config the worker's preempt leg trains (multihost_worker
    ``_preempt_zero_spmd``): same global batch, mesh and trims, so the
    in-process resume/baseline legs run the same schedule and data order
    on a different topology (1 process x 8 devices)."""
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
        data=DataConfig(dataset="synthetic", image_size=(64, 64), max_boxes=4),
        train=TrainConfig(
            batch_size=8,
            n_epoch=2,
            backend="spmd",
            shard_opt_state=True,
            grad_allreduce_dtype="bfloat16",
        ),
        mesh=MeshConfig(num_data=8),
        proposals=ProposalConfig(pre_nms_train=128, post_nms_train=32),
        roi_targets=ROITargetConfig(n_sample=8),
    )


@pytest.mark.slow
def test_two_process_zero_preempt_cross_topology_resume(tmp_path):
    """The scale-out acceptance path end to end: a 2-process ZeRO-1 run on
    the shard_map backend trains 5 global steps, both ranks are
    SIGTERM-preempted at the same dispatch boundary, the collective
    emergency save lands — then THIS process (1 process, 8 virtual
    devices: a different topology) resumes the emergency checkpoint and
    must finish with the same trajectory as an uninterrupted run."""
    workdir = str(tmp_path / "preempt_ckpt")
    procs, outs = _launch_workers("preempt", workdir)

    from replication_faster_rcnn_tpu.train import fault

    for p, out in zip(procs, outs):
        assert p.returncode == fault.EXIT_PREEMPTED, (
            f"expected preemption exit {fault.EXIT_PREEMPTED}, got "
            f"{p.returncode}:\n{out}"
        )
        assert "preempted step=5 emergency saved" in out

    # the emergency manifest records the 2-process topology it was saved on
    manifest = fault.load_manifest(workdir, 5)
    assert manifest is not None, "no manifest for the emergency step"
    assert manifest["kind"] == "emergency"
    topo = manifest.get("topology") or {}
    assert topo.get("process_count") == 2
    assert topo.get("device_count") == 8
    assert topo.get("shard_opt_state") is True

    # every rank wrote its own telemetry stream; the report merges them
    tele = os.path.join(workdir, "telemetry")
    assert os.path.exists(os.path.join(tele, "trace.json"))
    assert os.path.exists(os.path.join(tele, "trace.rank1.json"))
    from replication_faster_rcnn_tpu.telemetry.report import summarize_run

    summary = summarize_run(tele)
    assert summary.get("ranks") == [0, 1]

    # resume on a DIFFERENT topology: 1 process x 8 virtual devices
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.train.trainer import Trainer

    cfg = _preempt_cfg()
    ds = SyntheticDataset(cfg.data, length=32)
    resumed = Trainer(cfg, workdir=workdir, dataset=ds)
    resumed.train(resume=True)
    import jax
    import numpy as np

    assert int(jax.device_get(resumed.state.step)) == 8

    baseline = Trainer(cfg, workdir=str(tmp_path / "base_ckpt"), dataset=ds)
    baseline.train()
    assert int(jax.device_get(baseline.state.step)) == 8

    got = jax.device_get(resumed._host_state().params)
    want = jax.device_get(baseline._host_state().params)
    flat_g, tree_g = jax.tree_util.tree_flatten(got)
    flat_w, tree_w = jax.tree_util.tree_flatten(want)
    assert tree_g == tree_w
    # The first 5 steps ran on a different reduction topology (2-proc
    # gloo vs 1-proc), and the bf16 gradient all-reduce makes the
    # reassociation noise bf16-sized; where Adam's m_hat/sqrt(v_hat)
    # sits near zero that can flip an update's sign, moving a weight by
    # up to ~2*lr per step — the same elementwise bound the
    # shard_map-vs-auto parity test uses, here over all 8 steps. A
    # genuinely diverged trajectory (wrong resume step, missed replay)
    # shifts the BULK of the elements by the ~1e-2 update scale, which
    # the mean-abs-difference check below would catch even if every
    # element squeaked under the per-element bound.
    adam_bound = 2.5 * cfg.train.lr * 8
    total_absdiff, total_n = 0.0, 0
    for a, b in zip(flat_g, flat_w):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=adam_bound)
        total_absdiff += float(np.abs(a - b).sum())
        total_n += a.size
    assert total_absdiff / total_n < 1e-4


@pytest.mark.slow
def test_two_process_bucketed_augmented_bitwise_resume(tmp_path):
    """ISSUE 19 acceptance: the coco_overfit bucketed recipe on a REAL
    2-process gloo fleet (shard_map backend) with fully on-device
    augmentation (hflip + scale + translation jitter). Each worker runs
    an uninterrupted 8-step baseline, then a run SIGTERM-killed at step
    5 (mid-epoch-2) and resumed on the SAME topology — and asserts the
    resumed params/batch_stats hash equals the baseline hash BITWISE
    (counter-keyed bucket + augmentation streams replay exactly; f32
    grad exchange keeps reduction order invariant)."""
    workdir = str(tmp_path / "buckets_ckpt")
    procs, outs = _launch_workers("buckets", workdir)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out}"
        assert "preempted step=5 emergency saved" in out
        assert "bitwise parity OK" in out

    def hashes(out, phase):
        return [
            line.split("hash=")[1].strip()
            for line in out.splitlines()
            if f"{phase} done hash=" in line
        ]

    # params are replicated over the data mesh: both ranks must agree on
    # the baseline hash, and every resume hash must match it
    h0, h1 = hashes(outs[0], "baseline"), hashes(outs[1], "baseline")
    assert h0 and h0 == h1, (h0, h1)
    assert hashes(outs[0], "resume") == h0
    assert hashes(outs[1], "resume") == h1


def _elastic_cfg():
    """The EXACT config the worker's elastic leg trains (multihost_worker
    ``_elastic_child``): the preempt-leg config plus the elastic knobs.
    ``num_data`` stays -1 so the same config fits every topology it meets
    — gen 0's 2x4 fleet, the re-formed 1x4 world, and this process's
    1x8 restore/baseline."""
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

    return FasterRCNNConfig(
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
        mesh=MeshConfig(),
        proposals=ProposalConfig(pre_nms_train=128, post_nms_train=32),
        roi_targets=ROITargetConfig(n_sample=8),
        elastic=ElasticConfig(heartbeat_interval_s=0.2, lease_timeout_s=1.5),
    )


@pytest.mark.slow
def test_elastic_rank_loss_reforms_and_finishes_epoch(tmp_path):
    """The elastic acceptance path end to end: two REAL supervisor
    processes each run ``elastic.run_supervisor`` over a 2-process ZeRO-1
    fleet; a seeded ``heartbeat.beat`` drop kills rank 1 mid-epoch. Rank
    0's child detects the stale lease, exits EXIT_FLEET_SHRINK, and its
    supervisor re-forms a 1-host generation 1 that falls back to the last
    CRC-verified step, re-shards the epoch's unconsumed suffix across the
    shrunken world, and finishes all 16 steps — with end-state parity
    against an uninterrupted single-process run."""
    workdir = str(tmp_path / "elastic_ckpt")
    procs, outs = _launch_workers("elastic", workdir)

    from replication_faster_rcnn_tpu.parallel import elastic

    # rank 0 survives the whole ordeal; rank 1 is the seeded casualty and
    # its supervisor leaves the fleet without claiming a new generation
    assert procs[0].returncode == 0, f"survivor failed:\n{outs[0]}"
    assert procs[1].returncode != 0, f"casualty 'survived':\n{outs[1]}"
    assert "leaving fleet" in outs[1]

    # the re-form protocol settled on a 1-host generation 1
    fleet_dir = os.path.join(workdir, "fleet")
    assert elastic.read_plan(fleet_dir, 1) == {
        "generation": 1,
        "survivors": [0],
        "world": 1,
    }
    intent = elastic.read_intent(fleet_dir, 0)
    assert intent is not None and intent["lost"] == [1]

    # gen 0 sharded the Adam moments 8 ways (2 procs x 4 devices); the
    # re-formed world re-sliced them to 4, then finished the full run
    assert "elastic-leg gen 0 trainer built shards=8" in outs[0]
    assert "elastic-leg gen 1 trainer built shards=4" in outs[0]
    assert "elastic-leg gen 1 done step=16" in outs[0]

    # both fleet incidents hit the survivor's telemetry stream:
    # fleet_rank_lost from gen 0's watchdog, fleet_reformed from gen 1
    events = []
    with open(os.path.join(workdir, "telemetry", "metrics.jsonl")) as f:
        for line in f:
            row = json.loads(line)
            if "event" in row:
                events.append(row)
    lost = [e for e in events if e["event"] == "fleet_rank_lost"]
    reformed = [e for e in events if e["event"] == "fleet_reformed"]
    assert lost and lost[0]["lost"] == [1] and lost[0]["generation"] == 0
    assert reformed and reformed[0]["generation"] == 1
    assert reformed[0]["world_size"] == 1
    # the seeded drop itself was recorded (rank 0's registry fires the
    # same decision at the same hit; arg=1 means it ignores it and lives)
    chaos = [e for e in events if e["event"] == "chaos_injected"]
    assert chaos and chaos[0]["site"] == "heartbeat.beat"
    assert chaos[0]["fault_kind"] == "drop" and chaos[0]["arg"] == 1.0

    # the final checkpoint's manifest records the re-formed topology
    from replication_faster_rcnn_tpu.train import fault

    manifest = fault.load_manifest(workdir, 16)
    assert manifest is not None, "no manifest for the final step"
    topo = manifest.get("topology") or {}
    assert topo.get("generation") == 1
    assert topo.get("process_count") == 1
    assert topo.get("device_count") == 4
    assert topo.get("shard_opt_state") is True

    # end-state parity on yet another topology (1 process x 8 devices):
    # restore the elastic run's final step and compare against an
    # uninterrupted run of the same schedule
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.train.trainer import Trainer

    cfg = _elastic_cfg()
    ds = SyntheticDataset(cfg.data, length=64)
    final = Trainer(cfg, workdir=workdir, dataset=ds)
    assert final.restore() == 16

    import jax
    import numpy as np

    baseline = Trainer(cfg, workdir=str(tmp_path / "elastic_base"), dataset=ds)
    baseline.train()
    assert int(jax.device_get(baseline.state.step)) == 16

    got = jax.device_get(final._host_state().params)
    want = jax.device_get(baseline._host_state().params)
    flat_g, tree_g = jax.tree_util.tree_flatten(got)
    flat_w, tree_w = jax.tree_util.tree_flatten(want)
    assert tree_g == tree_w
    # same per-element bound as the preempt test (Adam sign flips under
    # bf16-allreduce reassociation noise move a weight by up to ~2*lr per
    # step), here over 16 steps spanning three reduction topologies; the
    # mean-abs check still catches a genuinely diverged trajectory
    adam_bound = 2.5 * cfg.train.lr * 16
    total_absdiff, total_n = 0.0, 0
    for a, b in zip(flat_g, flat_w):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=adam_bound)
        total_absdiff += float(np.abs(a - b).sum())
        total_n += a.size
    assert total_absdiff / total_n < 2e-4
