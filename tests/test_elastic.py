"""Fast-tier units for the elastic fleet protocol (`parallel/elastic.py`):
env contract, lease/intent/claim/plan files, the in-child agent driven
single-threaded with a fake clock, child argv rewriting, and the
supervisor's generation loop against fake spawns. The real 2-process
rank-kill acceptance (gloo fleet, mid-epoch loss, same-epoch finish)
lives in tests/test_multihost.py's slow tier."""

import os
import threading

import pytest

from replication_faster_rcnn_tpu.parallel import elastic
from replication_faster_rcnn_tpu.train.fault import (
    EXIT_FLEET_SHRINK,
    EXIT_PREEMPTED,
)


class TestEnvContract:
    def test_roundtrip(self):
        env = elastic.child_env({"PATH": "/bin"}, "/tmp/fleet", 3)
        assert env["PATH"] == "/bin"
        assert elastic.fleet_env(env) == ("/tmp/fleet", 3)

    def test_absent_means_disabled(self):
        assert elastic.fleet_env({}) == (None, 0)

    def test_garbage_generation_is_zero(self):
        assert elastic.fleet_env({elastic.ENV_GENERATION: "x"}) == (None, 0)


class TestFleetFiles:
    def test_names_encode_generation_and_rank(self, tmp_path):
        d = str(tmp_path)
        assert "gen2" in elastic.lease_path(d, 2, 1)
        assert elastic.lease_path(d, 2, 1) != elastic.lease_path(d, 3, 1)
        assert elastic.claim_path(d, 1, 0) != elastic.claim_path(d, 1, 1)

    def test_claims_plan_roundtrip(self, tmp_path):
        d = str(tmp_path)
        elastic.write_claim(d, 1, 0)
        elastic.write_claim(d, 1, 2)
        assert elastic.read_claims(d, 1, 4) == [0, 2]
        elastic.write_plan(d, 1, [2, 0])
        assert elastic.read_plan(d, 1) == {
            "generation": 1, "survivors": [0, 2], "world": 2,
        }

    def test_wait_plan_times_out(self, tmp_path):
        assert elastic.wait_plan(str(tmp_path), 1, timeout_s=0.05) is None

    def test_clear_fleet_dir_keeps_foreign_files(self, tmp_path):
        d = str(tmp_path)
        elastic.write_claim(d, 1, 0)
        elastic.write_plan(d, 1, [0])
        (tmp_path / "keep.txt").write_text("x")
        elastic.clear_fleet_dir(d)
        assert os.listdir(d) == ["keep.txt"]


class TestElasticAgent:
    def _agent(self, tmp_path, rank, now, **kw):
        kw.setdefault("lease_timeout_s", 1.0)
        return elastic.ElasticAgent(
            str(tmp_path), generation=0, rank=rank, world=2,
            clock=lambda: now[0], exit_on_shrink=False, **kw,
        )

    def test_missing_peer_lease_is_alive(self, tmp_path):
        """Compile skew between ranks must not read as death: leases
        start lazily at the first dispatch boundary."""
        now = [100.0]
        a = self._agent(tmp_path, 0, now)
        a.beat()
        assert a.lost_ranks() == []

    def test_stale_lease_is_lost_fresh_is_not(self, tmp_path):
        now = [0.0]
        a0, a1 = (self._agent(tmp_path, r, now) for r in (0, 1))
        a0.beat()
        a1.beat()
        now[0] = 0.9
        assert a0.lost_ranks() == []
        now[0] = 1.1
        assert a0.lost_ranks() == [1]
        assert a0.survivors([1]) == [0]

    def test_declare_shrink_writes_durable_intent(self, tmp_path):
        now = [0.0]
        a0 = self._agent(tmp_path, 0, now)
        assert a0.declare_shrink([1], step=7) == [0]
        intent = elastic.read_intent(str(tmp_path), 0)
        assert intent["lost"] == [1] and intent["survivors"] == [0]
        assert intent["step"] == 7 and intent["detected_by"] == 0

    def test_loss_path_fires_observer_once_then_check(self, tmp_path):
        now = [0.0]
        seen = []
        a0 = self._agent(
            tmp_path, 0, now,
            on_lost=lambda lost, sur: seen.append((lost, sur)),
        )
        assert a0.check() == []
        a0._on_peer_lost([1])
        assert seen == [([1], [0])]
        assert a0.check() == [1]

    def test_drop_failpoint_hits_only_its_rank(self, tmp_path):
        from replication_faster_rcnn_tpu.faultlib import failpoints

        now = [0.0]
        deaths = []
        failpoints.configure(
            [failpoints.Rule("heartbeat.beat", "drop", 1.0, 11, arg=1)]
        )
        try:
            a0 = self._agent(tmp_path, 0, now, on_drop=lambda: deaths.append(0))
            a1 = self._agent(tmp_path, 1, now, on_drop=lambda: deaths.append(1))
            a0.beat()  # fires, but arg=1 names the other rank: ignored
            a1.beat()
            assert deaths == [1]
            # the doomed rank never wrote its lease for that beat
            assert elastic.read_plan(str(tmp_path), 0) is None
            lease1 = elastic._read_json(
                elastic.lease_path(str(tmp_path), 0, 1)
            )
            assert lease1 is None
        finally:
            failpoints.disarm()

    def test_thread_lifecycle_stop_wins_grace_race(self, tmp_path):
        """stop() during the exit grace must win: tests and clean
        shutdowns never want the watchdog's os._exit."""
        now = [0.0]
        a0 = elastic.ElasticAgent(
            str(tmp_path), generation=0, rank=0, world=2,
            heartbeat_interval_s=0.01, lease_timeout_s=0.05,
            exit_grace_s=30.0, clock=lambda: now[0], exit_on_shrink=True,
        )
        # plant a stale peer lease, then let the thread find it
        elastic._write_json_atomic(
            elastic.lease_path(str(tmp_path), 0, 1),
            {"rank": 1, "generation": 0, "beat": 0, "t": -10.0},
        )
        a0.start()
        a0.start()  # idempotent
        deadline = threading.Event()
        for _ in range(200):
            if a0.check():
                break
            deadline.wait(0.01)
        assert a0.check() == [1]
        a0.stop()  # beats the 30s grace; process survives to assert this
        assert elastic.read_intent(str(tmp_path), 0)["lost"] == [1]


class TestChildArgv:
    ARGV = [
        "train", "--config", "tiny", "--elastic",
        "--num-processes", "2", "--process-id", "1",
        "--coordinator", "127.0.0.1:9911", "--workdir", "w",
    ]

    def test_reform_rewrites_topology_and_forces_resume(self):
        out = elastic.child_argv(
            self.ARGV, generation=1, rank=0, world=2,
            coordinator="127.0.0.1:9912",
        )
        assert "--elastic" not in out
        assert out[out.index("--num-processes") + 1] == "2"
        assert out[out.index("--process-id") + 1] == "0"
        assert out[out.index("--coordinator") + 1] == "127.0.0.1:9912"
        assert out.count("--resume") == 1

    def test_world_one_drops_distributed_flags_entirely(self):
        out = elastic.child_argv(
            self.ARGV, generation=1, rank=0, world=1, coordinator=None
        )
        for flag in ("--num-processes", "--process-id", "--coordinator"):
            assert flag not in out
        assert "--resume" in out

    def test_equals_form_flags_are_replaced(self):
        argv = ["train", "--elastic", "--num-processes=2", "--process-id=0",
                "--coordinator=h:1", "--workdir", "w"]
        out = elastic.child_argv(
            argv, generation=0, rank=0, world=2, coordinator="h:2"
        )
        assert "--num-processes=2" not in out
        assert out[out.index("--coordinator") + 1] == "h:2"

    def test_gen_zero_preserves_user_resume_without_duplicating(self):
        argv = self.ARGV + ["--resume"]
        out = elastic.child_argv(
            argv, generation=0, rank=1, world=2, coordinator="h:1"
        )
        assert out.count("--resume") == 1

    def test_gen_zero_without_resume_stays_fresh(self):
        out = elastic.child_argv(
            self.ARGV, generation=0, rank=1, world=2, coordinator="h:1"
        )
        assert "--resume" not in out

    def test_multi_process_needs_coordinator(self):
        with pytest.raises(ValueError, match="coordinator"):
            elastic.child_argv(
                self.ARGV, generation=0, rank=0, world=2, coordinator=None
            )


class _FakeProc:
    def __init__(self, rc):
        self.rc = rc

    def wait(self):
        return self.rc


def _supervise(tmp_path, rcs, rank=0, world=2, on_spawn=None, **kw):
    """Run the generation loop against scripted child exit codes."""
    calls = []

    def spawn(**kwargs):
        calls.append(kwargs)
        if on_spawn is not None:
            on_spawn(len(calls) - 1, kwargs)
        return _FakeProc(rcs[min(len(calls) - 1, len(rcs) - 1)])

    kw.setdefault("settle_s", 0.01)
    kw.setdefault("plan_timeout_s", 2.0)
    rc = elastic.run_supervisor(
        spawn, fleet_dir=str(tmp_path), rank=rank, world=world,
        host="127.0.0.1", base_port=9000, log=lambda m: None, **kw,
    )
    return rc, calls


class TestRunSupervisor:
    def test_clean_exit_propagates_zero(self, tmp_path):
        rc, calls = _supervise(tmp_path, [0])
        assert rc == 0 and len(calls) == 1
        assert calls[0]["coordinator"] == "127.0.0.1:9000"

    def test_preemption_passes_through(self, tmp_path):
        rc, calls = _supervise(tmp_path, [EXIT_PREEMPTED])
        assert rc == EXIT_PREEMPTED and len(calls) == 1

    def test_casualty_leaves_fleet_without_claiming(self, tmp_path):
        # a crash with no shrink intent naming us: not a shrink — the
        # injected-dead rank's supervisor resolves exactly this way
        rc, calls = _supervise(tmp_path, [3])
        assert rc == 3 and len(calls) == 1
        assert elastic.read_claims(str(tmp_path), 1, 2) == []

    def test_shrink_reforms_at_world_one(self, tmp_path):
        """Child 0 exits EXIT_FLEET_SHRINK; the dead rank 1 never claims,
        so the survivor plans itself into a 1-rank gen-1 fleet (no
        coordinator at world 1) and finishes there."""
        rc, calls = _supervise(tmp_path, [EXIT_FLEET_SHRINK, 0])
        assert rc == 0 and len(calls) == 2
        g1 = calls[1]
        assert g1["generation"] == 1 and g1["world"] == 1
        assert g1["rank"] == 0 and g1["coordinator"] is None
        plan = elastic.read_plan(str(tmp_path), 1)
        assert plan == {"generation": 1, "survivors": [0], "world": 1}

    def test_intent_naming_survivor_counts_as_shrink(self, tmp_path):
        """A child killed before it could exit 76 (e.g. the coordination
        service's SIGABRT won the race) still re-forms when the durable
        intent names this rank a survivor."""
        def plant_intent(i, kwargs):
            if i == 0:
                elastic._write_json_atomic(
                    elastic.intent_path(str(tmp_path), 0),
                    {"generation": 0, "lost": [1], "survivors": [0],
                     "step": -1, "detected_by": 0},
                )

        rc, calls = _supervise(
            tmp_path, [-6, 0], on_spawn=plant_intent
        )
        assert rc == 0 and len(calls) == 2
        assert calls[1]["world"] == 1

    def test_max_generations_bounds_the_loop(self, tmp_path):
        rc, calls = _supervise(
            tmp_path, [EXIT_FLEET_SHRINK], max_generations=1
        )
        assert rc == EXIT_FLEET_SHRINK and len(calls) == 1

    def test_coordinator_port_bumps_per_generation(self, tmp_path):
        """Two survivors of a 3-rank fleet re-form concurrently: both
        claim, the lowest-ranked claimant arbitrates, ranks renumber
        contiguously and the gen-1 coordinator moves to base_port+1
        (the dead fleet's gloo sockets may still hold the old port)."""
        results = {}
        # Production invariant the instant-exit _FakeProc would otherwise
        # break: no gen-0 child can EXIT before rank 0's supervisor has
        # cleared the fleet dir and spawned its own child (bring-up is a
        # collective), so a peer's re-form claims can never race the
        # startup clear_fleet_dir. Model it: rank 2 starts only after
        # rank 0's first spawn.
        rank0_spawned = threading.Event()

        def run(rank):
            if rank != 0:
                assert rank0_spawned.wait(10)
            rc, calls = _supervise(
                tmp_path / "shared", [EXIT_FLEET_SHRINK, 0],
                rank=rank, world=3, settle_s=0.2,
                on_spawn=lambda i, kw: rank0_spawned.set()
                if rank == 0
                else None,
            )
            results[rank] = (rc, calls)

        threads = [
            threading.Thread(target=run, args=(r,)) for r in (0, 2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert set(results) == {0, 2}
        for rank, (rc, calls) in results.items():
            assert rc == 0 and len(calls) == 2
            g1 = calls[1]
            assert g1["coordinator"] == "127.0.0.1:9001"
            assert g1["world"] == 2
            assert g1["rank"] == {0: 0, 2: 1}[rank]
        plan = elastic.read_plan(str(tmp_path / "shared"), 1)
        assert plan == {"generation": 1, "survivors": [0, 2], "world": 2}


class TestSupervisorStaysOffTheChip:
    def test_supervisor_imports_initialise_no_backend(self):
        """libtpu gives the chip to one process. The `train --elastic`
        supervisor spawns the training child, so everything it imports
        before that — this module, the config, the fault codes — must
        leave JAX's backends uninitialised, or the child it starts would
        find the chip taken."""
        import subprocess
        import sys

        code = (
            "import replication_faster_rcnn_tpu.parallel.elastic\n"
            "from replication_faster_rcnn_tpu.config import get_config\n"
            "get_config('voc_resnet18').elastic\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized(), 'backend up'\n"
            "print('OFF-CHIP-OK')\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert r.returncode == 0 and "OFF-CHIP-OK" in r.stdout, r.stderr[-2000:]
