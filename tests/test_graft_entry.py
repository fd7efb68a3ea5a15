"""The driver gate (`__graft_entry__.dryrun_multichip`) is a CPU
virtual-mesh check: it runs its body in a child pinned to the CPU backend
whatever the caller's environment says, so it never needs (or takes) an
accelerator the caller may hold. These tests pin that cheaply; the slow
leg runs the real body on two virtual devices.
"""

import os
import subprocess
import sys

import pytest


def _load_graft_entry():
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo_root)
    try:
        import __graft_entry__  # noqa: F401

        return __graft_entry__
    finally:
        sys.path.pop(0)


class TestDryrunIsolation:
    def test_parent_spawns_cpu_pinned_child(self, monkeypatch):
        g = _load_graft_entry()
        captured = {}

        def fake_run(cmd, **kwargs):
            captured["cmd"] = cmd
            captured.update(kwargs)
            return subprocess.CompletedProcess(cmd, 0)

        monkeypatch.setattr(subprocess, "run", fake_run)
        # a caller on an accelerator: the child must not follow it there
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        monkeypatch.setenv("PYTHONOPTIMIZE", "2")  # would strip child asserts

        g.dryrun_multichip(8)

        env = captured["env"]
        assert env["JAX_PLATFORMS"] == "cpu"
        assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
        assert "PYTHONOPTIMIZE" not in env  # child asserts must survive -O
        # child must run from the repo dir so `import __graft_entry__` works
        assert captured["cwd"] == os.path.dirname(
            os.path.abspath(g.__file__)
        )
        assert captured["cmd"][0] == sys.executable
        assert "-u" in captured["cmd"]
        assert "_dryrun_body(8)" in captured["cmd"][-1]

    def test_child_failure_raises(self, monkeypatch):
        g = _load_graft_entry()
        monkeypatch.setattr(
            subprocess,
            "run",
            lambda cmd, **kw: subprocess.CompletedProcess(cmd, 17),
        )
        with pytest.raises(RuntimeError, match="rc=17"):
            g.dryrun_multichip(8)


class TestLossAgreement:
    """The gate asserts dp-vs-shard_map agreement: its output is an
    equivalence proof, not just finiteness."""

    def test_within_tolerance_returns_delta(self):
        g = _load_graft_entry()
        assert g._assert_losses_agree(6.2559, 6.2557) == pytest.approx(2e-4)
        # tol floor of 1.0 keeps tiny losses from demanding absurd precision
        assert g._assert_losses_agree(1e-4, 2e-4) == pytest.approx(1e-4)

    def test_disagreement_raises(self):
        g = _load_graft_entry()
        # ValueError, not assert: the check must survive python -O
        with pytest.raises(ValueError, match="disagree"):
            g._assert_losses_agree(6.25, 6.27)

    @pytest.mark.slow
    def test_dryrun_body_end_to_end_two_devices(self):
        """Real gate body on a 2-device mesh: the agreement assert runs
        against actually-computed losses and the tail line carries the
        delta. Spatial leg skipped to keep this to two step compiles."""
        g = _load_graft_entry()
        repo = os.path.dirname(os.path.abspath(g.__file__))
        # the production child env, not a hand-copied one — drift-proof
        env = g._cpu_child_env(2)
        env["FRCNN_DRYRUN_FULL"] = "0"
        proc = subprocess.run(
            [sys.executable, "-u", "-c",
             "import __graft_entry__ as g; g._dryrun_body(2)"],
            env=env, cwd=repo, capture_output=True, text=True, timeout=480,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "(delta " in proc.stdout and "OK" in proc.stdout


class TestEntryDoesNotProbe:
    def test_entry_starts_no_process_and_keeps_the_platform(self, monkeypatch):
        """entry() builds the program on the backend JAX is on: no probe
        child (it would take the chip from this process) and no switch
        of jax_platforms."""
        import jax

        g = _load_graft_entry()

        def boom(*a, **k):
            raise AssertionError("entry() must not start a process")

        monkeypatch.setattr(subprocess, "run", boom)
        monkeypatch.setattr(subprocess, "Popen", boom)
        before = jax.config.jax_platforms
        fn, args = g.entry()
        assert jax.config.jax_platforms == before
        assert args[0].shape == (1, 600, 600, 3) and callable(fn)
