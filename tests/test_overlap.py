"""Critical-path overlap subsystem tests (PR 4): the double-buffered
device stager, the background checkpoint writer, the compile warm-start
config plumbing, and the step-profile overlap gate.

Everything here is compile-free (stub stage/work callables, synthetic
span streams, pure record logic) — the end-to-end bitwise-parity runs
that compile real train steps live in the slow tier
(tests/test_fault_train.py::TestOverlapParity)."""

import argparse
import dataclasses
import json
import threading
import time

import numpy as np
import pytest

from replication_faster_rcnn_tpu.data.prefetch_device import (
    HOST,
    STAGED,
    DevicePrefetcher,
)
from replication_faster_rcnn_tpu.train.async_checkpoint import (
    AsyncCheckpointWriter,
)


def _batches(n, bs=2):
    return [
        {"idx": np.arange(i * bs, (i + 1) * bs, dtype=np.int32)}
        for i in range(n)
    ]


class TestDevicePrefetcher:
    def test_chunked_order_and_tail(self):
        """chunk=2 over 5 batches: two staged chunks in feed order, then
        the odd tail batch as a HOST item for the per-step path."""
        staged_args = []

        def stage(bs):
            staged_args.append([b["idx"].copy() for b in bs])
            return ("staged", sum(len(b["idx"]) for b in bs))

        items = list(DevicePrefetcher(iter(_batches(5)), stage, chunk=2))
        kinds = [it[0] for it in items]
        assert kinds == [STAGED, STAGED, HOST]
        assert items[0][2] == 2 and items[0][3] == 4  # (kind, obj, k, images)
        assert items[1][2] == 2 and items[1][3] == 4
        np.testing.assert_array_equal(items[2][1]["idx"], [8, 9])
        # staging saw the batches in feed order, nothing duplicated
        flat = [idx for chunk in staged_args for idx in chunk]
        np.testing.assert_array_equal(
            np.concatenate(flat), np.arange(8, dtype=np.int32)
        )

    def test_unchunked_passthrough(self):
        items = list(
            DevicePrefetcher(iter(_batches(3)), lambda bs: len(bs), chunk=1)
        )
        assert [it[0] for it in items] == [STAGED] * 3
        assert all(it[2] == 1 and it[3] == 2 for it in items)

    def test_skip_discards_before_staging(self):
        """The resume-replay prefix must be dropped by the PRODUCER before
        any staging: skipped batches are never staged, never yielded, and
        the first trained batch is exactly feed[skip]."""
        staged = []

        def stage(bs):
            staged.append(bs[0]["idx"].copy())
            return bs[0]["idx"]

        items = list(
            DevicePrefetcher(iter(_batches(6)), stage, chunk=1, skip=4)
        )
        assert len(items) == 2
        np.testing.assert_array_equal(staged[0], [8, 9])
        np.testing.assert_array_equal(staged[1], [10, 11])

    def test_skip_counts_raw_batches_under_chunking(self):
        """skip is in BATCHES (the trainer's replay unit), not chunks —
        an odd replay offset must land mid-chunk correctly."""
        items = list(
            DevicePrefetcher(
                iter(_batches(7)),
                lambda bs: [b["idx"][0] for b in bs],
                chunk=2,
                skip=3,
            )
        )
        # 4 remaining batches -> 2 full chunks, no tail
        assert [it[0] for it in items] == [STAGED, STAGED]
        assert items[0][1] == [6, 8]

    def test_producer_error_reraised_at_consumer(self):
        def bad_stage(bs):
            raise RuntimeError("H2D failed")

        pf = DevicePrefetcher(iter(_batches(3)), bad_stage, chunk=1)
        with pytest.raises(RuntimeError, match="H2D failed"):
            list(pf)

    def test_source_error_reraised_at_consumer(self):
        def gen():
            yield _batches(1)[0]
            raise ValueError("feed died")

        pf = DevicePrefetcher(gen(), lambda bs: bs[0], chunk=1)
        next(pf)
        with pytest.raises(ValueError, match="feed died"):
            next(pf)

    def test_depth_bounds_producer_runahead(self):
        """With a stalled consumer the producer may hold at most `depth`
        staged buffers in the queue (+1 blocked in hand) — the bound that
        keeps double buffering from becoming unbounded HBM growth."""
        staged_count = []
        pf = DevicePrefetcher(
            iter(_batches(10)),
            lambda bs: staged_count.append(1) or len(bs),
            depth=2,
            chunk=1,
        )
        deadline = time.time() + 5.0
        while time.time() < deadline and len(staged_count) < 3:
            time.sleep(0.01)
        time.sleep(0.1)  # would-be overshoot window
        assert 2 <= len(staged_count) <= 3  # depth staged + one in flight
        assert pf.queue_depth() <= 2
        assert sum(1 for _ in pf) == 10
        pf.close()

    def test_close_unblocks_producer_and_is_idempotent(self):
        pf = DevicePrefetcher(
            iter(_batches(50)), lambda bs: len(bs), depth=1, chunk=1
        )
        next(pf)  # producer is now live and blocked on the full queue
        pf.close()
        pf.close()
        assert not pf._thread.is_alive()

    def test_validation(self):
        for kw in ({"depth": 0}, {"chunk": 0}, {"skip": -1}):
            with pytest.raises(ValueError):
                DevicePrefetcher(iter([]), lambda bs: bs, **kw)


class TestAsyncCheckpointWriter:
    def test_completes_in_submission_order(self):
        done = []
        w = AsyncCheckpointWriter()
        gate = threading.Event()

        def slow():
            gate.wait(5.0)
            done.append("a")

        w.submit(1, slow)
        assert w.in_flight
        gate.set()
        # second submit must block until the first landed (in-flight <= 1)
        w.submit(2, lambda: done.append("b"))
        assert done[0] == "a"
        assert w.wait() is None
        assert done == ["a", "b"]
        assert w.last_submitted_step == 2

    def test_error_surfaced_once_then_cleared(self):
        w = AsyncCheckpointWriter()

        def boom():
            raise OSError("disk full")

        assert w.submit(7, boom) is None
        err = w.submit(8, lambda: None)  # prior failure surfaces here
        assert err is not None
        step, exc = err
        assert step == 7 and isinstance(exc, OSError)
        assert w.wait() is None  # slot was cleared; save 8 succeeded
        assert not w.in_flight

    def test_wait_without_submit_is_noop(self):
        w = AsyncCheckpointWriter()
        assert w.wait() is None
        assert w.last_submitted_step is None


class TestConfigKnobs:
    def test_prefetch_device_validated(self):
        from replication_faster_rcnn_tpu.config import DataConfig

        assert DataConfig(prefetch_device=2).prefetch_device == 2
        with pytest.raises(ValueError, match="prefetch_device"):
            DataConfig(prefetch_device=-1)

    def test_compile_cache_dir_validated(self):
        from replication_faster_rcnn_tpu.config import CompileConfig

        assert CompileConfig().cache_dir == ""
        with pytest.raises(ValueError, match="cache_dir"):
            CompileConfig(cache_dir=123)

    def test_round_trip_with_new_sections(self):
        from replication_faster_rcnn_tpu.config import (
            config_from_dict,
            get_config,
        )

        cfg = get_config("voc_resnet18")
        cfg = cfg.replace(
            data=dataclasses.replace(cfg.data, prefetch_device=2),
            train=dataclasses.replace(cfg.train, async_checkpoint=True),
            compile=dataclasses.replace(cfg.compile, cache_dir="/tmp/xc"),
        )
        rt = config_from_dict(json.loads(json.dumps(dataclasses.asdict(cfg))))
        assert rt == cfg

    def test_dict_from_older_binary_tolerated(self):
        """A checkpointed config predating the `compile` section (or any
        future key) must still rebuild — resume across the PR boundary."""
        from replication_faster_rcnn_tpu.config import (
            config_from_dict,
            get_config,
        )

        d = dataclasses.asdict(get_config("voc_resnet18"))
        d.pop("compile")
        d["data"].pop("prefetch_device")
        cfg = config_from_dict(d)
        assert cfg.compile.cache_dir == ""
        assert cfg.data.prefetch_device == 0


class TestCompileCachePlacement:
    """`train/warmup.py::place_compile_cache` is the one owner of where
    the persistent compilation cache lives."""

    @pytest.fixture
    def updates(self, monkeypatch):
        """Record jax.config.update calls instead of applying them."""
        import jax

        seen = {}
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: seen.__setitem__(k, v)
        )
        # the in-checkout default is for accelerators; see the CPU test
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        return seen

    def test_env_places_it_and_flag_and_config_do_not_move_it(
        self, updates, monkeypatch, tmp_path
    ):
        from replication_faster_rcnn_tpu.train import warmup

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        used = warmup.place_compile_cache(str(tmp_path / "flag"))
        assert used == str(tmp_path / "env")
        # JAX read the variable itself; no directory is set in code
        assert "jax_compilation_cache_dir" not in updates
        assert not (tmp_path / "flag").exists()
        # cheap programs persist too
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1

    def test_a_hit_is_this_sources_own_program(self, updates, monkeypatch, tmp_path):
        """Name scopes are part of the key: an executable another commit
        cached carries that commit's `op_name`s, and a trace of it would be
        cut by stages this program does not have. Source locations are not:
        a nested jit keeps the call stack of whichever program traced it
        first, so a key with them would hang on what the process compiled
        before (`tests/test_executable_store.py` shows both)."""
        import jax

        from replication_faster_rcnn_tpu.train import warmup

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        warmup.place_compile_cache(str(tmp_path / "flag"))
        assert updates["jax_compilation_cache_include_metadata_in_key"] is True
        assert updates["jax_traceback_in_locations_limit"] == 0
        updates.clear()
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        warmup.place_compile_cache()
        assert updates["jax_compilation_cache_include_metadata_in_key"] is True
        assert updates["jax_traceback_in_locations_limit"] == 0
        # both options exist on the installed jax, under these names
        for name in updates:
            assert hasattr(jax.config, name), name

    def test_unset_env_uses_the_fixed_path_in_the_checkout(
        self, updates, monkeypatch
    ):
        import os

        from replication_faster_rcnn_tpu.train import warmup

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        used = warmup.place_compile_cache()
        assert used == os.path.join(repo, ".compile_cache")
        assert updates["jax_compilation_cache_dir"] == used
        assert warmup.place_compile_cache() == used  # fixed, not per call
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".compile_cache/" in f.read().split()

    def test_unset_env_honours_the_config_field(
        self, updates, monkeypatch, tmp_path
    ):
        from replication_faster_rcnn_tpu.train import warmup

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        used = warmup.place_compile_cache(str(tmp_path / "flag"))
        assert used == updates["jax_compilation_cache_dir"]
        assert used == str(tmp_path / "flag")

    def test_cpu_backend_keeps_no_cache_unless_the_env_asks(
        self, updates, monkeypatch, tmp_path
    ):
        import jax

        from replication_faster_rcnn_tpu.train import warmup

        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert warmup.place_compile_cache(str(tmp_path / "flag")) is None
        assert updates == {"jax_enable_compilation_cache": False}
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert warmup.place_compile_cache() == str(tmp_path / "env")

    def test_one_call_site_sets_the_directory(self):
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        hits = []
        for top in ("replication_faster_rcnn_tpu", "benchmarks", "."):
            for root, dirs, files in os.walk(os.path.join(repo, top)):
                if top == ".":
                    dirs.clear()  # the root-level scripts only
                for name in files:
                    if name.endswith(".py"):
                        with open(os.path.join(root, name)) as f:
                            if "jax_compilation_cache_dir" in f.read():
                                hits.append(name)
        assert hits == ["warmup.py"]


class TestCLI:
    def _parse(self, argv):
        from replication_faster_rcnn_tpu import cli

        p = argparse.ArgumentParser()
        cli._add_common(p)
        return cli._build_config(p.parse_args(argv))

    def test_new_flags_map_to_config(self):
        cfg = self._parse(
            [
                "--prefetch-device", "3",
                "--async-checkpoint",
                "--compile-cache", "/tmp/frcnn-xla-cache",
            ]
        )
        assert cfg.data.prefetch_device == 3
        assert cfg.train.async_checkpoint is True
        assert cfg.compile.cache_dir == "/tmp/frcnn-xla-cache"

    def test_defaults_leave_config_untouched(self):
        from replication_faster_rcnn_tpu.config import get_config

        assert self._parse([]) == get_config("voc_resnet18")

    def test_warmup_subcommand_registered(self):
        from replication_faster_rcnn_tpu import cli

        with pytest.raises(SystemExit) as e:
            cli.main(["warmup", "--no-such-flag"])
        assert e.value.code == 2  # argparse rejected the flag, not the cmd


class TestMfuFallback:
    def test_numpy_matmul_fallback(self, monkeypatch):
        """When the jitted matmul path is unavailable the measured-CPU
        basis must come from a numpy matmul, not collapse to None — the
        bench now exits 3 on a null-MFU CPU record, so a degraded host
        needs this to stay green."""
        import jax

        from replication_faster_rcnn_tpu.telemetry import mfu

        monkeypatch.delenv("FRCNN_CPU_PEAK_FLOPS", raising=False)
        monkeypatch.setattr(mfu, "_cpu_peak_cache", None)

        def broken_jit(*a, **kw):
            raise RuntimeError("backend wedged")

        monkeypatch.setattr(jax, "jit", broken_jit)
        peak = mfu.measured_cpu_peak_flops_per_sec(n=64, iters=2)
        assert peak is not None and peak > 0
        monkeypatch.setattr(mfu, "_cpu_peak_cache", None)  # don't poison


class TestStepProfileOverlapGate:
    def _rec(self, ips=100.0, overlap=None, blocked_frac=None):
        import step_profile as sp

        rec = {
            "schema": sp.SCHEMA,
            "images_per_sec": ips,
            "phases": {},
        }
        if overlap is not None or blocked_frac is not None:
            rec["overlap"] = {
                "overlap_fraction": overlap,
                "host_blocked_frac_of_dispatch": blocked_frac,
            }
        return rec

    @pytest.fixture(autouse=True)
    def _path(self, monkeypatch):
        import os
        import sys

        monkeypatch.syspath_prepend(
            os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")
        )
        yield
        sys.modules.pop("step_profile", None)

    def test_overlap_regression_fails(self):
        import step_profile as sp

        failures, _ = sp.check_regression(
            self._rec(overlap=0.5), self._rec(overlap=0.9)
        )
        assert any("overlap_fraction" in f for f in failures)

    def test_overlap_within_tol_passes(self):
        import step_profile as sp

        failures, _ = sp.check_regression(
            self._rec(overlap=0.85), self._rec(overlap=0.9)
        )
        assert not failures

    def test_records_without_overlap_section_skip_gate(self):
        import step_profile as sp

        failures, _ = sp.check_regression(
            self._rec(overlap=None), self._rec(overlap=0.9)
        )
        assert not failures
        failures, _ = sp.check_regression(
            self._rec(overlap=0.1), self._rec(overlap=None)
        )
        assert not failures

    def test_noise_floor_fraction_skips_relative_gate(self):
        # banked 0.12 is quotient-of-noise (millisecond staging on CPU);
        # a 100% relative drop there must not fail the check
        import step_profile as sp

        failures, _ = sp.check_regression(
            self._rec(overlap=0.0), self._rec(overlap=0.12)
        )
        assert not failures

    def test_host_blocked_frac_absolute_gate(self):
        import step_profile as sp

        # under the 0.10 floor: fine even if well above the banked value
        failures, _ = sp.check_regression(
            self._rec(blocked_frac=0.08), self._rec(blocked_frac=0.002)
        )
        assert not failures
        # above the floor AND above banked+tol: the acceptance number broke
        failures, _ = sp.check_regression(
            self._rec(blocked_frac=0.40), self._rec(blocked_frac=0.002)
        )
        assert any("host_blocked_frac_of_dispatch" in f for f in failures)
        # a banked-high record tolerates tol growth but not more
        failures, _ = sp.check_regression(
            self._rec(blocked_frac=0.50), self._rec(blocked_frac=0.45)
        )
        assert not failures
        failures, _ = sp.check_regression(
            self._rec(blocked_frac=0.60), self._rec(blocked_frac=0.45)
        )
        assert any("host_blocked_frac_of_dispatch" in f for f in failures)


class TestReportOverlapSummary:
    def _span(self, name, tid, dur_us=1000):
        return {"ph": "X", "name": name, "tid": tid, "dur": dur_us, "ts": 0}

    def test_blocked_vs_overlapped_attribution(self):
        from replication_faster_rcnn_tpu.telemetry.report import (
            overlap_summary,
        )

        events = [
            self._span("step/dispatch", tid=1, dur_us=10_000),
            self._span("data/fetch", tid=1, dur_us=2_000),  # blocked
            self._span("data/device_put", tid=2, dur_us=3_000),  # stager
        ]
        s = overlap_summary(events)
        assert s["dispatch_total_ms"] == 10.0
        assert s["host_blocked_ms"] == 2.0
        assert s["overlapped_ms"] == 3.0
        assert s["host_blocked_frac_of_dispatch"] == 0.2

    def test_none_without_dispatch_spans(self):
        from replication_faster_rcnn_tpu.telemetry.report import (
            overlap_summary,
        )

        assert overlap_summary([self._span("data/fetch", tid=1)]) is None


class TestPredictEvaluatorCache:
    def test_get_evaluator_cached_per_config_and_model(self):
        from replication_faster_rcnn_tpu.config import (
            DataConfig,
            FasterRCNNConfig,
            ModelConfig,
        )
        from replication_faster_rcnn_tpu.eval import predict
        from replication_faster_rcnn_tpu.models.faster_rcnn import FasterRCNN

        cfg = FasterRCNNConfig(
            model=ModelConfig(backbone="resnet18", roi_op="align"),
            data=DataConfig(dataset="synthetic", image_size=(64, 64)),
        )
        model = FasterRCNN(cfg)
        ev1 = predict.get_evaluator(cfg, model)
        ev2 = predict.get_evaluator(cfg, model)
        assert ev1 is ev2  # repeated predict_image calls reuse the jit
        other_model = FasterRCNN(cfg)
        assert predict.get_evaluator(cfg, other_model) is not ev1
        cfg2 = cfg.replace(
            eval=dataclasses.replace(cfg.eval, score_thresh=0.9)
        )
        assert predict.get_evaluator(cfg2, other_model) is not ev2
