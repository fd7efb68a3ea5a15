"""Serving engine (ISSUE 7 tentpole): bucketed AOT programs + continuous
micro-batching.

Pure tests pin the MicroBatcher contract (size/deadline flush triggers,
bounded-queue backpressure, drain-on-close, error relay), bucket routing
(snug-bucket selection, oversize downscale/reject), ServingConfig
validation, and the serving_profile regression-gate arithmetic — no JAX
compiles. The live module then compiles ONE 32x32 bucket (batches 1 and
2) and proves the acceptance claims end-to-end: engine detections are
bitwise-identical to `Evaluator.predict_batch`, concurrent submits
coalesce into shared flushes, partial batches pad-to-bucket and un-pad,
boxes de-normalize to original coordinates, and a strict session over
warm dispatches sees 0 implicit transfers and 0 recompiles.
"""

import dataclasses
import json
import os
import queue
import sys
import threading
import time

import numpy as np
import pytest

from replication_faster_rcnn_tpu.config import (
    DataConfig,
    EvalConfig,
    FasterRCNNConfig,
    MeshConfig,
    ModelConfig,
    ProposalConfig,
    ROITargetConfig,
    ServingConfig,
    TrainConfig,
    config_from_dict,
)
from replication_faster_rcnn_tpu.serving import (
    InferenceEngine,
    MicroBatcher,
    OversizedImageError,
    select_bucket,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ micro-batcher


class TestMicroBatcher:
    def test_size_trigger_flushes_full_batch(self):
        with MicroBatcher(lambda k, items: [x * 10 for x in items],
                          max_batch=3, max_delay_s=30.0) as mb:
            futs = [mb.submit("k", i) for i in range(3)]
            # size trigger: resolves promptly despite the huge deadline
            assert [f.result(timeout=5) for f in futs] == [0, 10, 20]
            assert mb.flush_log == [("k", 3)]

    def test_deadline_flushes_partial_group(self):
        with MicroBatcher(lambda k, items: list(items),
                          max_batch=8, max_delay_s=0.05) as mb:
            fut = mb.submit("k", "lone")
            assert fut.result(timeout=5) == "lone"
            assert mb.flush_log == [("k", 1)]

    def test_groups_key_separately(self):
        with MicroBatcher(lambda k, items: [(k, x) for x in items],
                          max_batch=2, max_delay_s=30.0) as mb:
            fa = [mb.submit("a", i) for i in range(2)]
            fb = [mb.submit("b", i) for i in range(2)]
            assert [f.result(timeout=5) for f in fa] == [("a", 0), ("a", 1)]
            assert [f.result(timeout=5) for f in fb] == [("b", 0), ("b", 1)]
            assert ("a", 2) in mb.flush_log and ("b", 2) in mb.flush_log

    def test_bounded_queue_backpressure(self):
        release = threading.Event()

        def slow(k, items):
            release.wait(10)
            return list(items)

        mb = MicroBatcher(slow, max_batch=1, max_delay_s=0.0, depth=2)
        try:
            futs = [mb.submit("k", 0)]  # worker takes this and blocks
            deadline = time.monotonic() + 5
            # fill the queue to depth (the worker may drain one entry
            # into its pending group before blocking, so keep topping up)
            while time.monotonic() < deadline:
                try:
                    futs.append(mb.submit("k", 1, timeout=0.05))
                except queue.Full:
                    break
            else:
                pytest.fail("queue never filled")
            with pytest.raises(queue.Full):
                mb.submit("k", 2, timeout=0.05)
        finally:
            release.set()
            mb.close()
        assert all(f.result(timeout=5) in (0, 1) for f in futs)

    def test_close_drains_accepted_requests(self):
        with MicroBatcher(lambda k, items: list(items),
                          max_batch=100, max_delay_s=30.0) as mb:
            futs = [mb.submit("k", i) for i in range(5)]
        # close flushed the partial group (5 < max_batch, before deadline)
        assert [f.result(timeout=1) for f in futs] == list(range(5))

    def test_submit_after_close_raises(self):
        mb = MicroBatcher(lambda k, items: list(items), max_batch=1)
        mb.close()
        with pytest.raises(RuntimeError, match="closed"):
            mb.submit("k", 1)
        mb.close()  # idempotent

    def test_error_relays_to_flush_futures_and_worker_survives(self):
        def process(k, items):
            if "boom" in items:
                raise ValueError("exploded")
            return list(items)

        with MicroBatcher(process, max_batch=2, max_delay_s=30.0) as mb:
            bad = [mb.submit("k", "boom"), mb.submit("k", "x")]
            with pytest.raises(ValueError, match="exploded"):
                bad[0].result(timeout=5)
            with pytest.raises(ValueError):
                bad[1].result(timeout=5)
            # the worker keeps serving after a failed flush
            good = [mb.submit("k", 1), mb.submit("k", 2)]
            assert [f.result(timeout=5) for f in good] == [1, 2]

    def test_result_count_mismatch_fails_flush(self):
        with MicroBatcher(lambda k, items: [1], max_batch=2,
                          max_delay_s=30.0) as mb:
            futs = [mb.submit("k", i) for i in range(2)]
            with pytest.raises(RuntimeError, match="2 items"):
                futs[0].result(timeout=5)

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="max_batch"):
            MicroBatcher(lambda k, i: i, max_batch=0)
        with pytest.raises(ValueError, match="max_delay_s"):
            MicroBatcher(lambda k, i: i, max_batch=1, max_delay_s=-1)
        with pytest.raises(ValueError, match="depth"):
            MicroBatcher(lambda k, i: i, max_batch=1, depth=0)


class TestMicroBatcherDeterministic:
    """Worker-loop ordering audited with injected time (``clock``) and a
    thread-free drive (``start=False`` + ``_service_once``) — no sleeps,
    no scheduler races (ISSUE 8 satellite: deadline-flush and
    ``_CLOSE``-drain audit)."""

    def _mb(self, clock, **kw):
        kw.setdefault("max_batch", 100)
        kw.setdefault("max_delay_s", 1.0)
        return MicroBatcher(
            lambda k, items: list(items), clock=clock, start=False, **kw
        )

    def test_hot_key_backlog_cannot_starve_other_deadlines(self):
        """The deadline scan runs on EVERY iteration. Before the fix it
        ran only when the queue read timed out, so a sustained backlog on
        one key deferred every other key's deadline flush indefinitely."""
        now = [0.0]
        mb = self._mb(lambda: now[0])
        cold = mb.submit("cold", "victim")
        for i in range(8):
            mb.submit("hot", i)  # backlog: the get never goes Empty
        now[0] = 2.0  # cold's deadline long past
        # one iteration consumes ONE hot entry — and must still flush cold
        assert mb._service_once(block=False)
        assert ("cold", 1) in mb.flush_log
        assert cold.result(timeout=0) == "victim"
        assert mb.queue_depth() > 0  # hot backlog still queued; no starving
        mb.close()

    def test_deadline_is_measured_from_oldest_entry_of_group(self):
        now = [0.0]
        mb = self._mb(lambda: now[0])
        mb.submit("k", "old")
        assert mb._service_once(block=False)  # into pending at t=0
        now[0] = 0.9
        mb.submit("k", "young")  # same group, later arrival
        assert mb._service_once(block=False)
        assert mb.flush_log == []  # 0.9 < 1.0: not due yet
        now[0] = 1.05  # oldest entry (t=0) is now past max_delay_s
        assert mb._service_once(block=False)
        assert mb.flush_log == [("k", 2)]
        mb.close()

    def test_close_sentinel_flushes_all_pending_groups(self):
        now = [0.0]
        mb = self._mb(lambda: now[0])
        fa, fb = mb.submit("a", 1), mb.submit("b", 2)
        mb.close()  # threadless: drains inline through _service_once
        assert fa.result(timeout=0) == 1 and fb.result(timeout=0) == 2
        assert sorted(mb.flush_log) == [("a", 1), ("b", 1)]

    def test_close_on_full_queue_makes_room_inline(self):
        """The sentinel must get a slot even when the queue is at depth
        and no worker thread exists to drain it."""
        now = [0.0]
        mb = self._mb(lambda: now[0], depth=2)
        futs = [mb.submit("k", i) for i in range(2)]  # queue full
        mb.close()  # put(_CLOSE) hits queue.Full -> inline service
        assert [f.result(timeout=0) for f in futs] == [0, 1]

    def test_poll_hook_runs_every_iteration(self):
        beats = []
        now = [0.0]
        mb = MicroBatcher(
            lambda k, items: list(items),
            max_batch=100,
            max_delay_s=1.0,
            clock=lambda: now[0],
            start=False,
            poll_hook=lambda: beats.append(now[0]),
        )
        mb.submit("k", 1)
        mb._service_once(block=False)
        now[0] = 5.0
        mb._service_once(block=False)
        assert beats == [0.0, 5.0]
        mb.close()

    def test_size_trigger_beats_deadline_under_injected_clock(self):
        now = [0.0]
        mb = self._mb(lambda: now[0], max_batch=2)
        mb.submit("k", 1)
        mb.submit("k", 2)
        mb._service_once(block=False)
        assert mb.flush_log == []  # one entry in pending: below size
        mb._service_once(block=False)
        assert mb.flush_log == [("k", 2)]  # size trigger, clock untouched
        mb.close()

    def test_on_expired_reports_dropped_count_deterministically(self):
        """ISSUE 14 satellite: the shed-accounting hooks audited under
        injected time — no live engine, no scheduler in the loop."""
        now = [0.0]
        expired_counts = []
        mb = MicroBatcher(
            lambda k, items: list(items), max_batch=100, max_delay_s=10.0,
            clock=lambda: now[0], start=False,
            on_expired=expired_counts.append,
        )
        doomed = [mb.submit("k", i, deadline_s=1.0) for i in range(2)]
        live = mb.submit("k", "survivor", deadline_s=50.0)
        for _ in range(3):  # stage all three into the pending group
            assert mb._service_once(block=False)
        now[0] = 11.0  # group deadline AND the 1s TTLs are past
        assert mb._service_once(block=False)
        assert expired_counts == [2]  # one flush, both expired entries
        assert mb.expired_total == 2
        for f in doomed:
            with pytest.raises(Exception, match="deadline"):
                f.result(timeout=0)
        assert live.result(timeout=0) == "survivor"
        assert mb.flush_log == [("k", 1)]  # only the live entry dispatched
        mb.close()

    def test_on_flush_result_reports_ok_and_failure_in_order(self):
        now = [0.0]
        outcomes = []

        def process(k, items):
            if "boom" in items:
                raise ValueError("exploded")
            return list(items)

        mb = MicroBatcher(
            process, max_batch=1, max_delay_s=1.0,
            clock=lambda: now[0], start=False,
            on_flush_result=outcomes.append,
        )
        mb.submit("k", "fine")
        mb.submit("k", "boom")
        mb.submit("k", "fine2")
        for _ in range(3):
            mb._service_once(block=False)
        assert outcomes == [True, False, True]
        mb.close()

    def test_all_expired_flush_skips_process_and_flush_result(self):
        """A flush whose every entry expired dispatches nothing — so
        ``on_flush_result`` must not fire (no process outcome to score),
        while ``on_expired`` still reports the drop."""
        now = [0.0]
        outcomes, expired_counts = [], []
        mb = MicroBatcher(
            lambda k, items: list(items), max_batch=100, max_delay_s=1.0,
            clock=lambda: now[0], start=False,
            on_expired=expired_counts.append,
            on_flush_result=outcomes.append,
        )
        mb.submit("k", "late", deadline_s=0.5)
        now[0] = 2.0
        assert mb._service_once(block=False)
        assert expired_counts == [1] and outcomes == []
        assert mb.flush_log == []  # nothing reached process
        mb.close()

    def test_on_flush_stats_reports_per_entry_queue_waits(self):
        now = [0.0]
        stats = []
        mb = MicroBatcher(
            lambda k, items: list(items), max_batch=2, max_delay_s=10.0,
            clock=lambda: now[0], start=False,
            on_flush_stats=lambda k, waits: stats.append((k, waits)),
        )
        mb.submit("k", 1)
        now[0] = 0.3
        mb.submit("k", 2)
        now[0] = 0.5
        mb._service_once(block=False)
        mb._service_once(block=False)
        assert stats == [("k", [0.5, 0.2])]  # waits from each submit time
        mb.close()

    def test_key_depths_gauge_tracks_submit_to_flush(self):
        now = [0.0]
        mb = self._mb(lambda: now[0], max_batch=2)
        mb.submit("a", 1)
        mb.submit("b", 2)
        assert mb.key_depths() == {"a": 1, "b": 1}
        mb.submit("a", 3)
        assert mb.key_depths()["a"] == 2
        mb._service_once(block=False)  # a:1 -> pending
        mb._service_once(block=False)  # b:1 -> pending
        mb._service_once(block=False)  # a:2 -> size-trigger flush
        assert mb.key_depths() == {"b": 1}  # a's entries flushed out
        mb.close()  # drain flushes b
        assert mb.key_depths() == {}

    def test_per_key_max_delay_callable_sets_independent_deadlines(self):
        now = [0.0]
        delays = {"slow": 5.0, "fast": 0.5}
        mb = MicroBatcher(
            lambda k, items: list(items), max_batch=100,
            max_delay_s=lambda k: delays[k],
            clock=lambda: now[0], start=False,
        )
        mb.submit("slow", 1)
        mb.submit("fast", 2)
        mb._service_once(block=False)
        mb._service_once(block=False)
        assert mb.delay_s("slow") == 5.0 and mb.delay_s("fast") == 0.5
        now[0] = 0.6  # fast's deadline only
        mb._service_once(block=False)
        assert mb.flush_log == [("fast", 1)]
        now[0] = 5.1
        mb._service_once(block=False)
        assert mb.flush_log == [("fast", 1), ("slow", 1)]
        mb.close()


# ------------------------------------------------- SLO deadline controller


class TestDeadlineController:
    """ISSUE 14 satellite: per-bucket max_delay adaptation from observed
    queue waits — bounded multiplicative steps inside [floor, ceiling]."""

    def _dc(self, **kw):
        from replication_faster_rcnn_tpu.serving.slo import DeadlineController

        kw.setdefault("slo_ms", 100.0)
        kw.setdefault("floor_ms", 1.0)
        kw.setdefault("ceiling_ms", 50.0)
        kw.setdefault("step", 2.0)
        kw.setdefault("initial_ms", 10.0)
        kw.setdefault("window", 4)
        return DeadlineController(**kw)

    def test_shrinks_when_wait_p99_nears_the_slo(self):
        dc = self._dc()
        dc.on_flush("b", [0.090] * 4)  # 90ms > 0.8 x 100ms
        assert dc.delay_s("b") == pytest.approx(0.005)  # 10 / step
        assert dc.adaptations == 1

    def test_grows_only_with_slo_headroom_and_partial_flushes(self):
        dc = self._dc(max_batch=lambda k: 8)
        dc.on_flush("b", [0.010] * 4)  # partial (4 < 8), p99 well under
        assert dc.delay_s("b") == pytest.approx(0.020)  # 10 x step
        # full flushes: a longer deadline buys nothing -> no growth
        dc2 = self._dc(max_batch=lambda k: 4)
        dc2.on_flush("b", [0.010] * 4)  # full batch
        assert dc2.delay_s("b") == pytest.approx(0.010)
        assert dc2.adaptations == 0

    def test_dead_zone_keeps_deadline_stable(self):
        dc = self._dc()
        dc.on_flush("b", [0.060] * 4)  # 0.4 < 0.6 < 0.8 of the SLO
        assert dc.delay_s("b") == pytest.approx(0.010)
        assert dc.adaptations == 0

    def test_clamped_to_floor_and_ceiling(self):
        dc = self._dc(initial_ms=2.0)
        for _ in range(8):
            dc.on_flush("b", [0.095] * 4)  # shrink every window
        assert dc.delay_s("b") == pytest.approx(0.001)  # floor, not 2/2^8
        dc = self._dc(initial_ms=40.0)
        for _ in range(8):
            dc.on_flush("b", [0.001] * 4)
        assert dc.delay_s("b") == pytest.approx(0.050)  # ceiling

    def test_adapts_once_per_window_not_per_flush(self):
        dc = self._dc(window=8)
        dc.on_flush("b", [0.090] * 4)  # 4 of 8 samples
        assert dc.adaptations == 0
        dc.on_flush("b", [0.090] * 4)  # window reached
        assert dc.adaptations == 1

    def test_keys_adapt_independently(self):
        dc = self._dc()
        dc.on_flush("hot", [0.090] * 4)
        dc.on_flush("idle", [0.002] * 4)
        assert dc.delay_s("hot") == pytest.approx(0.005)
        assert dc.delay_s("idle") == pytest.approx(0.020)
        assert set(dc.delays_ms()) == {"hot", "idle"}

    def test_from_config_maps_serving_knobs(self):
        from replication_faster_rcnn_tpu.serving.slo import DeadlineController

        serving = ServingConfig(
            max_delay_ms=8.0, adaptive_slo_ms=200.0, delay_floor_ms=2.0,
            delay_ceiling_ms=32.0, adaptive_delay_step=2.0,
        )
        dc = DeadlineController.from_config(serving, window=4)
        assert dc.delay_s("any") == pytest.approx(0.008)
        dc.on_flush("b", [0.190] * 4)  # p99 over 0.8 x 200ms
        assert dc.delay_s("b") == pytest.approx(0.004)

    def test_validation(self):
        with pytest.raises(ValueError, match="floor_ms"):
            self._dc(floor_ms=0.0)
        with pytest.raises(ValueError, match="step"):
            self._dc(step=1.0)
        with pytest.raises(ValueError, match="slo_ms"):
            self._dc(slo_ms=0.0)
        with pytest.raises(ValueError, match="window"):
            self._dc(window=0)

    def test_drives_microbatcher_deadlines_through_the_callable_seam(self):
        """Controller + batcher closed loop under injected time: a
        shrink decided at flush N binds the deadline of flush N+1."""
        now = [0.0]
        dc = self._dc(window=2)
        mb = MicroBatcher(
            lambda k, items: list(items), max_batch=100,
            max_delay_s=dc.delay_s, clock=lambda: now[0], start=False,
            on_flush_stats=dc.on_flush,
        )
        f1, f2 = mb.submit("b", 1), mb.submit("b", 2)
        mb._service_once(block=False)
        mb._service_once(block=False)
        now[0] = 0.090  # the pair waits 90ms -> deadline flush + shrink
        mb._service_once(block=False)
        assert f1.result(timeout=0) == 1 and f2.result(timeout=0) == 2
        assert mb.delay_s("b") == pytest.approx(0.005)  # adapted live
        mb.submit("b", 3)
        mb._service_once(block=False)
        now[0] = 0.096  # 6ms later: past the NEW 5ms deadline, not 10ms
        mb._service_once(block=False)
        assert mb.flush_log == [("b", 2), ("b", 1)]
        mb.close()


# ---------------------------------------------------------- bucket routing


class TestSelectBucket:
    BUCKETS = ((32, 32), (64, 64))

    def test_snug_bucket_wins(self):
        assert select_bucket(self.BUCKETS, 20, 30) == (32, 32)
        assert select_bucket(self.BUCKETS, 33, 10) == (64, 64)
        assert select_bucket(self.BUCKETS, 64, 64) == (64, 64)

    def test_oversize_downscale_routes_to_largest(self):
        assert select_bucket(self.BUCKETS, 100, 100, "downscale") == (64, 64)

    def test_oversize_reject_raises(self):
        with pytest.raises(OversizedImageError, match="100x100"):
            select_bucket(self.BUCKETS, 100, 100, "reject")

    def test_no_resolutions_raises(self):
        with pytest.raises(ValueError, match="no serving resolutions"):
            select_bucket((), 10, 10)


# ---------------------------------------------------------- serving config


class TestServingConfig:
    def test_defaults_derive_full_and_half_buckets(self):
        sc = ServingConfig()
        assert sc.bucket_resolutions((600, 600)) == ((300, 300), (600, 600))

    def test_explicit_resolutions_sorted_by_area(self):
        sc = ServingConfig(resolutions=((64, 64), (32, 32)))
        assert sc.bucket_resolutions((600, 600)) == ((32, 32), (64, 64))

    def test_validation(self):
        with pytest.raises(ValueError, match="batch_sizes"):
            ServingConfig(batch_sizes=())
        with pytest.raises(ValueError, match="batch_sizes"):
            ServingConfig(batch_sizes=(0,))
        with pytest.raises(ValueError, match="max_delay_ms"):
            ServingConfig(max_delay_ms=-1)
        with pytest.raises(ValueError, match="queue_depth"):
            ServingConfig(queue_depth=0)
        with pytest.raises(ValueError, match="params_dtype"):
            ServingConfig(params_dtype="float99")
        with pytest.raises(ValueError, match="oversize"):
            ServingConfig(oversize="explode")

    def test_config_from_dict_round_trip(self):
        cfg = FasterRCNNConfig(
            serving=ServingConfig(
                resolutions=((32, 32),), batch_sizes=(1, 4),
                max_delay_ms=5.0, params_dtype="float32",
            )
        )
        rebuilt = config_from_dict(
            json.loads(json.dumps(dataclasses.asdict(cfg)))
        )
        assert rebuilt == cfg

    def test_config_from_dict_without_serving_key_uses_default(self):
        d = dataclasses.asdict(FasterRCNNConfig())
        d.pop("serving")
        assert config_from_dict(d).serving == ServingConfig()


# ------------------------------------------------------- program registry


class TestServingSpecs:
    def test_names_and_specs_cover_the_bucket_matrix(self):
        from replication_faster_rcnn_tpu.train.warmup import (
            build_serving_specs,
            serve_program_name,
            serving_program_names,
        )

        cfg = FasterRCNNConfig(
            data=DataConfig(dataset="synthetic", image_size=(64, 64)),
            serving=ServingConfig(
                resolutions=((32, 32), (64, 64)), batch_sizes=(1, 2)
            ),
        )
        assert serve_program_name(32, 32, 1) == "serve_32x32_b1"
        names = serving_program_names(cfg)
        assert sorted(names) == sorted(
            f"serve_{s}x{s}_b{b}" for s in (32, 64) for b in (1, 2)
        )
        specs = build_serving_specs(cfg)
        assert sorted(specs) == sorted(names)
        for name, spec in specs.items():
            assert spec.feed == "serve"
            assert spec.arg_roles == ("variables", "images")
            h, w = spec.meta["bucket"]
            assert name == f"serve_{h}x{w}_b{spec.meta['batch']}"

    def test_audit_expected_names_include_serving(self):
        from replication_faster_rcnn_tpu.analysis import hlolint

        base = set(hlolint.expected_program_names())
        full = set(
            hlolint.expected_program_names(config=hlolint.audit_config())
        )
        extra = full - base
        serving = {n for n in extra if n.startswith("serve_")}
        # 4 bucket-matrix programs + the serve pallas twin (ISSUE 13)
        # + their 4 __int8 quantized twins and the int8 pallas twin
        # (ISSUE 17)
        assert len(serving) == 10 and "serve_64x64_b1__pallas" in serving
        int8 = {n for n in serving if "__int8" in n}
        assert int8 == {
            "serve_32x32_b1__int8",
            "serve_32x32_b2__int8",
            "serve_64x64_b1__int8",
            "serve_64x64_b2__int8",
            "serve_64x64_b1__int8__pallas",
        }
        # the only other config-dependent names are the remaining pallas
        # twins and the per-bucket training programs (ISSUE 15: the audit
        # config sets data.train_resolutions; ISSUE 19: EVERY train feed
        # buckets, so the matrix is feeds x Ks x resolutions)
        from replication_faster_rcnn_tpu.train.warmup import (
            bucket_train_program_names,
        )

        buckets = set(
            bucket_train_program_names(
                hlolint.audit_config(),
                feeds=hlolint.AUDIT_FEEDS,
                ks=hlolint.AUDIT_KS,
            )
        )
        expected_buckets = (
            len(hlolint.AUDIT_FEEDS) * len(hlolint.AUDIT_KS) * 2
        )
        assert buckets <= extra and len(buckets) == expected_buckets
        # ... and the sequence model's train program at its tiny preset (PR 31)
        assert extra - serving - buckets == {
            "train_loader_k1__pallas",
            "eval_infer__pallas",
            "train_lm_k1",
        }


# ------------------------------------------------- serving_profile harness


class TestServingProfileGate:
    @pytest.fixture()
    def sp(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "serving_profile",
            os.path.join(REPO, "benchmarks", "serving_profile.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _record(self, sp, ips=100.0, speedup=2.5, p99=50.0):
        return {
            "schema": sp.SCHEMA,
            sp.GATE_KEY: ips,
            "speedup": speedup,
            "sequential_images_per_sec": round(ips / speedup, 3),
            "engine": {"p99_ms": p99},
        }

    def test_regression_beyond_tol_fails(self, sp):
        cur, banked = self._record(sp, ips=80.0), self._record(sp, ips=100.0)
        failures, _ = sp.check_regression(cur, banked, tol=0.15)
        assert len(failures) == 1 and "regressed" in failures[0]

    def test_slip_within_tol_warns(self, sp):
        cur, banked = self._record(sp, ips=90.0), self._record(sp, ips=100.0)
        failures, warnings = sp.check_regression(cur, banked, tol=0.15)
        assert not failures
        assert any("slipping" in w for w in warnings)

    def test_speedup_floor_enforced_without_banked_record(self, sp):
        cur = self._record(sp, speedup=1.4)
        failures, _ = sp.check_regression(cur, None, min_speedup=2.0)
        assert len(failures) == 1 and "floor" in failures[0]

    def test_clean_run_passes(self, sp):
        cur = self._record(sp, ips=101.0)
        failures, warnings = sp.check_regression(cur, self._record(sp))
        assert not failures and not warnings

    def test_schema_mismatch_skips_comparison(self, sp):
        banked = self._record(sp)
        banked["schema"] = "other/v0"
        failures, warnings = sp.check_regression(self._record(sp), banked)
        assert not failures
        assert any("schema" in w for w in warnings)

    def test_banked_cpu_record_meets_acceptance(self, sp):
        """The committed record must hold the >= 2x acceptance claim."""
        path = sp.record_path(sp.record_key("tiny16b32", "cpu"))
        with open(path) as f:
            banked = json.load(f)
        assert banked["schema"] == sp.SCHEMA
        assert banked["speedup"] >= 2.0
        assert banked[sp.GATE_KEY] > banked["sequential_images_per_sec"]
        for leg in ("sequential", "engine", "engine_open_loop"):
            assert banked[leg]["p50_ms"] > 0
            assert banked[leg]["p99_ms"] >= banked[leg]["p50_ms"]


# ------------------------------------------------------------- live engine


def live_config():
    return FasterRCNNConfig(
        model=ModelConfig(
            backbone="resnet18", roi_op="align", compute_dtype="float32"
        ),
        data=DataConfig(
            dataset="synthetic", image_size=(32, 32), max_boxes=8
        ),
        train=TrainConfig(batch_size=1, n_epoch=1),
        mesh=MeshConfig(num_data=1),
        proposals=ProposalConfig(
            pre_nms_train=128, post_nms_train=32,
            pre_nms_test=16, post_nms_test=4,
        ),
        roi_targets=ROITargetConfig(n_sample=8),
        eval=EvalConfig(max_detections=4),
        serving=ServingConfig(
            resolutions=((32, 32),),
            batch_sizes=(1, 2),
            max_delay_ms=20.0,
            queue_depth=8,
            params_dtype="float32",  # bitwise parity with the Evaluator
        ),
    )


@pytest.fixture(scope="module")
def live():
    import jax

    from replication_faster_rcnn_tpu.eval.evaluator import Evaluator
    from replication_faster_rcnn_tpu.models.faster_rcnn import init_variables

    cfg = live_config()
    model, variables = init_variables(cfg, jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, model, variables, warmup=True)
    ev = Evaluator(cfg, model)
    rng = np.random.RandomState(0)
    images = [
        (rng.rand(32, 32, 3) * 2.0 - 1.0).astype(np.float32)
        for _ in range(3)
    ]
    yield {
        "cfg": cfg, "model": model, "variables": variables,
        "engine": engine, "ev": ev, "images": images,
    }
    engine.close()


class TestLiveEngine:
    def test_warmup_compiled_every_bucket_program(self, live):
        assert sorted(live["engine"].compile_seconds) == [
            "serve_32x32_b1", "serve_32x32_b2"
        ]

    def test_single_submit_bitwise_matches_evaluator(self, live):
        engine, ev = live["engine"], live["ev"]
        img = live["images"][0]
        out = engine.submit(img).result(timeout=60)
        ref = ev.predict_batch(live["variables"], img[None])
        for k in ("boxes", "scores", "classes", "valid"):
            np.testing.assert_array_equal(
                out[k], np.asarray(ref[k][0]),
                err_msg=f"engine vs Evaluator mismatch on {k}",
            )

    def test_concurrent_submits_coalesce_and_match_singles(self, live):
        engine = live["engine"]
        flushes_before = len(engine._batcher.flush_log)
        futs = [engine.submit(img) for img in live["images"][:2]]
        outs = [f.result(timeout=60) for f in futs]
        new = engine._batcher.flush_log[flushes_before:]
        # flush keys are (model_version, bucket) since the hot-swap work
        assert (("0", (32, 32)), 2) in new, f"no coalesced flush in {new}"
        for img, out in zip(live["images"][:2], outs):
            ref = live["ev"].predict_batch(live["variables"], img[None])
            np.testing.assert_allclose(
                out["boxes"], np.asarray(ref["boxes"][0]), atol=1e-5
            )
            np.testing.assert_array_equal(
                out["classes"], np.asarray(ref["classes"][0])
            )

    def test_partial_flush_pads_to_bucket_and_unpads(self, live):
        engine = live["engine"]
        img = live["images"][0]
        padded_before = engine.stats["padded_slots"]
        # force the pad path: drop the b1 program from the size ladder so
        # a single request must ride the compiled b2 program
        orig_sizes = engine.batch_sizes
        engine.batch_sizes = (2,)
        try:
            out = engine._process_bucket(
                (engine.model_version, (32, 32)), [(img, 32, 32)]
            )
        finally:
            engine.batch_sizes = orig_sizes
        assert len(out) == 1  # un-padded: one result for one request
        assert engine.stats["padded_slots"] == padded_before + 1
        ref = live["ev"].predict_batch(live["variables"], img[None])
        np.testing.assert_allclose(
            out[0]["boxes"], np.asarray(ref["boxes"][0]), atol=1e-5
        )

    def test_uint8_routing_and_box_denormalization(self, live):
        engine = live["engine"]
        rng = np.random.RandomState(1)
        # 16x24 uint8 routes to the 32x32 bucket; boxes come back scaled
        # to the ORIGINAL 16x24 frame
        small = (rng.rand(16, 24, 3) * 255).astype(np.uint8)
        out = engine.submit(small).result(timeout=60)
        h_scale, w_scale = 16 / 32, 24 / 32
        assert out["boxes"].shape[-1] == 4
        valid = out["boxes"][np.asarray(out["valid"], bool)]
        if valid.size:
            assert valid[:, 0].max() <= 16 + 1e-3
            assert valid[:, 1].max() <= 24 + 1e-3
        # the same content at bucket size must reproduce the normalized
        # boxes modulo that scaling
        from replication_faster_rcnn_tpu.data import native_ops

        resized = native_ops.resize_normalize(
            small, (32, 32),
            live["cfg"].data.pixel_mean, live["cfg"].data.pixel_std,
        )
        ref = engine.submit(resized.astype(np.float32)).result(timeout=60)
        np.testing.assert_allclose(
            out["boxes"],
            ref["boxes"] * np.asarray(
                [h_scale, w_scale, h_scale, w_scale], np.float32
            ),
            atol=1e-4,
        )

    def test_oversized_image_downscales_by_default(self, live):
        engine = live["engine"]
        big = (np.random.RandomState(2).rand(50, 40, 3) * 255).astype(
            np.uint8
        )
        out = engine.submit(big).result(timeout=60)
        valid = out["boxes"][np.asarray(out["valid"], bool)]
        if valid.size:  # de-normalized to the 50x40 original frame
            assert valid[:, 2].max() <= 50 + 1e-3

    def test_oversized_image_rejected_under_reject_policy(self, live):
        cfg = dataclasses.replace(
            live["cfg"],
            serving=dataclasses.replace(
                live["cfg"].serving, oversize="reject"
            ),
        )
        engine = InferenceEngine(cfg, live["model"], live["variables"])
        try:
            big = np.zeros((40, 40, 3), np.uint8)
            with pytest.raises(OversizedImageError):
                engine.submit(big)
            assert engine.stats["requests"] == 0
        finally:
            engine.close()

    def test_float_image_off_bucket_rejected(self, live):
        with pytest.raises(ValueError, match="matches no serving bucket"):
            live["engine"].submit(np.zeros((16, 16, 3), np.float32))

    def test_predict_images_multi_path_one_wave(self, live, tmp_path):
        from PIL import Image

        from replication_faster_rcnn_tpu.eval.predict import predict_images

        rng = np.random.RandomState(3)
        paths = []
        for i in range(2):
            p = str(tmp_path / f"img{i}.png")
            Image.fromarray(
                (rng.rand(20, 28, 3) * 255).astype(np.uint8)
            ).save(p)
            paths.append(p)
        engine = live["engine"]
        flushes_before = len(engine._batcher.flush_log)
        dets = predict_images(
            live["cfg"], live["model"], live["variables"], paths,
            score_thresh=0.0, engine=engine,
        )
        assert len(dets) == 2
        for d in dets:
            for det in d:
                assert set(det) == {"box", "score", "class_id", "class_name"}
        # both paths coalesced into one shared flush
        assert (("0", (32, 32)), 2) in engine._batcher.flush_log[flushes_before:]

    def test_strict_session_zero_transfers_zero_recompiles(self, live):
        from replication_faster_rcnn_tpu.analysis.strict import StrictHarness

        engine = live["engine"]
        h = StrictHarness()  # dispatch 2+ of each program is checked warm
        engine.strict = h
        try:
            with h.session():
                for _ in range(2):  # two b2 flushes, two b1 flushes
                    futs = [engine.submit(img) for img in live["images"][:2]]
                    _ = [f.result(timeout=60) for f in futs]
                    _ = engine.submit(live["images"][2]).result(timeout=60)
        finally:
            engine.strict = None
        report = h.report()
        assert report["violations"] == []
        assert report["compile_events_total"] == 0
        assert sum(
            p["warm_dispatches"] for p in report["programs"].values()
        ) >= 2

    def test_http_server_end_to_end(self, live, tmp_path):
        import urllib.error
        import urllib.request

        from PIL import Image

        from replication_faster_rcnn_tpu.serving.server import make_server

        p = str(tmp_path / "req.png")
        Image.fromarray(
            (np.random.RandomState(4).rand(20, 20, 3) * 255).astype(np.uint8)
        ).save(p)
        server = make_server(live["engine"], port=0, score_thresh=0.0)
        host, port = server.server_address
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            def call(method, path, payload=None):
                req = urllib.request.Request(
                    f"http://{host}:{port}{path}",
                    data=json.dumps(payload).encode() if payload else None,
                    method=method,
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return resp.status, json.loads(resp.read())

            status, health = call("GET", "/healthz")
            assert status == 200 and health["buckets"] == [[32, 32]]
            status, out = call("POST", "/predict", {"paths": [p]})
            assert status == 200
            for det in out["detections"][p]:
                assert set(det) == {"box", "score", "class_id", "class_name"}
            status, stats = call("GET", "/stats")
            assert status == 200 and stats["stats"]["requests"] >= 1
            with pytest.raises(urllib.error.HTTPError) as e:
                call("POST", "/predict", {})
            assert e.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as e:
                call("POST", "/predict", {"path": str(tmp_path / "no.png")})
            assert e.value.code == 400
        finally:
            server.shutdown()
            server.server_close()

    def test_get_engine_cache_reuses_and_displaces(self, live):
        from replication_faster_rcnn_tpu.serving.engine import get_engine

        e1 = get_engine(live["cfg"], live["model"], live["variables"])
        e2 = get_engine(live["cfg"], live["model"], live["variables"])
        assert e1 is e2
        variables2 = jax_tree_copy(live["variables"])
        e3 = get_engine(live["cfg"], live["model"], variables2)
        assert e3 is not e1
        # the displaced engine's worker was shut down
        with pytest.raises(RuntimeError, match="closed"):
            e1._batcher.submit((32, 32), None)
        e3.close()


def jax_tree_copy(tree):
    import jax

    return jax.tree_util.tree_map(lambda x: x, tree)


# ------------------------------------------- model-parallel serving layout


def mp_config(num_model=2):
    cfg = live_config()
    return dataclasses.replace(
        cfg,
        mesh=dataclasses.replace(
            cfg.mesh, num_data=1, num_model=num_model, param_sharding=True
        ),
    )


class TestMpServingSpecs:
    """`--mesh-shape DP,MP` serving seam: build_serving_specs attaches the
    `zero.param_shardings` layout to abstract params (shardlint SL001's
    fix for the replicated-params serve plan) and the engine's resident
    upload honors it. Spec construction is lazy — no compiles here."""

    def test_mp_config_attaches_sharded_layout_and_meta(self):
        import jax
        from replication_faster_rcnn_tpu.train.warmup import (
            build_serving_specs,
        )

        specs = build_serving_specs(mp_config())
        spec = specs["serve_32x32_b1"]
        assert spec.meta["param_sharding"] is True
        assert spec.meta["mesh_shape"] == {"data": 1, "model": 2}
        _, (vars_abs, _img) = spec.build()
        param_specs = [
            tuple(leaf.sharding.spec)
            for leaf in jax.tree_util.tree_leaves(vars_abs["params"])
        ]
        assert all(s is not None for s in param_specs)
        # the layout actually splits weights: some leaf rides the model axis
        assert any("model" in str(s) for s in param_specs)
        # non-param collections stay replicated on the same mesh
        for leaf in jax.tree_util.tree_leaves(vars_abs["batch_stats"]):
            assert tuple(leaf.sharding.spec) == ()
            assert dict(leaf.sharding.mesh.shape) == {"data": 1, "model": 2}

    def test_mp_layout_matches_zero_param_shardings(self):
        import jax
        from replication_faster_rcnn_tpu.parallel import zero
        from replication_faster_rcnn_tpu.train.warmup import (
            build_serving_specs,
        )

        cfg = mp_config()
        spec = build_serving_specs(cfg)["serve_32x32_b1"]
        _, (vars_abs, _img) = spec.build()
        leaves = jax.tree_util.tree_leaves(vars_abs["params"])
        mesh = leaves[0].sharding.mesh
        expected = zero.param_shardings(
            vars_abs["params"], mesh, cfg.mesh
        )
        for got, want in zip(
            leaves, jax.tree_util.tree_leaves(expected)
        ):
            assert got.sharding == want

    def test_default_config_attaches_no_shardings(self):
        import jax
        from replication_faster_rcnn_tpu.train.warmup import (
            build_serving_specs,
        )

        spec = build_serving_specs(live_config())["serve_32x32_b1"]
        assert "param_sharding" not in spec.meta
        assert "mesh_shape" not in spec.meta
        _, (vars_abs, _img) = spec.build()
        for leaf in jax.tree_util.tree_leaves(vars_abs):
            assert getattr(leaf, "sharding", None) is None

    def test_batch_target_follows_resident_mesh(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from replication_faster_rcnn_tpu.serving.engine import _batch_target

        # host / single-device trees: default placement
        assert _batch_target({"w": np.zeros((4, 4))}) is None
        one = jax.device_put(np.zeros((4, 4), np.float32))
        assert _batch_target({"w": one}) is None
        # mp-sharded tree: batch must be replicated over the SAME mesh
        mesh = Mesh(
            np.asarray(jax.devices()[:2]).reshape(1, 2), ("data", "model")
        )
        sharded = jax.device_put(
            np.zeros((4, 4), np.float32),
            NamedSharding(mesh, PartitionSpec("model", None)),
        )
        target = _batch_target({"w": sharded, "b": one})
        assert target == NamedSharding(mesh, PartitionSpec())


@pytest.mark.slow
class TestMpServingParity:
    def test_mp_engine_matches_replicated_engine(self):
        """End-to-end acceptance for satellite 1: the same weights served
        through the (1, 2) model-parallel layout produce the same
        detections as the single-device replicated path."""
        import jax

        from replication_faster_rcnn_tpu.models.faster_rcnn import (
            init_variables,
        )

        cfg_rep = live_config()
        cfg_mp = mp_config()
        model, variables = init_variables(cfg_rep, jax.random.PRNGKey(0))
        img = (
            np.random.RandomState(0).rand(32, 32, 3) * 255
        ).astype(np.uint8)
        eng_rep = InferenceEngine(cfg_rep, model, variables)
        try:
            ref = eng_rep.submit(img).result(timeout=300)
        finally:
            eng_rep.close()
        eng_mp = InferenceEngine(cfg_mp, model, variables)
        try:
            # resident params really live on the 2-device serving mesh
            resident = eng_mp._resident[eng_mp.model_version]
            leaves = jax.tree_util.tree_leaves(resident["params"])
            assert any(
                leaf.sharding.num_devices == 2 for leaf in leaves
            )
            out = eng_mp.submit(img).result(timeout=300)
        finally:
            eng_mp.close()
        np.testing.assert_array_equal(out["classes"], ref["classes"])
        np.testing.assert_array_equal(out["valid"], ref["valid"])
        for k in ("boxes", "scores"):
            np.testing.assert_allclose(
                out[k], ref[k], atol=2e-2, rtol=2e-2,
                err_msg=f"mp vs replicated mismatch on {k}",
            )
