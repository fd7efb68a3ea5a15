"""Overload-hardened serving tier (fast tier).

A real InferenceEngine (public submit/stats/deadline/degraded surface,
real MicroBatcher worker, real HTTP server) with FAKE bucket programs
pre-seeded into the AOT program cache — a controllable delay/failure
knob instead of a compile, so overload scenarios run in milliseconds.

Pins the overload contract: admission control sheds with ``queue.Full``
+ a counted ``shed`` stat (503 + Retry-After over HTTP), per-request
deadlines expire queued entries at flush time (never dispatched) and
time handler waits out to 504, the degraded flag trips after
``DEGRADED_AFTER`` consecutive flush failures and self-resets, per-path
errors stay isolated in multi-path requests, and the load generator's
client-side deadline/backoff reports timeouts and sheds instead of
hanging.
"""

import dataclasses
import json
import queue
import threading
import time
import urllib.error
import urllib.request

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
)
from replication_faster_rcnn_tpu.faultlib import failpoints
from replication_faster_rcnn_tpu.serving.batcher import DeadlineExceeded
from replication_faster_rcnn_tpu.serving.engine import (
    DEGRADED_AFTER,
    InferenceEngine,
)
from replication_faster_rcnn_tpu.serving.overload import (
    backoff_delays,
    retry_after_s,
)


def _cfg(**serving_kw):
    base = dict(
        resolutions=((32, 32),),
        batch_sizes=(1, 2),
        max_delay_ms=5.0,
        queue_depth=4,
        params_dtype="float32",
    )
    base.update(serving_kw)
    return FasterRCNNConfig(
        model=ModelConfig(
            backbone="resnet18", roi_op="align", compute_dtype="float32"
        ),
        data=DataConfig(dataset="synthetic", image_size=(32, 32), max_boxes=8),
        train=TrainConfig(batch_size=1, n_epoch=1),
        mesh=MeshConfig(num_data=1),
        proposals=ProposalConfig(
            pre_nms_train=128, post_nms_train=32,
            pre_nms_test=16, post_nms_test=4,
        ),
        roi_targets=ROITargetConfig(n_sample=8),
        eval=EvalConfig(max_detections=4),
        serving=ServingConfig(**base),
    )


@pytest.fixture(scope="module")
def parts():
    import jax

    from replication_faster_rcnn_tpu.models.faster_rcnn import init_variables

    cfg = _cfg()
    model, variables = init_variables(cfg, jax.random.PRNGKey(0))
    return {"model": model, "variables": variables}


class _Knobs:
    """Shared mutable dials for the fake programs."""

    def __init__(self):
        self.delay_s = 0.0
        self.fail = False
        self.dispatches = 0
        self.lock = threading.Lock()


def _make_engine(parts, knobs=None, **serving_kw):
    """Engine with fake AOT programs: real everything else, no compiles."""
    from replication_faster_rcnn_tpu.train.warmup import serve_program_name

    knobs = knobs if knobs is not None else _Knobs()
    engine = InferenceEngine(
        _cfg(**serving_kw), parts["model"], parts["variables"], warmup=False
    )

    def prog(variables, batch):
        with knobs.lock:
            knobs.dispatches += 1
        if knobs.delay_s:
            time.sleep(knobs.delay_s)
        if knobs.fail:
            raise RuntimeError("injected dispatch failure")
        b = int(batch.shape[0])
        return {
            "boxes": np.zeros((b, 4, 4), np.float32),
            "scores": np.zeros((b, 4), np.float32),
            "classes": np.zeros((b, 4), np.int32),
            "valid": np.zeros((b, 4), np.bool_),
        }

    for n in (1, 2):
        engine._programs[serve_program_name(32, 32, n)] = prog
    return engine, knobs


def _image(seed=0):
    return (
        np.random.RandomState(seed).rand(32, 32, 3).astype(np.float32) * 2 - 1
    )


# -------------------------------------------------------------- unit bits


class TestOverloadHelpers:
    def test_retry_after_rounds_up_to_whole_seconds(self):
        assert retry_after_s(10) == 1
        assert retry_after_s(2500) == 3

    def test_backoff_delays_seeded_and_bounded(self):
        a = list(backoff_delays(base_s=0.01, max_s=0.1, retries=6, seed=3))
        b = list(backoff_delays(base_s=0.01, max_s=0.1, retries=6, seed=3))
        assert a == b and len(a) == 6
        assert all(0 < d <= 0.1 for d in a)
        assert a != list(
            backoff_delays(base_s=0.01, max_s=0.1, retries=6, seed=4)
        )

    def test_request_timeout_config_validated(self):
        with pytest.raises(ValueError, match="request_timeout_s"):
            ServingConfig(request_timeout_s=-1.0)


# ----------------------------------------------------------- engine level


class TestEngineOverload:
    def test_public_queue_depth_and_stat_keys(self, parts):
        engine, _ = _make_engine(parts)
        try:
            assert engine.queue_depth() == 0
            for key in (
                "shed", "deadline_expired", "timeouts", "flush_errors",
            ):
                assert engine.stats[key] == 0
            assert engine.degraded is False
        finally:
            engine.close()

    def test_admission_control_sheds_and_counts(self, parts):
        knobs = _Knobs()
        knobs.delay_s = 0.4
        engine, _ = _make_engine(parts, knobs, queue_depth=2)
        futs, sheds = [], 0
        try:
            for i in range(10):
                try:
                    futs.append(engine.submit(_image(i), timeout=0))
                except queue.Full:
                    sheds += 1
            assert sheds >= 1, "bounded queue never filled at 10x capacity"
            assert engine.stats["shed"] == sheds
        finally:
            knobs.delay_s = 0.0
            engine.close()
        # accepted requests all completed despite the overload
        for f in futs:
            assert f.result(timeout=30)["boxes"].shape == (4, 4)

    def test_expired_entries_dropped_at_flush_never_dispatched(self, parts):
        knobs = _Knobs()
        knobs.delay_s = 0.3
        engine, _ = _make_engine(
            parts, knobs, queue_depth=8, request_timeout_s=0.05
        )
        try:
            futs = [engine.submit(_image(i)) for i in range(4)]
            # first pair flushes immediately (size trigger) and computes;
            # the second pair's deadline passes while that flush sleeps
            done, expired = 0, 0
            for f in futs:
                try:
                    f.result(timeout=30)
                    done += 1
                except DeadlineExceeded:
                    expired += 1
            assert expired >= 1, "no queued entry outlived its deadline"
            assert done >= 1
            assert engine.stats["deadline_expired"] == expired
            with knobs.lock:
                dispatched = knobs.dispatches
            # expired entries were dropped BEFORE compute: only the live
            # flushes reached the program
            assert dispatched <= 1 + done
        finally:
            knobs.delay_s = 0.0
            engine.close()

    def test_degraded_trips_after_streak_and_self_resets(self, parts):
        knobs = _Knobs()
        knobs.fail = True
        engine, _ = _make_engine(parts, knobs)
        try:
            for i in range(DEGRADED_AFTER):
                fut = engine.submit(_image(i))
                with pytest.raises(RuntimeError, match="injected dispatch"):
                    fut.result(timeout=30)
            assert engine.degraded is True
            assert engine.stats["flush_errors"] == DEGRADED_AFTER
            # one healthy flush clears the flag (self-resetting, not latched)
            knobs.fail = False
            engine.submit(_image(0)).result(timeout=30)
            assert engine.degraded is False
        finally:
            engine.close()

    def test_sub_threshold_errors_with_success_never_latch(self, parts):
        """ISSUE 14 satellite edge case: the 3-strike counter counts
        CONSECUTIVE failures — (threshold - 1) errors followed by a
        success must reset the streak, and the same dance repeated must
        never trip the flag."""
        knobs = _Knobs()
        engine, _ = _make_engine(parts, knobs)
        for round_i in range(3):
            knobs.fail = True
            for i in range(DEGRADED_AFTER - 1):
                fut = engine.submit(_image(i))
                with pytest.raises(RuntimeError):
                    fut.result(timeout=30)
            assert engine.degraded is False, f"latched in round {round_i}"
            knobs.fail = False
            engine.submit(_image(0)).result(timeout=30)
            assert engine.degraded is False
            assert engine.degraded_reason is None
        assert engine.stats["flush_errors"] == 3 * (DEGRADED_AFTER - 1)
        engine.close()

    def test_degraded_reason_names_streak_and_last_error(self, parts):
        knobs = _Knobs()
        knobs.fail = True
        engine, _ = _make_engine(parts, knobs)
        try:
            assert engine.degraded_reason is None
            for i in range(DEGRADED_AFTER):
                with pytest.raises(RuntimeError):
                    engine.submit(_image(i)).result(timeout=30)
            reason = engine.degraded_reason
            assert f"{DEGRADED_AFTER} consecutive" in reason
            assert "injected dispatch failure" in reason
            knobs.fail = False
            engine.submit(_image(0)).result(timeout=30)
            assert engine.degraded_reason is None
        finally:
            engine.close()

    def test_bucket_queue_depths_and_uptime_gauges(self, parts):
        knobs = _Knobs()
        knobs.delay_s = 0.3
        engine, _ = _make_engine(parts, knobs, queue_depth=8)
        try:
            assert engine.bucket_queue_depths() == {}
            futs = [engine.submit(_image(i)) for i in range(3)]
            depths = engine.bucket_queue_depths()
            # everything in flight sits under the single 32x32 bucket
            assert set(depths) <= {"32x32"}
            assert engine.uptime_s() >= 0.0
        finally:
            knobs.delay_s = 0.0
            for f in futs:
                f.result(timeout=30)
            engine.close()
        assert engine.bucket_queue_depths() == {}


# ------------------------------------------------------------- HTTP level


def _serve(engine):
    from replication_faster_rcnn_tpu.serving.server import make_server

    server = make_server(engine, port=0, score_thresh=0.0)
    host, port = server.server_address[:2]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    return server, f"http://{host}:{port}"


def _post(base, payload, timeout=30, headers=None):
    """(status, body) for POST /predict; HTTP errors return their code."""
    req = urllib.request.Request(
        f"{base}/predict",
        data=json.dumps(payload).encode(),
        method="POST",
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _png(tmp_path, name, seed=0):
    from PIL import Image

    p = str(tmp_path / name)
    Image.fromarray(
        (np.random.RandomState(seed).rand(24, 24, 3) * 255).astype(np.uint8)
    ).save(p)
    return p


class TestHTTPOverload:
    def test_overload_sheds_503_with_retry_after_never_hangs(
        self, parts, tmp_path
    ):
        knobs = _Knobs()
        knobs.delay_s = 0.4
        engine, _ = _make_engine(parts, knobs, queue_depth=2)
        server, base = _serve(engine)
        p = _png(tmp_path, "img.png")
        results = []
        lock = threading.Lock()

        def one():
            t0 = time.monotonic()
            status, _, headers = _post(base, {"path": p})
            with lock:
                results.append((status, headers, time.monotonic() - t0))

        try:
            # 2x+ the engine's capacity, all at once
            threads = [threading.Thread(target=one) for _ in range(10)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert len(results) == 10, "a handler thread hung"
            statuses = [s for s, _, _ in results]
            assert set(statuses) <= {200, 503}, f"unexpected: {statuses}"
            assert 503 in statuses, "overload never shed"
            assert 200 in statuses, "overload starved every request"
            for status, headers, _ in results:
                if status == 503:
                    assert int(headers["Retry-After"]) >= 1
            # p99 bounded: nobody waited anywhere near a hang
            assert max(dt for _, _, dt in results) < 20
            assert engine.stats["shed"] == statuses.count(503)
        finally:
            knobs.delay_s = 0.0
            server.shutdown()
            server.server_close()
            engine.close()

    def test_deadline_exceeded_maps_to_504_with_retry_after(
        self, parts, tmp_path
    ):
        knobs = _Knobs()
        knobs.delay_s = 0.5
        engine, _ = _make_engine(
            parts, knobs, queue_depth=8, request_timeout_s=0.1
        )
        server, base = _serve(engine)
        try:
            status, body, headers = _post(
                base, {"path": _png(tmp_path, "img.png")}
            )
            assert status == 504
            assert "deadline" in body["error"]
            # ISSUE 14 satellite: timeouts carry a retry hint like sheds
            assert int(headers["Retry-After"]) >= 1
            assert engine.stats["timeouts"] >= 1
        finally:
            knobs.delay_s = 0.0
            server.shutdown()
            server.server_close()
            engine.close()

    def test_healthz_enrichment_fields(self, parts):
        """ISSUE 14 satellite: /healthz carries the fleet-probe surface —
        per-bucket queue depth, uptime, replica identity, drain state,
        and a human-readable degraded_reason."""
        from replication_faster_rcnn_tpu.serving.server import make_server

        engine, _ = _make_engine(parts)
        server = make_server(engine, port=0, replica_id="replica-7")
        host, port = server.server_address[:2]
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        base = f"http://{host}:{port}"
        try:
            with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
                health = json.loads(r.read())
            assert health["ok"] is True
            assert health["degraded"] is False
            assert health["degraded_reason"] is None
            assert health["draining"] is False
            assert health["replica_id"] == "replica-7"
            assert health["uptime_s"] >= 0.0
            assert health["bucket_queue_depths"] == {}
            # the drain flag the SIGTERM handler raises is probe-visible
            server.draining = True
            with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
                assert json.loads(r.read())["draining"] is True
            with urllib.request.urlopen(f"{base}/stats", timeout=10) as r:
                stats = json.loads(r.read())
            assert "bucket_queue_depths" in stats
        finally:
            server.shutdown()
            server.server_close()
            engine.close()

    def test_healthz_degraded_reason_surfaces_after_streak(self, parts):
        knobs = _Knobs()
        knobs.fail = True
        engine, _ = _make_engine(parts, knobs)
        server, base = _serve(engine)
        try:
            for i in range(DEGRADED_AFTER):
                with pytest.raises(RuntimeError):
                    engine.submit(_image(i)).result(timeout=30)
            with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
                health = json.loads(r.read())
            assert health["degraded"] is True
            assert "consecutive" in health["degraded_reason"]
        finally:
            knobs.fail = False
            server.shutdown()
            server.server_close()
            engine.close()

    def test_stats_schema_and_prometheus_parity(self, parts, tmp_path):
        """ISSUE 16 acceptance at the replica tier: /stats serves the
        unified frcnn-stats/v1 envelope and /metrics serves Prometheus
        text with the SAME counter values — one registry, two renders."""
        from tests.test_observability import parse_prometheus

        engine, _ = _make_engine(parts)
        server, base = _serve(engine)
        p = _png(tmp_path, "img.png")
        try:
            for _ in range(2):
                assert _post(base, {"path": p})[0] == 200
            with urllib.request.urlopen(f"{base}/stats", timeout=10) as r:
                stats = json.loads(r.read())
            assert stats["schema"] == "frcnn-stats/v1"
            assert stats["tier"] == "replica"
            assert stats["stats"]["requests"] >= 2  # historical section
            assert "slo" in stats and "burn_rates" in stats["slo"]
            assert stats["metrics"]["counters"]["serve_requests_total"] \
                == stats["stats"]["requests"]
            with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
                ctype = r.headers["Content-Type"]
                text = r.read().decode()
            assert ctype.startswith("text/plain") and "0.0.4" in ctype
            values, types = parse_prometheus(text)
            assert types["serve_requests_total"] == "counter"
            for series, v in stats["metrics"]["counters"].items():
                assert values[series] == v, series
            assert values["serve_queue_wait_seconds_count"] >= 2
        finally:
            server.shutdown()
            server.server_close()
            engine.close()

    def test_request_hop_spans_join_the_callers_trace(self, parts, tmp_path):
        """A traceparent header on POST /predict threads the caller's
        trace id through the replica's hop spans (request -> queue wait
        -> dispatch) and back out on error replies."""
        from replication_faster_rcnn_tpu.telemetry.spans import (
            SpanTracer,
            set_tracer,
        )

        engine, _ = _make_engine(parts)
        server, base = _serve(engine)
        tid = "ab" * 16
        header = {"traceparent": f"00-{tid}-{'cd' * 8}-01"}
        tracer = SpanTracer()
        set_tracer(tracer)
        try:
            status, _, _ = _post(
                base, {"path": _png(tmp_path, "img.png")}, headers=header
            )
            assert status == 200
            # the handler emits `serve/request` after it has replied: the
            # reply can reach this thread first, so wait for the span
            deadline = time.monotonic() + 5.0
            while True:
                events = [e for e in tracer.to_dict()["traceEvents"]
                          if e["ph"] == "X"
                          and e.get("args", {}).get("trace_id") == tid]
                names = {e["name"] for e in events}
                if "serve/request" in names or time.monotonic() > deadline:
                    break
                time.sleep(0.01)
            assert {"serve/request", "serve/queue_wait",
                    "serve/dispatch"} <= names
            # the hops are phases of ONE replica-side span: they share
            # the handler's span id, distinguished by name
            assert len({e["args"]["span_id"] for e in events}) == 1
            # a malformed request's error reply names the trace
            status, body, _ = _post(base, {}, headers=header)
            assert status == 400
            assert body["trace_id"] == tid
        finally:
            set_tracer(None)
            server.shutdown()
            server.server_close()
            engine.close()

    def test_multi_path_per_path_error_isolation(self, parts, tmp_path):
        engine, _ = _make_engine(parts)
        server, base = _serve(engine)
        good = _png(tmp_path, "good.png")
        missing = str(tmp_path / "missing.png")
        try:
            status, body, _ = _post(base, {"paths": [good, missing]})
            # one bad path costs one "errors" entry, not the request
            assert status == 200
            assert good in body["detections"]
            assert missing in body["errors"]
            assert missing not in body["detections"]
        finally:
            server.shutdown()
            server.server_close()
            engine.close()

    def test_healthz_and_stats_surface_overload_state(self, parts):
        engine, _ = _make_engine(parts)
        server, base = _serve(engine)
        try:
            with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
                health = json.loads(r.read())
            assert health["degraded"] is False
            with urllib.request.urlopen(f"{base}/stats", timeout=10) as r:
                stats = json.loads(r.read())
            assert "queue_depth" in stats
            for key in ("shed", "deadline_expired", "timeouts", "flush_errors"):
                assert key in stats["stats"]
        finally:
            server.shutdown()
            server.server_close()
            engine.close()

    def test_http_handler_failpoint_ioerror_returns_500(
        self, parts, tmp_path
    ):
        engine, _ = _make_engine(parts)
        server, base = _serve(engine)
        try:
            failpoints.configure("http.handler:ioerror:1.0:0:0:1")
            status, body, _ = _post(
                base, {"path": _png(tmp_path, "img.png")}
            )
            assert status == 500
            assert "injected IOError" in body["error"]
            # rule exhausted: the tier recovered, next request serves
            status, _, _ = _post(base, {"path": _png(tmp_path, "img.png")})
            assert status == 200
        finally:
            failpoints.disarm()
            server.shutdown()
            server.server_close()
            engine.close()

    def test_http_handler_failpoint_drop_closes_connection(
        self, parts, tmp_path
    ):
        engine, _ = _make_engine(parts)
        server, base = _serve(engine)
        try:
            failpoints.configure("http.handler:drop:1.0:0:0:1")
            with pytest.raises(Exception):  # no response bytes at all
                _post(base, {"path": _png(tmp_path, "img.png")}, timeout=10)
        finally:
            failpoints.disarm()
            server.shutdown()
            server.server_close()
            engine.close()


# ---------------------------------------------------------------- loadgen


class TestLoadgenHardening:
    def test_closed_loop_reports_timeouts_and_sheds(self, parts):
        from replication_faster_rcnn_tpu.serving import loadgen

        knobs = _Knobs()
        knobs.delay_s = 0.25
        engine, _ = _make_engine(parts, knobs, queue_depth=2)
        try:
            summary = loadgen.run_closed_loop(
                engine,
                [_image(i) for i in range(3)],
                n_requests=8,
                timeout_s=0.05,
                admission=True,
                seed=7,
            )
        finally:
            knobs.delay_s = 0.0
            engine.close()
        for key in (
            "timeouts", "timeout_fraction", "shed", "submit_retries", "errors",
        ):
            assert key in summary, f"summary missing {key}"
        # a wedged-slow engine costs bounded waits, reported not raised
        assert summary["timeouts"] + summary["shed"] >= 1
        assert 0.0 <= summary["timeout_fraction"] <= 1.0

    def test_default_blocking_submit_path_unchanged(self, parts):
        """admission=False (the serving_profile default) still blocks on
        the bounded queue — no shed, every request measured."""
        from replication_faster_rcnn_tpu.serving import loadgen

        engine, _ = _make_engine(parts, queue_depth=4)
        try:
            summary = loadgen.run_closed_loop(
                engine, [_image(0)], n_requests=6
            )
        finally:
            engine.close()
        assert summary["n_requests"] == 6
        assert summary["shed"] == 0 and summary["timeouts"] == 0
        assert len(summary) and summary["p99_ms"] >= summary["p50_ms"]
