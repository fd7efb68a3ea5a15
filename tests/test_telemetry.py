"""The telemetry subsystem (`replication_faster_rcnn_tpu/telemetry/`):
span tracer emits valid Chrome-trace JSON, the watchdog fires and
recovers on a simulated stall, MFU matches hand-computed arithmetic, and
the train-health scalars ride a real train step.
"""

import io
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.telemetry import (
    NULL_TRACER,
    SpanTracer,
    StallWatchdog,
    current_tracer,
    set_tracer,
)
from replication_faster_rcnn_tpu.telemetry.health import (
    HEALTH_KEYS,
    health_metrics,
    nonfinite_count,
)
from replication_faster_rcnn_tpu.telemetry.mfu import (
    compute_mfu,
    measured_cpu_peak_flops_per_sec,
    peak_flops_per_sec,
    require_accelerator,
    tpu_peak_flops_per_sec,
)
from replication_faster_rcnn_tpu.telemetry.report import (
    format_report,
    health_summary,
    phase_table,
    summarize_run,
)


def _wait_until(pred, timeout=5.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class TestSpanTracer:
    def test_chrome_trace_schema(self, tmp_path):
        """The flushed file must be the object-format Chrome trace that
        chrome://tracing / Perfetto load: a traceEvents list of complete
        events with name/ph/ts/dur/pid/tid."""
        path = str(tmp_path / "trace.json")
        tr = SpanTracer(path)
        with tr.span("data/fetch", cat="data"):
            with tr.span("data/build", cat="data", batch=4):
                pass
        tr.instant("epoch_start")
        tr.counter("loader/queue_depth", 2)
        tr.flush()
        doc = json.load(open(path))
        assert isinstance(doc["traceEvents"], list)
        assert doc["displayTimeUnit"] == "ms"
        complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in complete} == {"data/fetch", "data/build"}
        for ev in complete:
            assert ev["dur"] >= 0 and ev["ts"] >= 0
            assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        # the child span nests inside the parent interval
        by_name = {e["name"]: e for e in complete}
        parent, child = by_name["data/fetch"], by_name["data/build"]
        assert parent["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1
        assert child["args"] == {"batch": 4}
        kinds = {e["ph"] for e in doc["traceEvents"]}
        assert kinds == {"X", "i", "C"}

    def test_span_records_even_on_exception(self, tmp_path):
        tr = SpanTracer(str(tmp_path / "t.json"))
        with pytest.raises(RuntimeError):
            with tr.span("step/dispatch"):
                raise RuntimeError("boom")
        # after the opening instant of a tracer that writes to a file
        names = [e["name"] for e in tr.to_dict()["traceEvents"]]
        assert names == ["telemetry/open", "step/dispatch"]

    def test_event_cap_counts_drops(self):
        tr = SpanTracer(max_events=2)
        for _ in range(5):
            with tr.span("s"):
                pass
        doc = tr.to_dict()
        assert len(doc["traceEvents"]) == 2
        assert doc["otherData"]["dropped_events"] == 3

    def test_last_span_for_watchdog(self):
        tr = SpanTracer()
        assert tr.last_span is None
        with tr.span("checkpoint/save", cat="checkpoint"):
            snap = tr.last_span
        assert snap["name"] == "checkpoint/save"
        assert snap["age_s"] >= 0

    def test_global_registry_and_null_tracer(self):
        assert current_tracer() is NULL_TRACER
        tr = SpanTracer()
        prev = set_tracer(tr)
        try:
            assert prev is None
            assert current_tracer() is tr
        finally:
            set_tracer(None)
        assert current_tracer() is NULL_TRACER
        # the null tracer's whole surface is a no-op, never an error
        with NULL_TRACER.span("x", cat="y", z=1):
            pass
        NULL_TRACER.instant("x")
        NULL_TRACER.counter("x", 1)
        NULL_TRACER.flush()
        assert NULL_TRACER.last_span is None


class TestWatchdog:
    def test_fires_and_recovers_on_simulated_stall(self, tmp_path):
        """No beat past the timeout => exactly one stall snapshot with the
        diagnostic fields; the next beat records a recovery and re-arms."""
        snap_path = str(tmp_path / "watchdog.jsonl")
        tracer = SpanTracer()
        with tracer.span("step/dispatch", cat="step"):
            pass  # leaves last_span behind, like a wedged dispatch would
        wd = StallWatchdog(
            timeout_s=0.15,
            poll_s=0.03,
            snapshot_path=snap_path,
            progress_path=str(tmp_path / "progress.json"),
            tracer=tracer,
            providers={"loader_queue_depth": lambda: 2,
                       "sick_gauge": lambda: 1 / 0},
        )
        wd.start()
        try:
            wd.beat(step=7, phase="train")
            assert _wait_until(lambda: wd.fired_count == 1)
            # one episode fires once, not once per poll
            time.sleep(0.1)
            assert wd.fired_count == 1
            wd.beat(step=8, phase="train")  # simulated recovery
            assert wd.recovered_count == 1
            # a fresh stall after recovery fires again
            assert _wait_until(lambda: wd.fired_count == 2)
        finally:
            wd.stop()

        events = [json.loads(line) for line in open(snap_path)]
        kinds = [e["kind"] for e in events]
        assert kinds == ["stall", "recovered", "stall"]
        stall = events[0]
        assert stall["elapsed_since_progress_s"] >= 0.15
        assert stall["last_step"] == 7 and stall["last_phase"] == "train"
        assert stall["last_span"]["name"] == "step/dispatch"
        assert stall["gauges"]["loader_queue_depth"] == 2
        assert "error" in stall["gauges"]["sick_gauge"]

    def test_stall_snapshot_attaches_all_thread_stacks(self, tmp_path):
        """Stall incidents carry a faulthandler dump of EVERY thread —
        the hung prefetch/serving/writer thread is diagnosable from the
        incident file post-mortem (ISSUE 8 satellite)."""
        snap_path = str(tmp_path / "watchdog.jsonl")
        wd = StallWatchdog(
            timeout_s=0.1, poll_s=0.02, snapshot_path=snap_path
        )
        wd.start()
        try:
            assert _wait_until(lambda: wd.fired_count == 1)
        finally:
            wd.stop()
        events = [json.loads(line) for line in open(snap_path)]
        stall = next(e for e in events if e["kind"] == "stall")
        assert isinstance(stall["threads"], list)
        joined = "\n".join(stall["threads"])
        # faulthandler's format: one header per thread, frames beneath
        assert "thread" in joined.lower() and 'File "' in joined
        # more than one thread is visible (main + the watchdog poller)
        headers = [
            ln for ln in stall["threads"]
            if ln.startswith(("Thread ", "Current thread "))
        ]
        assert len(headers) >= 2, joined

    def test_progress_file_tracks_beats(self, tmp_path):
        path = str(tmp_path / "progress.json")
        wd = StallWatchdog(timeout_s=60.0, progress_path=path)
        wd.beat(step=3, phase="train")
        doc = json.load(open(path))
        assert doc["step"] == 3 and doc["phase"] == "train"
        assert doc["beats"] == 1

    def test_on_stall_callback(self, tmp_path):
        seen = []
        wd = StallWatchdog(timeout_s=0.1, poll_s=0.02, on_stall=seen.append)
        wd.start()
        try:
            assert _wait_until(lambda: len(seen) == 1)
        finally:
            wd.stop()
        assert seen[0]["kind"] == "stall"


class TestMFU:
    def test_arithmetic_matches_hand_computed(self):
        # 1 GFLOP/step at 10 steps/sec against a 20 GFLOP/s peak => 50%
        assert compute_mfu(1e9, 10.0, 20e9) == pytest.approx(0.5)
        assert compute_mfu(0, 10.0, 20e9) is None
        assert compute_mfu(1e9, 10.0, None) is None

    def test_tpu_datasheet_table(self):
        assert tpu_peak_flops_per_sec("TPU v5 lite", 1) == 197e12
        assert tpu_peak_flops_per_sec("TPU v5e", 4) == 4 * 197e12
        assert tpu_peak_flops_per_sec("TPU v5p", 1) == 459e12
        assert tpu_peak_flops_per_sec("TPU v4", 1) == 275e12
        assert tpu_peak_flops_per_sec("TPU v6e", 1) == 918e12
        # v5p must not fall through to the bare-v5 bucket and vice versa
        assert tpu_peak_flops_per_sec("TPU v5", 1) == 459e12
        # a TPU that is not in the table is an error, never a null peak
        with pytest.raises(KeyError, match="Unknown Gen"):
            tpu_peak_flops_per_sec("Unknown Gen", 1)

    def test_cpu_backend_peak_is_measured_and_nonnull(self):
        """On the CPU test backend the peak must come from the measured
        matmul basis — this is what makes step_profile's mfu non-null off-TPU."""
        peak, basis = peak_flops_per_sec()
        assert basis == "cpu_measured_matmul"
        assert peak is not None and peak > 0

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("FRCNN_CPU_PEAK_FLOPS", "123e9")
        assert measured_cpu_peak_flops_per_sec() == pytest.approx(123e9)

    def test_a_measurement_entry_point_never_carries_on_on_the_cpu(self, capsys):
        with pytest.raises(SystemExit) as exc:
            require_accelerator("some_on_chip_script")
        assert exc.value.code not in (0, None)
        assert "no accelerator" in str(exc.value.code)
        assert "cpu" in str(exc.value.code)  # says what it found
        assert capsys.readouterr().out == ""


class TestHealthMetrics:
    def test_nonfinite_count(self):
        tree = {
            "a": jnp.array([1.0, jnp.nan, jnp.inf]),
            "b": jnp.ones((2, 2)),
            "c": jnp.array([1, 2], jnp.int32),  # integer leaves don't count
        }
        assert int(nonfinite_count(tree)) == 2
        assert int(nonfinite_count({"a": jnp.ones(3)})) == 0

    def test_health_metrics_values(self):
        g = {"w": jnp.full((3,), 2.0)}
        p = {"w": jnp.full((3,), 4.0)}
        u = {"w": jnp.full((3,), 1.0)}
        m = health_metrics(g, p, u)
        assert set(m) == set(HEALTH_KEYS)
        assert float(m["grad_norm"]) == pytest.approx(math.sqrt(12.0))
        assert float(m["param_norm"]) == pytest.approx(math.sqrt(48.0))
        assert float(m["update_norm"]) == pytest.approx(math.sqrt(3.0))
        assert float(m["update_ratio"]) == pytest.approx(0.25)
        assert int(m["nonfinite_count"]) == 0

    @pytest.mark.slow  # compiles a full train step (~1 min on CPU); the
    # fast tier still exercises the health keys through test_device_cache's
    # fed-vs-cached all-metric-keys comparison
    def test_health_on_tiny_train_step(self):
        """A real jitted step must emit the health scalars alongside the
        per-component losses — and they must be sane on healthy training."""
        from replication_faster_rcnn_tpu.config import (
            DataConfig,
            FasterRCNNConfig,
            MeshConfig,
            ModelConfig,
            TrainConfig,
        )
        from replication_faster_rcnn_tpu.data import SyntheticDataset
        from replication_faster_rcnn_tpu.data.loader import collate
        from replication_faster_rcnn_tpu.train.train_step import (
            create_train_state,
            make_optimizer,
            make_train_step,
        )

        cfg = FasterRCNNConfig(
            model=ModelConfig(backbone="resnet18", roi_op="align",
                              compute_dtype="float32"),
            data=DataConfig(dataset="synthetic", image_size=(64, 64),
                            max_boxes=8),
            train=TrainConfig(batch_size=2, n_epoch=1),
            mesh=MeshConfig(num_data=1),
        )
        ds = SyntheticDataset(cfg.data, length=2)
        batch = {k: jnp.asarray(v) for k, v in collate([ds[0], ds[1]]).items()}
        tx, _ = make_optimizer(cfg, steps_per_epoch=1)
        model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
        _, metrics = jax.jit(make_train_step(model, cfg, tx))(state, batch)
        metrics = jax.device_get(metrics)
        # per-component losses AND health scalars in one metrics dict
        for key in ("loss", "rpn_cls_loss", "rpn_reg_loss", "head_cls_loss",
                    "head_reg_loss", *HEALTH_KEYS):
            assert key in metrics, key
        assert float(metrics["grad_norm"]) > 0
        assert float(metrics["param_norm"]) > 0
        assert int(metrics["nonfinite_count"]) == 0
        assert float(metrics["update_ratio"]) == pytest.approx(
            float(metrics["update_norm"]) / float(metrics["param_norm"]),
            rel=1e-4,
        )


class TestReport:
    def _run_dir(self, tmp_path):
        d = tmp_path / "run"
        d.mkdir()
        tr = SpanTracer(str(d / "trace.json"))
        for _ in range(3):
            with tr.span("step/dispatch", cat="step"):
                pass
        with tr.span("data/fetch", cat="data"):
            pass
        tr.flush()
        with open(d / "metrics.jsonl", "w") as f:
            for step in (10, 20):
                f.write(json.dumps({
                    "step": step, "loss": 2.0 / step, "grad_norm": 1.5,
                    "nonfinite_count": 0.0,
                }) + "\n")
            f.write("{torn line")  # killed-run tail must not break parsing
        with open(d / "watchdog.jsonl", "w") as f:
            f.write(json.dumps({
                "kind": "stall", "elapsed_since_progress_s": 12.0,
                "last_step": 20, "last_phase": "train",
                "last_span": {"name": "step/dispatch"},
            }) + "\n")
        return str(d)

    def test_phase_table_aggregates(self):
        events = [
            {"name": "a", "ph": "X", "dur": 1000.0},
            {"name": "a", "ph": "X", "dur": 3000.0},
            {"name": "b", "ph": "X", "dur": 500.0},
            {"name": "c", "ph": "C"},  # counters don't aggregate
        ]
        rows = phase_table(events)
        assert [r["name"] for r in rows] == ["a", "b"]
        assert rows[0] == {"name": "a", "count": 2, "total_ms": 4.0,
                           "mean_ms": 2.0, "max_ms": 3.0}

    def test_health_summary(self):
        rows = [{"step": 1, "loss": 2.0}, {"step": 2, "loss": 1.0},
                {"event": "stall"}]
        h = health_summary(rows)
        assert h["rows"] == 2 and h["last_step"] == 2
        assert h["metrics"]["loss"] == {"last": 1.0, "max": 2.0, "min": 1.0}

    def test_summarize_and_format(self, tmp_path):
        summary = summarize_run(self._run_dir(tmp_path))
        assert set(summary["artifacts"]) == {
            "trace.json", "metrics.jsonl", "watchdog.jsonl"
        }
        assert summary["incidents"]["stalls"] == 1
        text = format_report(summary)
        assert "step/dispatch" in text
        assert "grad_norm" in text
        assert "1 stall(s)" in text

    def test_rank_suffixed_artifacts_merge(self, tmp_path):
        d = self._run_dir(tmp_path)
        # rank-1 siblings, as a 2-process trainer writes them
        tr = SpanTracer(os.path.join(d, "trace.rank1.json"))
        with tr.span("step/dispatch", cat="step"):
            pass
        tr.flush()
        with open(os.path.join(d, "metrics.rank1.jsonl"), "w") as f:
            f.write(json.dumps({"step": 10, "loss": 0.2,
                                "process_index": 1}) + "\n")
        with open(os.path.join(d, "watchdog.rank1.jsonl"), "w") as f:
            f.write(json.dumps({"kind": "stall",
                                "elapsed_since_progress_s": 5.0,
                                "last_step": 10, "last_phase": "train",
                                "last_span": None}) + "\n")

        summary = summarize_run(d)
        assert summary["ranks"] == [0, 1]
        assert "trace.rank1.json" in summary["artifacts"]
        # spans merged: 3 coordinator dispatches + 1 from rank 1
        dispatch = next(r for r in summary["phases"]
                        if r["name"] == "step/dispatch")
        assert dispatch["count"] == 4
        # health rows merged and attributed per rank
        assert summary["health"]["per_rank"][0]["rows"] == 2
        assert summary["health"]["per_rank"][1] == {
            "rows": 1, "last_step": 10
        }
        # incidents summed across ranks
        assert summary["incidents"]["stalls"] == 2
        text = format_report(summary)
        assert "2 ranks" in text and "rank 1: 1 step rows" in text

    def test_fleet_snapshot_renders_router_and_replicas(self, tmp_path):
        d = self._run_dir(tmp_path)
        snap = {
            "router": {"requests": 12, "cache_hits": 2, "failovers": 1,
                       "hedges": 3, "hedge_wins": 1, "unavailable": 0},
            "replicas": {
                "r0": {"ok": 6, "fail": 0,
                       "breaker": {"state": "closed", "opens": 0}},
                "r1": {"ok": 4, "fail": 2,
                       "breaker": {"state": "open", "opens": 1}},
            },
            "registry": {
                "r0": {"state": "healthy", "role": "serving"},
                "r1": {"state": "dead", "role": "serving"},
            },
        }
        with open(os.path.join(d, "fleet.jsonl"), "w") as f:
            f.write(json.dumps({"router": {"requests": 1}}) + "\n")
            f.write(json.dumps(snap) + "\n")  # later snapshot wins
        summary = summarize_run(d)
        assert "fleet.jsonl" in summary["artifacts"]
        assert summary["fleet"]["router"]["requests"] == 12
        text = format_report(summary)
        assert "fleet router" in text
        assert "1 failover(s)" in text
        assert "breaker=open (1 open(s))" in text
        assert "dead" in text

    def test_cli_telemetry_subcommand(self, tmp_path, capsys):
        from replication_faster_rcnn_tpu import cli

        rc = cli.main(["telemetry", self._run_dir(tmp_path)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "phase time" in out and "train health" in out

    def test_cli_telemetry_empty_dir_fails(self, tmp_path, capsys):
        from replication_faster_rcnn_tpu import cli

        rc = cli.main(["telemetry", str(tmp_path)])
        assert rc == 1
        assert "no telemetry artifacts" in capsys.readouterr().out


class TestMetricLoggerTelemetry:
    def test_event_rows_distinguishable_from_steps(self, tmp_path):
        from replication_faster_rcnn_tpu.utils.logging import MetricLogger

        path = str(tmp_path / "m.jsonl")
        lg = MetricLogger(stream=io.StringIO(), jsonl_path=path)
        lg.log(5, {"loss": 1.0, "grad_norm": np.float32(2.0)})
        lg.event("stall", elapsed_s=3.5, last_phase="train")
        rows = [json.loads(line) for line in open(path)]
        assert rows[0]["step"] == 5 and rows[0]["grad_norm"] == 2.0
        assert rows[1]["event"] == "stall" and "step" not in rows[1]

    def test_log_survives_non_numeric_values(self):
        from replication_faster_rcnn_tpu.utils.logging import MetricLogger

        buf = io.StringIO()
        MetricLogger(stream=buf).log(1, {"loss": 1.0, "note": "resumed"})
        assert "note=resumed" in buf.getvalue()


@pytest.mark.slow  # full Trainer epoch, like test_trainer.py
class TestTrainerTelemetryIntegration:
    def test_training_run_produces_artifacts(self, tmp_path):
        """Acceptance: a telemetry-enabled training run yields a loadable
        Chrome-trace JSON plus JSONL rows carrying grad_norm, the
        per-component losses, and nonfinite_count."""
        from replication_faster_rcnn_tpu.config import (
            DataConfig,
            FasterRCNNConfig,
            MeshConfig,
            ModelConfig,
            TrainConfig,
        )
        from replication_faster_rcnn_tpu.data import SyntheticDataset
        from replication_faster_rcnn_tpu.train.trainer import Trainer

        cfg = FasterRCNNConfig(
            model=ModelConfig(backbone="resnet18", roi_op="align",
                              compute_dtype="float32"),
            data=DataConfig(dataset="synthetic", image_size=(64, 64),
                            max_boxes=8),
            train=TrainConfig(batch_size=2, n_epoch=1),
            mesh=MeshConfig(num_data=1),
        )
        ds = SyntheticDataset(cfg.data, length=4)
        tdir = str(tmp_path / "telemetry")
        trainer = Trainer(
            cfg, workdir=str(tmp_path / "ckpt"), dataset=ds,
            telemetry_dir=tdir, stall_timeout_s=600.0,
        )
        try:
            trainer.train(log_every=1)
        finally:
            from replication_faster_rcnn_tpu.telemetry import spans

            spans.set_tracer(None)  # don't leak the tracer into other tests

        doc = json.load(open(os.path.join(tdir, "trace.json")))
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert {"data/fetch", "step/dispatch", "step/sync"} <= names

        rows = [json.loads(line) for line in open(os.path.join(tdir, "metrics.jsonl"))]
        step_rows = [r for r in rows if "step" in r]
        assert step_rows, "no step metrics logged"
        for key in ("grad_norm", "rpn_cls_loss", "rpn_reg_loss",
                    "head_cls_loss", "head_reg_loss", "nonfinite_count"):
            assert key in step_rows[0], key

        assert json.load(open(os.path.join(tdir, "progress.json")))["step"] > 0

        # and the CLI report reads the run back
        from replication_faster_rcnn_tpu import cli

        assert cli.main(["telemetry", tdir]) == 0
