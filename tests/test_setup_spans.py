"""Set-up seen from inside the program: the compile listener's `jit/*`
spans (`telemetry/compiles.py`), the trainer's `setup/*` spans, and the five
readers that split `setup_s` (`perf/metrics/setup_*.py`)."""

import glob
import json
import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from replication_faster_rcnn_tpu.analysis import strict as strict_mod
from replication_faster_rcnn_tpu.analysis.strict import StrictHarness, StrictViolation
from replication_faster_rcnn_tpu.telemetry import compiles
from replication_faster_rcnn_tpu.telemetry import spans as tspans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import harness, manifest  # noqa: E402

READERS = ("setup_before_trainer_s", "setup_trainer_s", "setup_jit_s", "setup_cache_misses", "setup_unattributed_s")
CELLS = [w["name"] for w in manifest.load(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]  # every cell reports the five


def _reader(name):
    return harness.load_file(os.path.join(ROOT, "perf", "metrics", f"{name}.py"))


def _read_all(ctx):
    return {name: _reader(name).read(ctx) for name in READERS}


@pytest.fixture
def tracer():
    compiles.install()
    tr = tspans.SpanTracer()
    prev = tspans.set_tracer(tr)
    try:
        yield tr
    finally:
        tspans.set_tracer(prev)


def _jit_events(tr):
    return [e for e in tr.to_dict()["traceEvents"] if e["name"].startswith("jit/")]


# ----------------------------------------------------------- the listener


class TestCompileSpans:
    def test_a_compile_inside_a_span_lies_inside_it_on_its_thread_and_once(self, tracer):
        x = jnp.ones(3)
        f = jax.jit(lambda v: v * 3 + 1)
        n = len(tracer.to_dict()["traceEvents"])
        with tracer.span("step/dispatch", cat="step", step=1):
            f(x).block_until_ready()
        events = tracer.to_dict()["traceEvents"]
        outer = next(e for e in events if e["name"] == "step/dispatch")
        jits = [e for e in events[n:] if e["name"].startswith("jit/")]
        assert {e["name"] for e in jits} == {"jit/trace", "jit/lower", "jit/compile"}
        for e in jits:
            assert e["tid"] == outer["tid"] and e["cat"] == "compile"
            assert outer["ts"] <= e["ts"] and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"], e
        # the function's own phases in their order, each ending before the next begins
        # (its trace holds the traces of the jax.numpy functions it calls)
        mine = [e for e in jits if "<lambda>" in e["args"]["fun"]]
        assert sorted(e["name"] for e in mine) == ["jit/compile", "jit/lower", "jit/trace"]
        order = {e["name"]: e for e in mine}
        assert order["jit/trace"]["ts"] + order["jit/trace"]["dur"] <= order["jit/lower"]["ts"]
        assert order["jit/lower"]["ts"] + order["jit/lower"]["dur"] <= order["jit/compile"]["ts"]
        n = len(events)
        with tracer.span("step/dispatch", cat="step", step=2):
            f(x).block_until_ready()
        assert [e["name"] for e in tracer.to_dict()["traceEvents"][n:]] == ["step/dispatch"]

    def test_without_a_cache_the_outcome_is_off(self, tracer):
        jax.jit(lambda v: v - 5)(jnp.ones(4)).block_until_ready()
        outcome = [e["args"]["cache"] for e in _jit_events(tracer) if e["name"] == "jit/compile"]
        assert outcome and set(outcome) == {"off"}

    def test_the_persistent_cache_reads_miss_then_hit(self, tracer, tmp_path):
        from jax.experimental.compilation_cache import compilation_cache as cc

        keys = (
            "jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes",
        )
        before = {k: getattr(jax.config, k) for k in keys}
        x = jnp.ones(6)
        try:
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            cc.reset_cache()
            outcomes = []
            for _ in range(2):
                n = len(_jit_events(tracer))
                jax.jit(lambda v: v * 17 - 2)(x).block_until_ready()
                jax.clear_caches()
                mine = [e for e in _jit_events(tracer)[n:] if e["name"] == "jit/compile" and "<lambda>" in e["args"]["fun"]]
                outcomes.append([e["args"]["cache"] for e in mine])
        finally:
            for k, v in before.items():
                jax.config.update(k, v)
            cc.reset_cache()
        assert outcomes == [["miss"], ["hit"]]

    def test_a_span_that_began_before_the_tracer_opened_is_clipped_to_its_open(self):
        tr = tspans.SpanTracer()
        assert tr.wall_us(time.time() - 100.0) == 0.0
        assert abs(tr.wall_us(time.time()) - tr.now_us()) < 1e4
        assert tspans.NULL_TRACER.wall_us(time.time()) == 0.0
        listener = compiles.CompileListener()
        prev = tspans.set_tracer(tr)
        try:
            now = time.time()
            listener.on_time_span(compiles.TRACE_EVENT, now - 100.0, now - 50.0, fun_name="gone")
            listener.on_time_span(compiles.TRACE_EVENT, now - 100.0, now, fun_name="straddles")
        finally:
            tspans.set_tracer(prev)
        (event,) = tr.to_dict()["traceEvents"]
        assert event["args"] == {"fun": "straddles"} and event["ts"] == 0.0 and event["dur"] > 0

    def test_a_cache_event_of_an_earlier_compile_is_not_this_ones(self):
        tr = tspans.SpanTracer()
        listener = compiles.CompileListener()
        prev = tspans.set_tracer(tr)
        try:
            listener.on_event("/jax/compilation_cache/cache_hits")
            start = time.time()
            listener.on_time_span(compiles.COMPILE_EVENT, start, time.time(), fun_name="f")
            start = time.time()
            listener.on_event("/jax/compilation_cache/compile_requests_use_cache")
            listener.on_time_span(compiles.COMPILE_EVENT, start, time.time(), fun_name="g")
            # another thread's cache event is not this thread's compile's
            other = threading.Thread(target=listener.on_event, args=("/jax/compilation_cache/cache_hits",))
            start = time.time()
            other.start()
            other.join(10.0)
            listener.on_time_span(compiles.COMPILE_EVENT, start, time.time(), fun_name="h")
        finally:
            tspans.set_tracer(prev)
        got = {e["args"]["fun"]: e["args"]["cache"] for e in tr.to_dict()["traceEvents"]}
        assert got == {"f": "off", "g": "miss", "h": "off"}
        totals = listener.totals()
        assert totals["events"]["/jax/compilation_cache/cache_hits"] == 2 and totals["durations"] == {}

    def test_with_tracing_off_the_listener_still_counts(self):
        assert tspans.current_tracer() is tspans.NULL_TRACER
        listener = compiles.install()
        x = jnp.ones(2)
        before = listener.backend_compiles()
        jax.jit(lambda v: v * 23)(x).block_until_ready()
        assert listener.backend_compiles() == before + 1
        assert compiles.install() is listener  # once a process


class TestOneListener:
    def test_only_the_telemetry_module_registers_with_jax_monitoring(self):
        """The package and the scripts at the root read compiles through
        `telemetry/compiles.py` alone."""
        pattern = re.compile(r"register_event\w*_listener")
        package = os.path.join(ROOT, "replication_faster_rcnn_tpu")
        files = glob.glob(os.path.join(ROOT, "*.py")) + glob.glob(os.path.join(package, "**", "*.py"), recursive=True)
        found = []
        for path in files:
            with open(path) as f:
                if pattern.search(f.read()):
                    found.append(os.path.relpath(path, ROOT))
        assert found == [os.path.join("replication_faster_rcnn_tpu", "telemetry", "compiles.py")]

    def test_the_strict_gate_still_raises_and_counts_with_the_one_listener(self):
        f = jax.jit(lambda v: v * 29)
        x4, x8 = jnp.zeros(4), jnp.zeros(8)
        h = StrictHarness(warmup_dispatches=1)
        with h.session():
            base = strict_mod.compile_event_count()
            with h.dispatch("p", f):
                f(x4)
            assert strict_mod.compile_event_count() == base + 1
            assert strict_mod.compile_event_count() == compiles.install().backend_compiles()
            with h.dispatch("p", f):
                f(x4)
            with pytest.raises(StrictViolation, match="1 backend_compile event"):
                with h.dispatch("p", f):
                    f(x8)
        assert h.report()["programs"]["p"]["recompiles_after_warmup"] == 1
        assert h.report()["compile_events_total"] == 2

    def test_chip_smokes_compile_log_sums_what_a_listener_of_its_own_would(self):
        """`CompileLog.take` reads the shared totals as deltas: the same keys
        and values two listeners registered for the leg would have summed."""
        import chip_smoke
        import jax.monitoring as mon

        def wanted(name):
            return "compil" in name or "cache" in name

        own = {"seconds": {}, "counts": {}}

        def on_duration(name, secs, **_):
            if wanted(name):
                own["seconds"][name] = own["seconds"].get(name, 0.0) + secs

        def on_event(name, **_):
            if wanted(name):
                own["counts"][name] = own["counts"].get(name, 0) + 1

        log = chip_smoke.CompileLog()
        log.install()
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        try:
            jax.jit(lambda v: v * 31 + 3)(jnp.ones(5)).block_until_ready()
            got = log.take()
        finally:
            mon.unregister_event_duration_listener(on_duration)
            mon.unregister_event_listener(on_event)
        assert set(got["seconds"]) == set(own["seconds"]) and got["counts"] == dict(sorted(own["counts"].items()))
        assert got["seconds"] == {k: round(v, 3) for k, v in sorted(own["seconds"].items())}
        assert list(got["seconds"]) == sorted(got["seconds"])
        assert log.take() == {"seconds": {}, "counts": {}}  # a leg with no compile


# ------------------------------------------------------------- the readers


def _open(tid=1, age=2.5):
    args = {"dir": "/x/runs/cell/telemetry"}
    if age is not None:
        args["process_age_s"] = age
    return {"name": "telemetry/open", "ph": "i", "ts": 0.0, "tid": tid, "args": args}


def _x(name, ts, dur, tid=1, **args):
    return {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur), "tid": tid, "args": args}


class TestTheReaders:
    """Hand-made events, in microseconds; the window starts at 220."""

    def _ctx(self):
        spans = [
            _open(),
            _x("jit/trace", 0, 5, fun="early"),  # began before the tracer opened: clipped to its open
            _x("setup/trainer", 10, 100),
            _x("setup/init_state", 15, 50),
            _x("jit/trace", 20, 20, fun="init"),
            _x("jit/compile", 30, 30, fun="init", cache="miss"),
            _x("data/decode", 110, 40, tid=2),  # a loader thread: does not cover the main thread's gap
            _x("step/dispatch", 150, 100, step=1),  # begins in set-up, ends in the window
            _x("jit/compile", 160, 40, fun="train_step", cache="hit"),
            _x("jit/compile", 300, 10, fun="late", cache="miss"),  # in the window
        ]
        return {"spans": spans, "window": {"span_window_us": (220.0, 400.0)}}

    def test_each_reader_on_hand_made_events(self):
        got = _read_all(self._ctx())
        assert got["setup_before_trainer_s"] == 2.5
        # the jit union [0,5] + [20,60] + [160,200] inside [0, 220]
        assert got["setup_jit_s"] == pytest.approx(85e-6)
        # setup/trainer's 100 less its jit/* [20, 60]
        assert got["setup_trainer_s"] == pytest.approx(60e-6)
        assert got["setup_cache_misses"] == 1
        # [5, 10] and [110, 150] on the main thread: the loader's span does not count
        assert got["setup_unattributed_s"] == pytest.approx(45e-6)
        # the identity: + step/dispatch's part in set-up [150, 220] less its jit [160, 200]
        other = (70 - 40) * 1e-6
        total = got["setup_trainer_s"] + got["setup_jit_s"] + other + got["setup_unattributed_s"]
        assert total == pytest.approx(220e-6, abs=1e-12)

    def test_nothing_to_read_gives_none(self):
        assert set(_read_all({"spans": [], "window": {"span_window_us": (0.0, 1.0)}}).values()) == {None}
        # a program without the set-up instrumentation: an open instant, no age, no setup/* or jit/*
        parent = [_open(age=None), _x("step/dispatch", 50, 10, step=1), _x("data/device_put", 40, 5, step=1)]
        assert set(_read_all({"spans": parent, "window": {"span_window_us": (100.0, 200.0)}}).values()) == {None}
        # an untraced window holds no span time
        assert set(_read_all({"spans": self._ctx()["spans"], "window": {}}).values()) == {None}


class TestATinyTrainer:
    def test_every_reader_reads_and_the_identity_holds(self, tmp_path):
        """Two steps of a tiny detector's trainer with a telemetry directory,
        the window opened after them: each reader gives a number, the first
        dispatch holds its step's compile, and the set-up interval is cut
        whole to a microsecond."""
        from tests.test_stage_scopes import _cfg

        from replication_faster_rcnn_tpu.data import SyntheticDataset
        from replication_faster_rcnn_tpu.data.loader import collate
        from replication_faster_rcnn_tpu.train.trainer import Trainer

        cfg = _cfg()
        ds = SyntheticDataset(cfg.data, length=4)
        batch = collate([ds[0], ds[1]])
        tel = str(tmp_path / "tel")
        trainer = Trainer(cfg, workdir=str(tmp_path / "ckpt"), dataset=ds, telemetry_dir=tel, stall_timeout_s=600.0)
        try:
            for _ in range(2):
                metrics = trainer.train_one_batch(batch)
            jax.block_until_ready(metrics)
            window = (trainer.tracer.now_us(), trainer.tracer.now_us())
            trainer.flush_telemetry()
        finally:
            tspans.set_tracer(None)
        with open(os.path.join(tel, "trace.json")) as f:
            spans = json.load(f)["traceEvents"]
        ctx = {"spans": spans, "window": {"span_window_us": window}}
        got = _read_all(ctx)
        assert all(isinstance(v, (int, float)) for v in got.values()), got
        assert got["setup_before_trainer_s"] > 0 and got["setup_jit_s"] > 0 and got["setup_trainer_s"] > 0
        assert got["setup_cache_misses"] == 0  # no persistent cache on the CPU: every outcome is "off"

        opened = spans[0]
        assert opened["name"] == "telemetry/open"
        xs = [e for e in spans if e["ph"] == "X"]
        names = {e["name"] for e in xs}
        assert {"setup/trainer", "setup/dataset", "setup/loader", "setup/init_state", "setup/place_state"} <= names
        init = next(e for e in xs if e["name"] == "setup/init_state")
        assert any(e["name"] == "jit/compile" and e["args"]["fun"] == "jit(init)" and init["ts"] <= e["ts"] for e in xs)
        first = min((e for e in xs if e["name"] == "step/dispatch"), key=lambda e: e["ts"])
        step = next(e for e in xs if e["name"] == "jit/compile" and e["args"]["fun"] == "jit(train_step)")
        assert step["tid"] == first["tid"] == opened["tid"]
        assert first["ts"] <= step["ts"] and step["ts"] + step["dur"] <= first["ts"] + first["dur"]

        setup = harness.load_file(os.path.join(ROOT, "perf", "metrics", "setup_jit_s.py"))
        lo, hi = opened["ts"], window[0]
        trainer_span = next(e for e in xs if e["name"] == "setup/trainer")
        t_lo, t_hi = trainer_span["ts"], trainer_span["ts"] + trainer_span["dur"]
        jits = setup.clipped([e for e in xs if e["name"].startswith("jit/")], lo, hi)
        others = setup.clipped(
            [e for e in xs if e["tid"] == opened["tid"] and not e["name"].startswith("jit/")
             and (e["ts"] >= t_hi or e["ts"] + e["dur"] <= t_lo)], lo, hi,
        )
        other_s = (setup.union_us(others + jits) - setup.union_us(jits)) / 1e6
        total = got["setup_trainer_s"] + got["setup_jit_s"] + other_s + got["setup_unattributed_s"]
        assert abs(total - (hi - lo) / 1e6) < 1e-6


class TestTheBenchmarksEntries:
    """What the two benchmark tests that pin the end of `BENCHMARK.json`'s
    per-layer list (and stop there: tests/conftest.py) say beside that,
    said again by name, and the five entries as they stand. Entries a later
    cell appends stand behind the five, so nothing here reads a position
    from the end of a list."""

    def test_the_five_stand_at_the_end_and_every_cell_reports_them(self):
        """The five stand together, behind the ten, every cell of the
        record reports them, and what stands behind them is no cell's but
        the cells' that the record added after them."""
        record = manifest.load(os.path.join(ROOT, "BENCHMARK.json"))
        assert manifest.validate(record) == []
        names = [p["name"] for p in record["per_layer"]]
        first = names.index(READERS[0])
        entries = record["per_layer"][first : first + len(READERS)]
        assert [p["name"] for p in entries] == list(READERS)
        assert first > names.index("idle_unattributed_pct.resident")
        for p in entries:
            assert (p["layer"], p["moves"], p["better"], p["workloads"]) == ("set-up", "setup_s", "lower", CELLS)
            assert p["unit"] == ("count" if p["name"] == "setup_cache_misses" else "s")
            assert os.path.exists(os.path.join(ROOT, "perf", "metrics", f"{p['name']}.py"))
        older = {"r18c4.resident", "trinity_ep8.packed8k", "qwen3next_ep16.packed16k"}
        for p in record["per_layer"][first + len(READERS) :]:
            assert not older & set(p["workloads"]), p["name"]
        for cell in CELLS:
            reported = [m["name"] for m in manifest.Cell(ROOT, os.path.join(ROOT, "BENCHMARK.json"), cell).per_layer]
            at = reported.index(READERS[0])
            assert reported[at : at + len(READERS)] == list(READERS)

    def test_what_the_pinned_statements_stood_beside(self):
        record = manifest.load(os.path.join(ROOT, "BENCHMARK.json"))
        names = [p["name"] for p in record["per_layer"]]
        by_name = {p["name"]: p for p in record["per_layer"]}
        # test_cells_of_record.py: behind the ten, only the five reach the detector's cell
        behind = record["per_layer"][names.index("idle_unattributed_pct.resident") + 1 :]
        assert [p["name"] for p in behind if "r18c4.resident" in p["workloads"]] == list(READERS)
        detector = manifest.Cell(ROOT, os.path.join(ROOT, "BENCHMARK.json"), "r18c4.resident")
        upto = names[: names.index("idle_unattributed_pct.resident") + 1]
        assert [m["name"] for m in detector.per_layer] == upto + list(READERS)
        # hybrid/test_hybrid_cell.py: the hybrid cell's two list that cell alone and stand
        # right before the five; the record holds it and its configuration; the program's
        # preset is what the file states
        for name in ("stage_linear_attention_ms", "delta_rule_roofline"):
            assert by_name[name]["workloads"] == ["qwen3next_ep16.packed16k"]
        assert names.index("delta_rule_roofline") == names.index("stage_linear_attention_ms") + 1 == names.index(READERS[0]) - 1
        assert "qwen3next_ep16.packed16k" in [w["name"] for w in record["workloads"]]
        assert "qwen3_next_ep16" in [c["name"] for c in record["configs"]]
        from replication_faster_rcnn_tpu.config import get_config

        cell = manifest.Cell(ROOT, os.path.join(ROOT, "BENCHMARK.json"), "qwen3next_ep16.packed16k")
        harness.program_config(cell, 2_147_484_033, {}, "", get_config)

