"""The step program names its stages, and the program's spans ride the
profiler's clock (telemetry/stages.py, telemetry/spans.py, train/trainer.py).

CPU only, tiny sizes: what is checked is names, metadata and clocks' agreement,
never a time. The step programs are lowered, not compiled: what the compiled
module carries as `op_name` metadata is the lowered module's location names.
"""

import contextlib
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.config import (
    DataConfig,
    FasterRCNNConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from replication_faster_rcnn_tpu.telemetry import spans as tspans
from replication_faster_rcnn_tpu.telemetry import stages


def _cfg(n_data=1, **train_kw):
    return FasterRCNNConfig(
        model=ModelConfig(backbone="resnet18", roi_op="pool", compute_dtype="float32"),
        data=DataConfig(dataset="synthetic", image_size=(64, 64), max_boxes=8),
        train=TrainConfig(batch_size=2 * n_data, n_epoch=1, **train_kw),
        mesh=MeshConfig(num_data=n_data),
    )


def _batch(cfg, stacked=0):
    """Abstract shapes of one batch with uint8 pixels (so that `preprocess`
    has work to do under `frcnn.input`); `stacked` adds a leading [K] axis."""
    b, (h, w), m = cfg.train.batch_size, cfg.data.image_size, cfg.data.max_boxes
    lead = (stacked,) if stacked else ()
    return {
        "image": jax.ShapeDtypeStruct(lead + (b, h, w, 3), jnp.uint8),
        "boxes": jax.ShapeDtypeStruct(lead + (b, m, 4), jnp.float32),
        "labels": jax.ShapeDtypeStruct(lead + (b, m), jnp.int32),
        "mask": jax.ShapeDtypeStruct(lead + (b, m), jnp.bool_),
    }


def _lowered(kind):
    """One of the three step programs, lowered."""
    from replication_faster_rcnn_tpu.train.train_step import (
        build_multi_step,
        create_train_state,
        make_optimizer,
        make_train_step,
    )

    if kind == "shard_map":
        from replication_faster_rcnn_tpu.parallel import make_mesh
        from replication_faster_rcnn_tpu.parallel.spmd import make_shard_map_train_step

        cfg = _cfg(n_data=2, backend="spmd")
        tx, _ = make_optimizer(cfg, steps_per_epoch=1)
        mesh = make_mesh(cfg.mesh, jax.devices()[:2])
        step, _ = make_shard_map_train_step(cfg, tx, mesh)
        state = jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0), tx)[1])
        return step.lower(state, _batch(cfg))
    cfg = _cfg()
    tx, _ = make_optimizer(cfg, steps_per_epoch=1)
    model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
    step = make_train_step(model, cfg, tx)
    if kind == "fused_k2":
        step = build_multi_step(step, 2)
    batch = _batch(cfg, stacked=2 if kind == "fused_k2" else 0)
    return jax.jit(step).lower(state, batch)


def _op_names(lowered):
    """The operations' location names, `loc("jit(f)/scope/op"(...))`; a file
    location, `loc("/path/x.py":1:2)`, is not one. Inside a scan body or a
    called function they are relative to it: no `jit(...)` prefix there."""
    return set(re.findall(r'loc\("([^"]*/[^"]*)"\(', lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def jit_step():
    return _lowered("jit")


class TestStageScopes:
    def test_names_are_fixed_and_distinct(self):
        assert len(set(stages.STAGES)) == len(stages.STAGES) == 9
        assert all(re.fullmatch(r"frcnn\.[a-z_]+", s) for s in stages.STAGES)

    @pytest.mark.parametrize("kind", ["jit", "shard_map", "fused_k2"])
    def test_every_stage_is_in_the_lowered_steps_op_names(self, kind, request):
        """Forward as `jvp(frcnn.x)`, backward as `transpose(jvp(frcnn.x))`;
        the stages no gradient flows through (targets, proposals, update)
        appear forward only, and `frcnn.update` outside `value_and_grad`."""
        names = _op_names(request.getfixturevalue("jit_step") if kind == "jit" else _lowered(kind))
        both = (stages.TRUNK, stages.RPN, stages.ROI_POOL, stages.BOX_HEAD)
        for scope in stages.STAGES:
            held = [n for n in names if scope in n]
            assert held, f"{scope} in no op_name of the {kind} step"
            if scope in both:
                forward = [n for n in held if "transpose(" not in n]
                backward = [n for n in held if "transpose(jvp(" in n]
                assert forward and backward, scope
        # nested scopes keep their order: roi_pool inside box_head, input inside trunk
        assert any(f"jvp({stages.BOX_HEAD})" in n and n.rfind(stages.ROI_POOL) > n.find(stages.BOX_HEAD) for n in names)
        assert any(f"jvp({stages.TRUNK})" in n and stages.INPUT in n for n in names)
        assert any(f"{stages.UPDATE}/" in n and "jvp(" not in n for n in names)

    def test_roi_pool_custom_vjp_keeps_the_scope_both_ways(self, jit_step):
        """ROIPool's backward is hand-written (`jax.custom_vjp`): its
        operations must still read under `frcnn.roi_pool`, as `transpose(`,
        in the compiled step's `op_name`s, or the stage cut would put them
        down as unscoped. The two selection matmuls stand for the rest."""
        ops = set(re.findall(r'op_name="([^"]*)"', jit_step.compile().as_text()))
        forward = [n for n in ops if n.endswith("rjk,khc->rjhc/dot_general")]
        backward = [n for n in ops if n.endswith("rjk,rjhc->khc/dot_general")]
        assert forward and backward
        for n in forward:
            assert f"jvp({stages.BOX_HEAD})" in n and f"/{stages.ROI_POOL}/" in n and "transpose(" not in n, n
        for n in backward:
            assert f"transpose(jvp({stages.BOX_HEAD}))" in n and f"/{stages.ROI_POOL}/" in n, n
        # and nothing of `roi_pool`'s own jit is outside the scope
        strays = [n for n in ops if "jit(roi_pool)" in n and stages.ROI_POOL not in n]
        assert not strays, strays[:3]

    def test_stem_pool_custom_vjp_keeps_the_scope_both_ways(self, jit_step):
        """The stem's norm + ReLU + max-pool is two kernels with a
        hand-written backward (`ops/pool_ops.py`): the forward must read
        `jvp(frcnn.trunk)` and the backward `transpose(jvp(frcnn.trunk))`,
        or `stage_trunk_ms` would lose them to the unscoped time."""
        names = _op_names(jit_step)
        forward = [n for n in names if "/stem_pool/" in n]
        backward = [n for n in names if "/stem_pool_backward/" in n]
        assert forward and backward
        for n in forward:
            assert f"jvp({stages.TRUNK})" in n and "transpose(" not in n, n
        for n in backward:
            assert f"transpose(jvp({stages.TRUNK}))" in n, n

    def test_scopes_change_no_instruction(self, jit_step, monkeypatch):
        """The step lowered with `jax.named_scope` patched to a null context
        is the same program, locations (the later `metadata={...}`) apart."""
        monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = _lowered("jit")
        leaked = sorted(n for n in _op_names(bare) if "frcnn." in n)
        assert not leaked, leaked[:5]
        assert any("frcnn." in n for n in _op_names(jit_step))
        assert bare.as_text() == jit_step.as_text()


class TestSequenceModelStageScopes:
    """The sequence model's step (models/lm.py) names its stages under the
    same prefix: perf/stagecut.py reads both programs, and `frcnn.update` is
    one stage of both."""

    @pytest.fixture(scope="class")
    def lm_names(self):
        from replication_faster_rcnn_tpu.config import get_config
        from replication_faster_rcnn_tpu.train.train_step import create_train_state, make_optimizer, make_train_step

        cfg = get_config("trinity_tiny")
        tx, _ = make_optimizer(cfg, steps_per_epoch=1)
        state = jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0), tx)[1])
        batch = {"tokens": jax.ShapeDtypeStruct((2, cfg.data.seq_len), jnp.int32)}
        return _op_names(jax.jit(make_train_step(None, cfg, tx)).lower(state, batch))

    def test_names_are_fixed_distinct_and_share_the_update_stage(self):
        assert len(set(stages.LM_STAGES)) == len(stages.LM_STAGES) == 13
        assert all(re.fullmatch(r"frcnn\.[a-z_]+", s) for s in stages.LM_STAGES)
        assert set(stages.LM_STAGES) & set(stages.STAGES) == {stages.UPDATE}

    # the delta-rule layer's two scopes are in the hybrid preset's step: tests/test_lm_hybrid.py; the
    # latent layer's and the multi-token-prediction module's in the latent preset's: tests/test_lm_latent.py
    @pytest.mark.parametrize(
        "scope",
        [
            s for s in stages.LM_STAGES
            if s not in (stages.UPDATE, stages.LM_LINEAR_ATTENTION, stages.LM_DELTA_CORE, stages.LM_LATENT, stages.LM_MTP)
        ],
    )
    def test_every_stage_is_in_the_lowered_step_forward_and_backward(self, lm_names, scope):
        """Each layer is recomputed in the backward pass (`jax.checkpoint`):
        a stage reads `jvp(...)` forward and under `transpose(` backward."""
        held = [n for n in lm_names if scope in n]
        assert [n for n in held if "transpose(" not in n], scope
        assert [n for n in held if "transpose(" in n], scope

    def test_nested_scopes_keep_their_order_and_the_update_is_outside_the_gradient(self, lm_names):
        assert any(n.rfind(stages.LM_ATTN_CORE) > n.find(stages.LM_ATTENTION) >= 0 for n in lm_names)
        assert any(n.rfind(stages.LM_EXPERT_MM) > n.find(stages.LM_EXPERTS) >= 0 for n in lm_names)
        assert any(f"{stages.UPDATE}/" in n and "jvp(" not in n for n in lm_names)
        assert not [n for n in lm_names if any(s in n for s in set(stages.STAGES) - {stages.UPDATE})]

    def test_few_operations_of_the_step_lie_in_no_stage(self, lm_names):
        """Of the step's own operations (an absolute name: inside a kernel's
        interpreter or a scan body names are relative), under a twentieth is
        in no stage: the step's rng fold and reshapes between stages."""
        own = [n for n in lm_names if n.startswith("jit(train_step)/")]
        loose = [n for n in own if "frcnn." not in n]
        assert len(own) > 200 and len(loose) <= 0.05 * len(own), sorted(loose)


# ------------------------------------------------- spans on the profiler


def _host_events(trace_dir, names):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, ev.duration_ns, dict(ev.stats)))
    return out


class TestSpansOnTheProfilersClock:
    def test_open_event_is_first_and_names_the_directory(self, tmp_path):
        tr = tspans.SpanTracer(str(tmp_path / "tel" / "trace.json"))
        with tr.span("step/dispatch", step=1):
            pass
        first = tr.to_dict()["traceEvents"][0]
        assert first["name"] == "telemetry/open" and first["ph"] == "i"
        assert first["args"]["dir"] == str(tmp_path / "tel")
        assert os.path.isabs(first["args"]["dir"])
        # a tracer with no file has no directory to name
        assert tspans.SpanTracer().to_dict()["traceEvents"] == []

    def test_a_tiny_profiled_loop_finds_the_program_spans_in_the_xplane(self, tmp_path):
        """`step/dispatch` and `data/device_put` of a two-step loop are among
        the xplane's host events, with the same `step`, each no longer than
        its Chrome twin (the span takes its start before it enters the
        annotation and its end after it exits), and the closer of the two
        steps within 50 us of it: one step's gap may hold a preemption of
        the thread between the two clocks' readings."""
        from replication_faster_rcnn_tpu.data import SyntheticDataset
        from replication_faster_rcnn_tpu.data.loader import collate
        from replication_faster_rcnn_tpu.train.trainer import Trainer

        cfg = _cfg()
        ds = SyntheticDataset(cfg.data, length=4)
        batch = collate([ds[0], ds[1]])
        trainer = Trainer(
            cfg, workdir=str(tmp_path / "ckpt"), dataset=ds,
            telemetry_dir=str(tmp_path / "tel"), stall_timeout_s=600.0,
        )
        profile = str(tmp_path / "profile")
        try:
            jax.block_until_ready(trainer.train_one_batch(batch))  # compiles
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(profile, profiler_options=opts)
            try:
                for _ in range(2):
                    metrics = trainer.train_one_batch(batch)
                jax.block_until_ready(metrics)
            finally:
                jax.profiler.stop_trace()
            trainer.flush_telemetry()
        finally:
            tspans.set_tracer(None)
        with open(os.path.join(str(tmp_path / "tel"), "trace.json")) as f:
            chrome = json.load(f)["traceEvents"]
        assert chrome[0]["name"] == "telemetry/open"
        for name in ("step/dispatch", "data/device_put"):
            mine = {e["args"]["step"]: e["dur"] for e in chrome if e["name"] == name}
            assert set(mine) == {1, 2, 3}, name
            theirs = {st["step"]: dur for n, dur, st in _host_events(profile, {name})}
            assert set(theirs) == {2, 3}, name  # the profiler ran over steps 2 and 3
            gaps = {step: mine[step] - dur_ns / 1e3 for step, dur_ns in theirs.items()}
            assert all(gap >= -1.0 for gap in gaps.values()), (name, gaps)
            assert min(gaps.values()) < 50.0, (name, gaps)

    def test_a_chunk_carries_its_first_step(self, tmp_path):
        tr = tspans.SpanTracer()
        with tr.span("step/dispatch", cat="step", steps=2, step=5):
            pass
        assert tr.to_dict()["traceEvents"][0]["args"] == {"steps": 2, "step": 5}

    def test_null_tracer_makes_no_profiler_call(self, monkeypatch):
        def boom(*a, **k):
            raise AssertionError("the null tracer reached jax.profiler")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
        with tspans.NULL_TRACER.span("step/dispatch", cat="step", step=1):
            pass
        # and an enabled tracer does reach it
        with pytest.raises(AssertionError):
            with tspans.SpanTracer().span("step/dispatch"):
                pass

    def test_trainer_without_telemetry_builds_no_span(self, tmp_path, monkeypatch):
        """`telemetry_dir=None`: the null tracer, no profiler call, no span
        object, through a staged and a host batch."""
        from replication_faster_rcnn_tpu.data import SyntheticDataset
        from replication_faster_rcnn_tpu.data.loader import collate
        from replication_faster_rcnn_tpu.train.trainer import Trainer

        def boom(*a, **k):
            raise AssertionError("a span was built with telemetry off")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
        monkeypatch.setattr(tspans.SpanTracer, "span", boom)
        cfg = _cfg()
        ds = SyntheticDataset(cfg.data, length=2)
        trainer = Trainer(cfg, workdir=str(tmp_path / "ckpt"), dataset=ds)
        assert trainer.tracer is tspans.NULL_TRACER
        staged = trainer._stage_batch(collate([ds[0], ds[1]]))
        metrics = trainer.train_one_batch(staged=staged)
        assert np.isfinite(float(jax.device_get(metrics)["loss"]))

    def test_spans_and_report_import_without_jax(self):
        code = (
            "import sys\n"
            "import replication_faster_rcnn_tpu.telemetry.spans as s\n"
            "import replication_faster_rcnn_tpu.telemetry.report\n"
            "import replication_faster_rcnn_tpu.telemetry.stages\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "t = s.SpanTracer()\n"
            "assert 'jax' not in sys.modules\n"
        )
        got = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
        assert got.returncode == 0, got.stderr
