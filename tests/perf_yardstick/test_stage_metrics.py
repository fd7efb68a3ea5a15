"""Tier-1 tests of the stage cut (perf/stagecut.py) and the ten readers built
on it: CPU only. Hand-built traces check the arithmetic; one traced rehearsal
of a tiny resident cell (perf/rehearsal/BENCHMARK.stages.json) walks the files,
the readers and the result line. No time or share from these runs means
anything.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import harness, manifest, stagecut, xtrace  # noqa: E402

STAGES_MANIFEST = os.path.join(ROOT, "perf", "rehearsal", "BENCHMARK.stages.json")
SEED = 2_147_484_003
NEW_METRICS = (
    "stage_trunk_ms", "stage_rpn_ms", "stage_targets_ms", "stage_proposals_ms", "stage_roi_pool_ms",
    "stage_box_head_ms", "stage_update_ms", "stage_backward_pct", "stage_unscoped_pct",
    "idle_unattributed_pct.resident",
)
MS = 1e6  # ns
SCOPES = stagecut.scope_re("frcnn.")  # the detector reference's SCOPE_PREFIX


def _line(name, opcode="fusion"):
    return f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p), kind=kLoop"


ORIGIN = {
    "fusion.1": "jit(train_step)/jit(main)/jvp(frcnn.trunk)/FasterRCNN.extract_features/trunk/conv1/conv_general_dilated",
    "fusion.2": "jit(train_step)/jit(main)/transpose(jvp(frcnn.box_head))/FasterRCNN.head_forward/head/frcnn.roi_pool/vmap(jit(roi_pool))/reduce_sum",
    "while.3": "jit(train_step)/jit(main)/jvp(frcnn.proposals)/FasterRCNN.propose/vmap(jit(nms_fixed_tiled))/while",
    "fusion.4": "jit(train_step)/jit(main)/jvp(frcnn.proposals)/FasterRCNN.propose/vmap(jit(nms_fixed_tiled))/while/body/gt",
    "fusion.5": "jit(train_step)/jit(main)/jvp()/reduce_sum",
    "fusion.6": "jit(train_step)/jit(main)/frcnn.update/mul",
}


def _chip():
    """20 ms window: trunk [0,4), roi_pool backward [4,6), a while [6,12)
    holding two fusions [7,9) and [9,11), an unscoped fusion [12,13), a copy
    the module does not name [13,14), idle [14,18), update [18,20)."""
    return [
        (_line("fusion.1"), 0 * MS, 4 * MS, {}),
        (_line("fusion.2"), 4 * MS, 2 * MS, {}),
        (_line("while.3", "while"), 6 * MS, 6 * MS, {}),
        (_line("fusion.4"), 7 * MS, 2 * MS, {}),
        (_line("fusion.4"), 9 * MS, 2 * MS, {}),
        (_line("fusion.5"), 12 * MS, 1 * MS, {}),
        (_line("copy.9", "copy"), 13 * MS, 1 * MS, {}),
        (_line("fusion.6"), 18 * MS, 2 * MS, {}),
    ]


# ------------------------------------------------------- the device side


def test_a_while_with_nested_fusions_counts_once():
    events = _chip()
    own = stagecut.self_times(events)
    assert own[2] == 2 * MS  # the while keeps [6,7) and [11,12)
    assert own[3] == own[4] == 2 * MS
    assert sum(own) == xtrace.total(xtrace.union(xtrace.intervals_of(events))) == 16 * MS
    # events that overlap without nesting still share the time once
    cross = [("a", 0.0, 10.0, {}), ("b", 5.0, 10.0, {}), ("c", 20.0, 0.0, {})]
    assert stagecut.self_times(cross) == [5.0, 10.0, 0.0]


@pytest.mark.parametrize(
    "path, stage, backward",
    [
        (ORIGIN["fusion.2"], "frcnn.roi_pool", True),  # nested: the last scope
        ("jit(train_step)/jvp(frcnn.trunk)/FasterRCNN.extract_features/frcnn.input/div", "frcnn.input", False),
        (ORIGIN["fusion.4"], "frcnn.proposals", False),
        (ORIGIN["fusion.6"], "frcnn.update", False),
        ("jit(train_step)/transpose(jvp(FasterRCNN.head_forward))/head/add_any", "unscoped", True),
        (ORIGIN["fusion.5"], "unscoped", False),
        ("", "unscoped", False),
    ],
)
def test_an_operation_goes_to_the_last_scope_of_its_op_name(path, stage, backward):
    assert stagecut.stage_of(path, SCOPES) == (stage, backward)


def test_stage_sums_and_unscoped_close_on_busy_time():
    planes = {"/device:TPU:0": _chip(), "/device:TPU:1": _chip()[:2]}
    cut = stagecut.cut_device(planes, ORIGIN, SCOPES)
    assert cut["busy_ns"] == (16 + 6) * MS
    assert sum(sum(v.values()) for v in cut["stage_ns"].values()) == cut["busy_ns"]
    assert cut["stage_ns"]["frcnn.proposals"] == {"forward": 6 * MS, "backward": 0.0}
    assert cut["stage_ns"]["frcnn.roi_pool"] == {"forward": 0.0, "backward": 4 * MS}
    assert cut["stage_ns"]["frcnn.trunk"]["forward"] == 8 * MS
    # no scope in the path, and no path at all (a compiler-made copy)
    assert cut["stage_ns"]["unscoped"]["forward"] == 2 * MS
    row = cut["ops"]["fusion.4 f32[8] fusion"]
    assert (row["stage"], row["side"], row["ns"]) == ("frcnn.proposals", "forward", 4 * MS)


def test_op_names_come_from_the_compiled_modules_text():
    text = "\n".join(
        [
            "HloModule jit_train_step",
            "%fused_computation.7 (p: f32[8]) -> f32[8] {",
            '  ROOT %multiply.1 = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(train_step)/frcnn.update/mul"}',
            "}",
            "ENTRY %main (a: f32[8]) -> (f32[8], s32[]) {",
            '  %fusion.6 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(train_step)/frcnn.update/mul" source_file="x.py"}',
            "  %copy.9 = f32[8]{0} copy(f32[8]{0} %fusion.6)",
            '  ROOT %tuple.2 = (f32[8]{0}, s32[]) tuple(f32[8]{0} %copy.9, s32[] %c), metadata={op_name="jit(train_step)/jvp()/add"}',
            "}",
        ]
    )
    origin = stagecut.load_origin(text)
    assert origin["fusion.6"] == "jit(train_step)/frcnn.update/mul"
    assert origin["tuple.2"].endswith("jvp()/add") and "copy.9" not in origin


# --------------------------------------------------------- the host side


def test_a_gap_goes_to_the_innermost_span_on_whatever_thread():
    idle = [(14 * MS, 18 * MS), (30 * MS, 31 * MS)]
    spans = [
        ("step/sync", 13 * MS, 4.5 * MS, {"line": 0}),  # covers [14,17.5)
        ("data/build", 15 * MS, 1 * MS, {"line": 3}),  # a loader thread, inside it
        ("step/dispatch", 5 * MS, 1 * MS, {"line": 0}),  # nowhere near a gap
    ]
    got = stagecut.attribute_idle(idle, spans)
    assert got == {
        "step/sync": 2.5 * MS, "data/build": 1 * MS, "unattributed": (0.5 + 1) * MS,
    }
    whole = stagecut.cut_idle({"/device:TPU:0": _chip()}, spans)
    assert whole["idle_ns"] == 4 * MS and sum(whole["by_span"].values()) == 4 * MS
    assert stagecut.attribute_idle([], spans) == {}


def test_the_run_directory_comes_from_the_tracers_first_event():
    opened = {"name": "telemetry/open", "ph": "i", "args": {"dir": "/x/runs/cell/telemetry"}}
    span = {"name": "step/dispatch", "ph": "X", "ts": 1.0, "dur": 2.0}
    assert stagecut.run_dir([opened, span]) == "/x/runs/cell"
    assert stagecut.run_dir([span, opened]) is None  # another tracer's file, or none
    assert stagecut.run_dir([]) is None
    assert stagecut.span_names([opened, span]) == {"step/dispatch"}


# -------------------------------------------------------------- readers


def _read(metric, ctx):
    cell = manifest.Cell(ROOT, STAGES_MANIFEST, "tiny.stages")
    return harness.load_file(cell.reader_path(metric)).read(ctx)


def _hand_ctx():
    planes = {"/device:TPU:0": _chip()}
    cut = stagecut.cut_device(planes, ORIGIN, SCOPES)
    cut.update(stagecut.cut_idle(planes, [("step/sync", 13 * MS, 4 * MS, {})]))
    cut["chips"] = 1
    return {"window": {"traced_steps": 2}, "spans": [], "stagecut": cut}


@pytest.mark.parametrize(
    "metric, want",
    [
        ("stage_trunk_ms", 2.0),  # 4 ms over two steps
        ("stage_rpn_ms", 0.0),
        ("stage_targets_ms", 0.0),
        ("stage_proposals_ms", 3.0),
        ("stage_roi_pool_ms", 1.0),
        ("stage_box_head_ms", 0.0),  # roi_pool's time is not its parent's
        ("stage_update_ms", 1.0),
        ("stage_backward_pct", 100.0 * 2 / 14),
        ("stage_unscoped_pct", 100.0 * 2 / 16),
        ("idle_unattributed_pct.resident", 25.0),  # [17,18) of [14,18)
    ],
)
def test_each_reader_on_a_hand_built_cut(metric, want):
    assert _read(metric, _hand_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_program_without_the_open_event_gives_nothing_to_read(metric):
    """The parent commit's tracer: no `telemetry/open`, so no directory, so
    every reader returns None and raises nothing."""
    ctx = {
        "window": {"traced_steps": 3},
        "spans": [{"name": "step/dispatch", "ph": "X", "ts": 0.0, "dur": 1.0}],
    }
    assert _read(metric, ctx) is None
    assert ctx["stagecut"] is None  # looked for once


# ------------------------------------------------------------ rehearsal


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("stages")
    result, code = harness.run_cell(
        ROOT, STAGES_MANIFEST, "tiny.stages", SEED, 1.0, True, scratch=str(tmp), require_tpu=False,
    )
    assert code == 0
    return result, os.path.join(str(tmp), "runs", "tiny.stages")


def test_the_stages_manifest_is_sound_and_adds_only_the_ten():
    m = manifest.load(STAGES_MANIFEST)
    assert manifest.validate(m) == []
    names = [p["name"] for p in m["per_layer"]]
    assert names[-10:] == list(NEW_METRICS)
    record = manifest.load(os.path.join(ROOT, "BENCHMARK.json"))
    assert [p["name"] for p in record["per_layer"]][-10:] == list(NEW_METRICS)
    by_name = {p["name"]: p for p in record["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == ["r18c4.resident"]
        assert by_name[name]["moves"] == "resident_img_per_s"


def test_a_traced_rehearsal_reports_all_ten(traced):
    result, _ = traced
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(got)
    staged = sum(got[m] for m in NEW_METRICS[:7])
    # the seven stages and the unscoped time close on the step's busy time
    assert staged + got["step_device_ms"] * got["stage_unscoped_pct"] / 100 == pytest.approx(
        got["step_device_ms"], rel=0.01
    )
    assert got["stage_proposals_ms"] > 0  # the executor threads run NMS's small fusions
    assert 0 <= got["stage_backward_pct"] <= 100 and 0 <= got["idle_unattributed_pct.resident"] <= 100


def test_the_table_by_hand_reads_the_same_run(traced):
    _, where = traced
    out = io.StringIO()
    with redirect_stdout(out):
        assert stagecut.main(["stagecut.py", where]) == 0
    table = json.loads(out.getvalue())
    assert table["steps"] > 0 and table["chips"] == 1
    assert sum(sum(v.values()) for v in table["stages_ms"].values()) == pytest.approx(table["step_busy_ms"])
    assert table["ops_ms"] and "step/sync" in table["idle_by_span_ms"]
