"""What the benchmark's own tests say of `BENCHMARK.json`, kept for a file
that holds more than one kind of cell.

Two of them, `test_perf_benchmark.py::test_manifests_are_sound_and_their_files_exist`
and `test_stage_metrics.py::test_the_stages_manifest_is_sound_and_adds_only_the_ten`,
each hold one statement that is true only while the detector's cell is alone:
that every cell of record carries the DETECTOR's limit names, and that PR 24's
ten stage metrics are the file's last ten entries and list that cell alone.
PR 31 appends a second cell and may edit neither file; `tests/conftest.py`
expects exactly those two statements to fail, and a test stops at its first
failure. Every other statement of the two is made again here, by name and not
by position, so none goes unchecked until a `benchmark` PR updates the two.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import harness, manifest  # noqa: E402

RECORD = os.path.join(ROOT, "BENCHMARK.json")
STAGES_MANIFEST = os.path.join(ROOT, "perf", "rehearsal", "BENCHMARK.stages.json")
# PR 24's ten, in the order it added them (test_stage_metrics.py::NEW_METRICS)
THE_TEN = (
    "stage_trunk_ms", "stage_rpn_ms", "stage_targets_ms", "stage_proposals_ms", "stage_roi_pool_ms",
    "stage_box_head_ms", "stage_update_ms", "stage_backward_pct", "stage_unscoped_pct",
    "idle_unattributed_pct.resident",
)
# of the ten, what any train step has: a later cell may append its name to these
SHARED_OF_THE_TEN = {"stage_update_ms", "stage_backward_pct", "stage_unscoped_pct", "idle_unattributed_pct.resident"}
# the limit names a cell of record carries at least, by its reference module
LIMITS = {
    "frcnn": {"rpn_cls_grad_gap", "change_norm_gap", "feed_box_gap", "feed_pixel_gap"},
    "afmoe": {"loss3_gap", "grad_norm_median_gap", "attn0_grad_gap", "change_norm_gap", "feed_token_gap"},
}
CELLS = [w["name"] for w in manifest.load(RECORD)["workloads"]]


def test_the_manifest_of_record_is_sound_and_holds_the_detectors_cell_first():
    m = manifest.load(RECORD)
    assert manifest.validate(m) == []
    assert CELLS[0] == "r18c4.resident"
    assert manifest.Cell(ROOT, RECORD, "r18c4.resident").config["reference"] == "frcnn"


@pytest.mark.parametrize("name", CELLS)
def test_a_cell_of_record_has_its_files_its_limits_and_the_benchmarks_own_modules(name):
    cell = manifest.Cell(ROOT, RECORD, name)
    assert cell.config["sizes"] and cell.mix["feed"] in ("loader", "staged")
    for metric in cell.per_layer:
        assert os.path.exists(cell.reader_path(metric["name"])), metric["name"]
    assert {"setup_s"} < {e["name"] for e in cell.end_to_end}
    assert set(cell.config["limits"]) >= LIMITS[cell.config["reference"]]
    for module in (harness.load_reference(cell), harness.load_feed_reference(cell)):
        assert os.path.dirname(module.__file__) == os.path.join(ROOT, "perf", "references")


def test_the_ten_stage_metrics_stand_together_in_their_order_and_keep_the_detectors_cell():
    stages = manifest.load(STAGES_MANIFEST)
    assert manifest.validate(stages) == []
    assert [p["name"] for p in stages["per_layer"]][-10:] == list(THE_TEN)
    record = manifest.load(RECORD)
    names = [p["name"] for p in record["per_layer"]]
    first = names.index(THE_TEN[0])
    assert names[first : first + 10] == list(THE_TEN)
    by_name = {p["name"]: p for p in record["per_layer"]}
    for name in THE_TEN:
        listed = by_name[name]["workloads"]
        assert listed[0] == "r18c4.resident" and by_name[name]["moves"] == "resident_img_per_s"
        if name not in SHARED_OF_THE_TEN:
            assert listed == ["r18c4.resident"]  # a detector's stage: no other kind of cell reads it
        assert set(listed) <= set(CELLS)


def test_what_was_added_after_the_ten_is_read_by_no_detector_cell():
    """Entries behind the ten belong to later cells: the detector's cell of
    record reports what it reported."""
    record = manifest.load(RECORD)
    names = [p["name"] for p in record["per_layer"]]
    behind = record["per_layer"][names.index(THE_TEN[-1]) + 1 :]
    assert all("r18c4.resident" not in p["workloads"] for p in behind), [p["name"] for p in behind]
    cell = manifest.Cell(ROOT, RECORD, "r18c4.resident")
    assert [m["name"] for m in cell.per_layer] == names[: names.index(THE_TEN[-1]) + 1]
