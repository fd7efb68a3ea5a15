"""The hybrid sequence model's cell, rehearsed on the CPU (this directory: a
manifest, a configuration and a mix of its own; the reference, the data
module and the readers are the benchmark's own, perf/references/ and
perf/metrics/): `harness.run_cell` drives the package's `Trainer` on the
`qwen3_next_tiny` preset through `package_program()`. No time, rate or share
from these runs means anything.

And what `test_cells_of_record.py` says of a cell of record, for the cell
ISSUE 33 adds: that file's `LIMITS` is keyed by reference module, knows two,
and may not be edited here, so its case for the third cell stops at the
lookup (`tests/conftest.py` expects exactly that). Here the limit names are
read from the cell's reference module itself.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import compare, harness, manifest  # noqa: E402

RECORD = os.path.join(ROOT, "BENCHMARK.json")
HYBRID_MANIFEST = os.path.join(ROOT, "tests", "perf_yardstick", "hybrid", "BENCHMARK.json")
CELL, CELL_OF_RECORD = "qwen3next_tiny.packed256", "qwen3next_ep16.packed16k"
SEED = 2_147_484_033  # more than 32 signed bits hold, as the driver's are


# the faults a rehearsal plants in the timed path are the second cell's own
_lm_cell = harness.load_file(os.path.join(ROOT, "tests", "perf_yardstick", "test_lm_cell.py"))
_unchanged_state, _half_batch = _lm_cell._unchanged_state, _lm_cell._half_batch


def _rehearse(tmp, trace=False, break_step=None):
    result, code = harness.run_cell(
        ROOT, HYBRID_MANIFEST, CELL, SEED, 1.0, trace, scratch=str(tmp), require_tpu=False, break_step=break_step,
    )
    assert code == 0
    return result


def limit_names(ref):
    """What a cell of a sequence model is held to at least, by its reference
    module alone: the last step's loss, the median leaf of the first gradient,
    the change's worst leaf, each number the module names for a mechanism
    (all but the embedding's, which no cell limits), the counter compared at
    step 1, and the feed."""
    named = {name for name in ref.LEAF_NUMBERS if name != "embed_grad_gap"}
    parts = {f"{part.removesuffix('_loss')}1_gap" for part in ref.LOSS_PARTS if part != "nll_loss"}
    return {f"loss{harness.CHECK_STEPS}_gap", "grad_norm_median_gap", "change_norm_gap", "feed_token_gap"} | named | parts


def test_the_manifests_are_sound_and_the_cell_of_record_is_as_issue_33_names_it():
    for path in (HYBRID_MANIFEST, RECORD):
        assert manifest.validate(manifest.load(path)) == [], path
    cell = manifest.Cell(ROOT, RECORD, CELL_OF_RECORD)
    assert (cell.cell["config"], cell.cell["traffic"], cell.chips) == ("qwen3_next_ep16", "packed16k", 1)
    assert cell.config["reference"] == "qwen3next" and cell.config["feed_reference"] == "tokenfeed"
    assert cell.config["per_chip_batch"] == 1
    mix = {k: cell.mix[k] for k in ("feed", "staged_batches", "n_rows", "row_len", "id_rows", "doc_median", "doc_sigma", "doc_longest", "zipf_exponent")}
    assert mix == {
        "feed": "staged", "staged_batches": 8, "n_rows": 32, "row_len": 16384, "id_rows": 18992, "doc_median": 700,
        "doc_sigma": 1.2, "doc_longest": 16384, "zipf_exponent": 1.0,
    }
    assert (cell.mix["row_len"], cell.mix["id_rows"]) == (cell.config["sizes"]["data.seq_len"], cell.config["sizes"]["lm.vocab_rows"])
    reported = {m["name"] for m in cell.end_to_end + cell.per_layer}
    trinity = manifest.Cell(ROOT, RECORD, "trinity_ep8.packed8k")
    assert reported == {m["name"] for m in trinity.end_to_end + trinity.per_layer} | {"stage_linear_attention_ms", "delta_rule_roofline"}
    assert "conv_roofline" not in reported and "stage_trunk_ms" not in reported
    record = manifest.load(RECORD)
    assert [p["name"] for p in record["per_layer"]][-2:] == ["stage_linear_attention_ms", "delta_rule_roofline"]
    assert all(p["workloads"] == [CELL_OF_RECORD] for p in record["per_layer"][-2:])
    assert [w["name"] for w in record["workloads"]][-1] == CELL_OF_RECORD and record["configs"][-1]["name"] == "qwen3_next_ep16"
    # the program's preset is what the file states, size for size
    from replication_faster_rcnn_tpu.config import get_config

    harness.program_config(cell, SEED, {}, "", get_config)


def test_the_new_cell_of_record_has_its_files_its_limits_and_the_benchmarks_own_modules():
    """`test_cells_of_record.py`'s case, every statement of it, with the
    limit names taken from the cell's reference module."""
    cell = manifest.Cell(ROOT, RECORD, CELL_OF_RECORD)
    assert cell.config["sizes"] and cell.mix["feed"] in ("loader", "staged")
    for metric in cell.per_layer:
        assert os.path.exists(cell.reader_path(metric["name"])), metric["name"]
    assert {"setup_s"} < {e["name"] for e in cell.end_to_end}
    ref = harness.load_reference(cell)
    assert set(cell.config["limits"]) >= limit_names(ref)
    assert limit_names(ref) == {
        "loss3_gap", "grad_norm_median_gap", "change_norm_gap", "delta0_grad_gap", "attn3_grad_gap",
        "expert_assignments1_gap", "feed_token_gap",
    }
    # the same rule gives the second cell the names its own case pins
    afmoe = harness.load_reference(manifest.Cell(ROOT, RECORD, "trinity_ep8.packed8k"))
    assert limit_names(afmoe) - {"expert_assignments1_gap"} == {"loss3_gap", "grad_norm_median_gap", "attn0_grad_gap", "change_norm_gap", "feed_token_gap"}
    for module in (ref, harness.load_feed_reference(cell)):
        assert os.path.dirname(module.__file__) == os.path.join(ROOT, "perf", "references")
    assert cell.config["limits_readings"] and set(cell.config["memory_reckoning"]) >= {"train_step", "reference_step"}


def test_the_configuration_keeps_every_key_of_the_catalog_row_or_lists_it_as_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    with open(os.path.join(ROOT, "perf", "configs", "qwen3_next_ep16.json")) as f:
        conf = json.load(f)
    odd = [k for k, v in row["config"].items() if conf.get(k) != v and k not in conf["reduced"]]
    assert not odd, odd
    assert conf["source"].startswith(row["source_url"]) and conf["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert conf["published"]["num_experts"] == row["config"]["num_experts"] and "16 chips" in conf["deployment"]
    # every published width is the program's own size
    sizes, c = conf["sizes"], row["config"]
    assert (sizes["lm.hidden_size"], sizes["lm.num_heads"], sizes["lm.num_kv_heads"], sizes["lm.head_size"]) == (
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"])
    assert (sizes["lm.linear_num_key_heads"], sizes["lm.linear_num_value_heads"], sizes["lm.linear_key_head_dim"],
            sizes["lm.linear_value_head_dim"], sizes["lm.linear_conv_kernel"]) == (
        c["linear_num_key_heads"], c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"],
        c["linear_conv_kernel_dim"])
    assert (sizes["lm.expert_width"], sizes["lm.num_experts"], sizes["lm.experts_per_token"], sizes["lm.rotary_fraction"]) == (
        c["moe_intermediate_size"], c["num_experts"], c["num_experts_per_tok"], c["partial_rotary_factor"])
    assert c["shared_expert_intermediate_size"] == sizes["lm.expert_width"] and sizes["lm.rope_theta"] == c["rope_theta"]
    assert sizes["lm.rms_norm_eps"] == c["rms_norm_eps"] and sizes["lm.vocab_rows"] * 8 == c["vocab_size"]
    assert sizes["lm.layer_types"] == ["linear_attention"] * (c["full_attention_interval"] - 1) + ["full_attention"]


def test_the_tiny_cell_is_correct_and_every_reader_reads(tmp_path):
    """A traced rehearsal: `correct`, 0 recompilations, nothing dropped, each
    per-layer metric of the cell gives a number, and the two counters ISSUE
    33 adds are among the window's events."""
    r = _rehearse(tmp_path, trace=True)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["recompiles"]["value"] == 0 and r["compared"]["feed_token_gap"]["value"] == 0
    cell = manifest.Cell(ROOT, HYBRID_MANIFEST, CELL)
    # a roofline share needs device time under its kernel's scope, which the
    # CPU executor's trace of an interpreted kernel does not always show
    may_lack = {"attention_roofline", "expert_mm_roofline", "delta_rule_roofline"}
    assert {m["name"] for m in cell.per_layer} - may_lack <= set(r["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert r["metrics"]["stage_linear_attention_ms"]["value"] > 0
    assert r["notes"]["tokens_per_sample"] == 256
    with open(os.path.join(str(tmp_path), "runs", CELL, "telemetry", "trace.json")) as f:
        counters = {e["name"] for e in json.load(f)["traceEvents"] if e.get("ph") == "C"}
    assert {"lm/delta_state_absmax", "lm/delta_decay_mean", "lm/expert_assignments"} <= counters
    assert "lm/router_bias_absmax" not in counters


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_a_broken_timed_path_comes_out_not_correct(tmp_path, fault):
    r = _rehearse(tmp_path, break_step=fault)
    assert r["correct"] is False
    over = [k for k, v in r["compared"].items() if v["limit"] is not None and not v["value"] <= v["limit"]]
    assert over, r["compared"]


def test_the_needed_work_is_the_hand_count():
    """ISSUE 33's reckoning: the matrix-product parameters a token meets, the
    causal pairs of a row, the chunked delta rule's products."""
    ref = harness.load_file(os.path.join(ROOT, "perf", "references", "qwen3next.py"))
    with open(os.path.join(ROOT, "perf", "configs", "qwen3_next_ep16.json")) as f:
        sizes = json.load(f)["sizes"]
    sz = ref.Sizes(sizes, 1)
    assert ref.visible_pairs(16384) == 134_225_920
    # a token and value head, forward: 3 products with the state, 2 of keys with keys and queries,
    # the masked product with V_new, the triangular system for U and W
    assert ref.delta_rule_flops_per_token(sz) == 3 * 2 * 128 * 128 + 2 * 2 * 64 * 128 + 2 * 64 * 128 + 64 * 256 == 163_840
    linear = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 4096 * 2048
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    every = 2048 * 512 + 2048 + 3 * 2048 * 512 * (1 + 10 * 32 / 512)
    met = 3 * linear + full + 4 * every + 2048 * 18992
    want = 6.0 * met * 16384 + 12.0 * 256 * 16 * 134_225_920 + 3.0 * 163_840 * 32 * 16384 * 3
    assert ref.train_flops_per_image(sizes) == want and 26e12 < want < 27e12  # ISSUE 33: about 26.5 TFLOP a step
    # bytes bind the delta rule's forward pass (0.497 ms a layer against 0.436 by FLOPs) and its backward
    moved = 16384 * (2 * (2 * 16 * 128 + 2 * 32 * 128) + 4 * 2 * 32)
    least = ref.delta_rule_roofline_seconds(sizes, 1, 197e12, 819e9)
    assert abs(least - 3 * 3 * moved / 819e9) / least < 1e-9 and 163_840 * 32 * 16384 / 197e12 < moved / 819e9
    # compute binds the attention at these shapes
    attn = ref.attention_roofline_seconds(sizes, 1, 197e12, 819e9)
    assert abs(attn - 12 * 256 * 16 * 134_225_920 / 197e12) / attn < 1e-9


def test_the_controls_come_out_not_correct_under_the_limits_of_the_cell_of_record():
    """The reference put in the program's place at the tiny size: computed in
    float8 e4m3 (the nearest precision below the configuration's bfloat16)
    on half of each batch's rows, and with the recurrence's state rounded to
    bfloat16 after every token (`PROBE_PRECISIONS`). Each fails the comparison
    under the limits of the cell of record, an unchanged state fails it too,
    and the reference against itself passes. (The last is a finding of the
    tiny size, where a key has 16 numbers: at the published 128 the same
    rounding reads `delta0_grad_gap` 2.7e-4..4.6e-4 on the chip, half of what
    the program's own bfloat16 operands read, and no limit can be held
    against it: PERF.md section 6, PR 33.) The readings the limits were set from, on the chip
    at the cell's sizes: `benchmarks/lm_limits_on_chip.py --workload
    qwen3next_ep16.packed16k`."""
    import numpy as np

    record = manifest.Cell(ROOT, RECORD, CELL_OF_RECORD)
    tiny = manifest.Cell(ROOT, HYBRID_MANIFEST, CELL)
    ref = harness.load_reference(tiny)
    sz = ref.Sizes(tiny.config["sizes"], 2)
    rng = np.random.RandomState(5)
    batches = [{"tokens": rng.randint(0, 64, (2, 256)).astype(np.int32)} for _ in range(harness.CHECK_STEPS)]
    jitted = {}
    plain = harness.reference_numbers(ref, sz, SEED, batches, jitted=jitted)
    limits = record.config["limits"]
    for kw in ({"precision": "float8"}, {"precision": ref.PROBE_PRECISIONS[0]}, {"rows": 1}):
        other = harness.reference_numbers(ref, sz, SEED, batches, jitted=(jitted if "rows" not in kw else {}), **kw)
        nums = compare.numbers(other, plain)
        assert compare.judge(nums, limits) is False, (kw, nums)
    still = dict(plain, change_norms={k: 0.0 for k in plain["change_norms"]})
    nums = compare.numbers(still, plain)
    assert nums["change_norm_gap"]["value"] == pytest.approx(1.0) and compare.judge(nums, limits) is False
    assert compare.judge(compare.numbers(plain, plain), limits) is True
