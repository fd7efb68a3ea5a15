"""The latent-attention sequence model's cell, rehearsed on the CPU (this
directory: a manifest, a configuration and a mix of its own; the reference,
the data module and the readers are the benchmark's own, perf/references/
and perf/metrics/): `harness.run_cell` drives the package's `Trainer` on the
`joyai_tiny` preset through `package_program()`. No time, rate or share from
these runs means anything.

And what `test_cells_of_record.py` says of a cell of record, for the
latent-attention cell: that file's `LIMITS` is keyed by reference module, knows two,
and may not be edited here, so its case for this cell stops at the lookup
(`tests/conftest.py` expects exactly that). Here the limit names are stated
by name and read from the cell's reference module.
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
LATENT_MANIFEST = os.path.join(ROOT, "tests", "perf_yardstick", "latent", "BENCHMARK.json")
CELL, CELL_OF_RECORD = "joyai_tiny.packed128", "joyai_ep16.packed8k"
SEED = 2_147_484_033  # more than 32 signed bits hold
# what the cell of record is held to: the sequence cells' set, the first layer's latent
# up-projection's gradient and the multi-token-prediction module's loss at step 1
LIMIT_NAMES = {
    "loss3_gap", "grad_norm_median_gap", "change_norm_gap", "expert_assignments1_gap", "feed_token_gap",
    "latent0_grad_gap", "mtp1_gap",
}
NEW_METRICS = ["stage_latent_ms", "stage_mtp_ms"]

# a cell's limit names by its reference module: the hybrid cell's rule
_hybrid_cell = harness.load_file(os.path.join(ROOT, "tests", "perf_yardstick", "hybrid", "test_hybrid_cell.py"))


def _rehearse(tmp, trace=False):
    result, code = harness.run_cell(ROOT, LATENT_MANIFEST, CELL, SEED, 1.0, trace, scratch=str(tmp), require_tpu=False)
    assert code == 0
    return result


def test_the_manifests_are_sound_and_the_cell_of_record_is_the_latent_cell():
    for path in (LATENT_MANIFEST, RECORD):
        assert manifest.validate(manifest.load(path)) == [], path
    cell = manifest.Cell(ROOT, RECORD, CELL_OF_RECORD)
    assert (cell.cell["config"], cell.cell["traffic"], cell.chips) == ("joyai_flash_ep16", "packed8k_v16160", 1)
    assert cell.config["reference"] == "joyai" and cell.config["feed_reference"] == "tokenfeed"
    assert cell.config["per_chip_batch"] == 1
    mix = {k: cell.mix[k] for k in ("feed", "staged_batches", "n_rows", "row_len", "id_rows", "doc_median", "doc_sigma", "doc_longest", "zipf_exponent")}
    assert mix == {
        "feed": "staged", "staged_batches": 8, "n_rows": 32, "row_len": 8192, "id_rows": 16160, "doc_median": 700,
        "doc_sigma": 1.2, "doc_longest": 8192, "zipf_exponent": 1.0,
    }
    assert (cell.mix["row_len"], cell.mix["id_rows"]) == (cell.config["sizes"]["data.seq_len"], cell.config["sizes"]["lm.vocab_rows"])
    # what the other sequence cells report, and the two new stages; no detector's metric
    reported = {m["name"] for m in cell.end_to_end + cell.per_layer}
    trinity = manifest.Cell(ROOT, RECORD, "trinity_ep8.packed8k")
    assert reported == {m["name"] for m in trinity.end_to_end + trinity.per_layer} | set(NEW_METRICS)
    record = manifest.load(RECORD)
    by_name = {p["name"]: p for p in record["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL_OF_RECORD] and by_name[name]["moves"] == "resident_img_per_s"
        assert os.path.exists(cell.reader_path(name))
    assert CELL_OF_RECORD in [w["name"] for w in record["workloads"]]
    assert "joyai_flash_ep16" in [c["name"] for c in record["configs"]]
    # the program's preset is what the file states, size for size
    from replication_faster_rcnn_tpu.config import get_config

    harness.program_config(cell, SEED, {}, "", get_config)


def test_the_new_cell_of_record_has_its_files_its_limits_and_the_benchmarks_own_modules():
    """`test_cells_of_record.py`'s case, every statement of it, with the
    limit names stated here and read from the cell's reference module."""
    cell = manifest.Cell(ROOT, RECORD, CELL_OF_RECORD)
    assert cell.config["sizes"] and cell.mix["feed"] in ("loader", "staged")
    for metric in cell.per_layer:
        assert os.path.exists(cell.reader_path(metric["name"])), metric["name"]
    assert {"setup_s"} < {e["name"] for e in cell.end_to_end}
    ref = harness.load_reference(cell)
    assert _hybrid_cell.limit_names(ref) == LIMIT_NAMES
    assert set(cell.config["limits"]) >= LIMIT_NAMES
    for module in (ref, harness.load_feed_reference(cell)):
        assert os.path.dirname(module.__file__) == os.path.join(ROOT, "perf", "references")
    assert cell.config["limits_readings"] and set(cell.config["memory_reckoning"]) >= {"train_step", "reference_step"}


def test_the_configuration_keeps_every_key_of_the_catalog_row_or_lists_it_as_reduced():
    # the published configuration: the name, the source and every key of its config.json
    with open(os.path.join(ROOT, "tests", "perf_yardstick", "latent", "joyai_llm_flash_published.json")) as f:
        row = json.load(f)
    with open(os.path.join(ROOT, "perf", "configs", "joyai_flash_ep16.json")) as f:
        conf = json.load(f)
    odd = [k for k, v in row["config"].items() if conf.get(k) != v and k not in conf["reduced"]]
    assert not odd, odd
    assert conf["source"].startswith(row["source_url"]) and conf["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert conf["published"]["n_routed_experts"] == row["config"]["n_routed_experts"] and "16 chips" in conf["deployment"]
    # every published width is the program's own size
    sizes, c = conf["sizes"], row["config"]
    assert (sizes["lm.hidden_size"], sizes["lm.num_heads"], sizes["lm.num_kv_heads"]) == (
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"])
    assert (sizes["lm.q_lora_rank"], sizes["lm.kv_lora_rank"], sizes["lm.qk_nope_head_dim"], sizes["lm.qk_rope_head_dim"],
            sizes["lm.v_head_dim"]) == (c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])
    assert c["qk_head_dim"] == c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    assert (sizes["lm.dense_width"], sizes["lm.expert_width"], sizes["lm.num_experts"], sizes["lm.experts_per_token"]) == (
        c["intermediate_size"], c["moe_intermediate_size"], c["n_routed_experts"], c["num_experts_per_tok"])
    assert (sizes["lm.route_scale"], sizes["lm.router_score"], sizes["lm.num_dense_layers"]) == (
        c["routed_scaling_factor"], c["scoring_func"], c["first_k_dense_replace"])
    assert (sizes["lm.rope_theta"], sizes["lm.rope_interleave"], sizes["lm.rms_norm_eps"]) == (c["rope_theta"], c["rope_interleave"], c["rms_norm_eps"])
    assert sizes["lm.mtp_layers"] == c["num_nextn_predict_layers"] and sizes["lm.vocab_rows"] * 8 == c["vocab_size"]
    assert sizes["lm.layer_types"] == ["latent_attention"] * 5 and conf["parameters"]["total"] == 680_439_808


def test_the_tiny_cell_is_correct_and_every_reader_reads(tmp_path):
    """A traced rehearsal: `correct`, 0 recompilations, nothing dropped, each
    per-layer metric of the cell gives a number (the latent stage above
    nought), and the MTP loss is among the window's counters."""
    r = _rehearse(tmp_path, trace=True)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["recompiles"]["value"] == 0 and r["compared"]["feed_token_gap"]["value"] == 0
    assert set(r["compared"]) >= LIMIT_NAMES
    cell = manifest.Cell(ROOT, LATENT_MANIFEST, CELL)
    # a roofline share needs device time under its kernel's scope, which the
    # CPU executor's trace of an interpreted kernel does not always show
    may_lack = {"attention_roofline", "expert_mm_roofline"}
    assert {m["name"] for m in cell.per_layer} - may_lack <= set(r["metrics"]) <= {m["name"] for m in cell.per_layer}
    # the module's own few small operations may read nought in the CPU executor's trace: on the
    # chip they do not (PERF.md section 6)
    assert r["metrics"]["stage_latent_ms"]["value"] > 0 and r["metrics"]["stage_mtp_ms"]["value"] >= 0
    assert r["notes"]["tokens_per_sample"] == 128
    with open(os.path.join(str(tmp_path), "runs", CELL, "telemetry", "trace.json")) as f:
        counters = {e["name"] for e in json.load(f)["traceEvents"] if e.get("ph") == "C"}
    assert {"lm/mtp_loss", "lm/expert_assignments", "lm/router_bias_absmax"} <= counters


def test_the_needed_work_is_the_hand_count():
    """The reckoning of a step: the matrix-product parameters a token meets, the
    causal pairs of a row, the latent attention's two products a pair."""
    ref = harness.load_file(os.path.join(ROOT, "perf", "references", "joyai.py"))
    with open(os.path.join(ROOT, "perf", "configs", "joyai_flash_ep16.json")) as f:
        sizes = json.load(f)["sizes"]
    assert ref.visible_pairs(8192) == 33_558_528
    mla = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    moe = 2048 * 256 + 3 * 2048 * 768 * (1 + 8 * 16 / 256)
    met = 5 * mla + 3 * 2048 * 7168 + 4 * moe + (2 * 2048 * 2048 + mla + moe) + 2 * 2048 * 16160
    want = 6.0 * met * 8192 + 3.0 * (2 * 192 + 2 * 128) * 32 * 33_558_528 * 6
    assert ref.train_flops_per_image(sizes) == want and 27e12 < want < 29e12  # about 28 TFLOP a step
    # compute binds the attention: 0.687 TFLOP a layer forward
    attn = ref.attention_roofline_seconds(sizes, 1, 197e12, 819e9)
    assert abs(attn - 3 * 640 * 32 * 33_558_528 * 6 / 197e12) / attn < 1e-9


def test_the_controls_come_out_not_correct_under_the_limits_of_the_cell_of_record():
    """The reference put in the program's place at the tiny size: computed in
    float8 e4m3 (the nearest precision below the configuration's bfloat16)
    and on half of each row. Each fails the comparison under the limits of
    the cell of record, an unchanged state fails it too, and the reference
    against itself passes. (That a fault planted in the timed path reaches
    the comparison is the harness's, rehearsed with the hybrid cell's.) The readings the limits were set from, on the chip
    at the cell's sizes: `benchmarks/lm_limits_on_chip.py --workload
    joyai_ep16.packed8k`."""
    import numpy as np

    record = manifest.Cell(ROOT, RECORD, CELL_OF_RECORD)
    tiny = manifest.Cell(ROOT, LATENT_MANIFEST, CELL)
    ref = harness.load_reference(tiny)
    sz = ref.Sizes(tiny.config["sizes"], 2)
    rng = np.random.RandomState(5)
    batches = [{"tokens": rng.randint(0, 64, (2, 128)).astype(np.int32)} for _ in range(harness.CHECK_STEPS)]
    jitted = {}
    plain = harness.reference_numbers(ref, sz, SEED, batches, jitted=jitted)
    limits = record.config["limits"]
    for kw in ({"precision": "float8"}, {"rows": 1}):
        other = harness.reference_numbers(ref, sz, SEED, batches, jitted=(jitted if "rows" not in kw else {}), **kw)
        nums = compare.numbers(other, plain)
        assert compare.judge(nums, limits) is False, (kw, nums)
    still = dict(plain, change_norms={k: 0.0 for k in plain["change_norms"]})
    nums = compare.numbers(still, plain)
    assert nums["change_norm_gap"]["value"] == pytest.approx(1.0) and compare.judge(nums, limits) is False
    assert compare.judge(compare.numbers(plain, plain), limits) is True
