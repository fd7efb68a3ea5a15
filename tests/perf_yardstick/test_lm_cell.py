"""The sequence model's cell, rehearsed on the CPU (tests/perf_yardstick/lm/:
a manifest, a configuration and a mix of its own; the reference, the data
module and the readers are the benchmark's own, perf/references/ and
perf/metrics/): `harness.run_cell` drives the package's `Trainer` on the tiny
preset through `package_program()`, with no `program=`. No time, rate or
share from these runs means anything.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import harness, manifest  # noqa: E402

LM_MANIFEST = os.path.join(ROOT, "tests", "perf_yardstick", "lm", "BENCHMARK.json")
CELL = "trinity_tiny.packed64"
SEED = 2_147_484_001  # more than 32 signed bits hold, as the driver's are


def _unchanged_state(trainer, step_call):
    """A step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp

    def broken(kw):
        kept = jax.tree_util.tree_map(jnp.copy, trainer.state)
        metrics = step_call(kw)
        trainer.state = kept.replace(step=trainer.state.step)
        return metrics

    return broken


def _half_batch(trainer, step_call):
    """Half of the batch left out: its second half is overwritten with the first."""
    import jax
    import jax.numpy as jnp

    def broken(kw):
        (key, batch), = kw.items()
        half = next(iter(batch.values())).shape[0] // 2
        with jax.transfer_guard("allow"):
            batch = {k: jnp.concatenate([v[:half], v[:half]]) for k, v in batch.items()}
        return step_call({key: batch})

    return broken


def _rehearse(tmp, trace=False, break_step=None):
    result, code = harness.run_cell(
        ROOT, LM_MANIFEST, CELL, SEED, 1.0, trace, scratch=str(tmp), require_tpu=False, break_step=break_step,
    )
    assert code == 0
    return result


def test_the_manifests_are_sound_and_the_cell_of_record_is_as_issue_31_names_it():
    for path in (LM_MANIFEST, os.path.join(ROOT, "BENCHMARK.json")):
        assert manifest.validate(manifest.load(path)) == [], path
    cell = manifest.Cell(ROOT, os.path.join(ROOT, "BENCHMARK.json"), "trinity_ep8.packed8k")
    assert (cell.cell["config"], cell.cell["traffic"], cell.chips) == ("trinity_mini_ep8", "packed8k", 1)
    assert cell.config["reference"] == "afmoe" and cell.config["feed_reference"] == "tokenfeed"
    assert cell.config["per_chip_batch"] == 2 and cell.mix["feed"] == "staged" and cell.mix["staged_batches"] == 8
    assert (cell.mix["row_len"], cell.mix["id_rows"]) == (cell.config["sizes"]["data.seq_len"], cell.config["sizes"]["lm.vocab_rows"])
    reported = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert {"resident_img_per_s", "setup_s", "stage_update_ms", "train_mfu_pct.resident", "attention_roofline",
            "expert_mm_roofline", "expert_load_max_over_mean", "stage_attention_ms", "stage_experts_ms"} <= reported
    assert "conv_roofline" not in reported and "stage_trunk_ms" not in reported
    # the program's preset is what the file states, size for size
    from replication_faster_rcnn_tpu.config import get_config

    harness.program_config(cell, SEED, {}, "", get_config)


def test_the_configuration_keeps_every_key_of_the_catalog_row_or_lists_it_as_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    with open(os.path.join(ROOT, "perf", "configs", "trinity_mini_ep8.json")) as f:
        conf = json.load(f)
    odd = [k for k, v in row["config"].items() if conf.get(k) != v and k not in conf["reduced"]]
    assert not odd, odd
    assert conf["source"].startswith(row["source_url"])


def test_the_tiny_cell_is_correct_and_every_reader_reads(tmp_path):
    """A traced rehearsal: `correct`, 0 recompilations, nothing dropped, and
    each per-layer metric of the cell gives a number."""
    r = _rehearse(tmp_path, trace=True)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["recompiles"]["value"] == 0 and r["compared"]["feed_token_gap"]["value"] == 0
    cell = manifest.Cell(ROOT, LM_MANIFEST, CELL)
    # a roofline share needs device time under its kernel's scope, which the
    # CPU executor's trace of an interpreted kernel does not always show
    may_lack = {"attention_roofline", "expert_mm_roofline"}
    assert {m["name"] for m in cell.per_layer} - may_lack <= set(r["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert r["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    assert r["notes"]["tokens_per_sample"] == 64


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_a_broken_timed_path_comes_out_not_correct(tmp_path, fault):
    r = _rehearse(tmp_path, break_step=fault)
    assert r["correct"] is False
    over = [k for k, v in r["compared"].items() if v["limit"] is not None and not v["value"] <= v["limit"]]
    assert over, r["compared"]


def test_the_needed_flops_are_the_hand_count():
    """ISSUE 31's reckoning: visible pairs of a windowed and a full row, and
    the matrix-product parameters a token meets."""
    ref = harness.load_file(os.path.join(ROOT, "perf", "references", "afmoe.py"))
    assert ref.visible_pairs(8192, 2048) == 14_681_088 and ref.visible_pairs(8192, None) == 33_558_528
    assert ref.visible_pairs(64, 100) == 64 * 65 // 2
    with open(os.path.join(ROOT, "perf", "configs", "trinity_mini_ep8.json")) as f:
        sizes = json.load(f)["sizes"]
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    met = 5 * attn + 3 * 2048 * 6144 + 4 * (2048 * 128 + 2 * 3 * 2048 * 1024) + 2048 * 25024
    pairs = 4 * 14_681_088 + 33_558_528
    assert ref.train_flops_per_image(sizes) == 6.0 * met * 8192 + 12.0 * 128 * 32 * pairs
    # compute binds the attention at these shapes; a share above 100 would mean a wrong count
    least = ref.attention_roofline_seconds(sizes, 2, 197e12, 819e9)
    assert abs(least - 2 * 12 * 128 * 32 * pairs / 197e12) / least < 1e-6


def test_the_control_in_float8_comes_out_not_correct_and_half_the_rows_neither():
    """The reference put in the program's place: computed in float8 e4m3, the
    nearest precision below the configuration's bfloat16, and on half of each
    batch's rows. Both fail the comparison under the limits of the cell of
    record (not the rehearsal's), an unchanged state fails it too, and the
    reference against itself passes. The readings the limits were set from, on
    the chip at the cell's sizes: `benchmarks/lm_limits_on_chip.py`."""
    import numpy as np

    from perf import compare

    record = manifest.Cell(ROOT, os.path.join(ROOT, "BENCHMARK.json"), "trinity_ep8.packed8k")
    tiny = manifest.Cell(ROOT, LM_MANIFEST, CELL)
    ref = harness.load_reference(tiny)
    sz = ref.Sizes(tiny.config["sizes"], 2)
    rng = np.random.RandomState(5)
    batches = [{"tokens": rng.randint(0, 64, (2, 64)).astype(np.int32)} for _ in range(harness.CHECK_STEPS)]
    jitted = {}
    plain = harness.reference_numbers(ref, sz, SEED, batches, jitted=jitted)
    limits = record.config["limits"]
    for kw in ({"precision": "float8"}, {"rows": 1}):
        other = harness.reference_numbers(ref, sz, SEED, batches, jitted=(jitted if "rows" not in kw else {}), **kw)
        nums = compare.numbers(other, plain)
        assert compare.judge(nums, limits) is False, (kw, nums)
    # an unchanged state reads 1 on the one number that is held against it
    still = dict(plain, change_norms={k: 0.0 for k in plain["change_norms"]})
    nums = compare.numbers(still, plain)
    assert nums["change_norm_gap"]["value"] == pytest.approx(1.0) and compare.judge(nums, limits) is False
    assert compare.judge(compare.numbers(plain, plain), limits) is True
