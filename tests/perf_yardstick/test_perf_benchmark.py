"""Tier-1 tests of the benchmark's yardstick (perf/): CPU only.

What is checked here is arithmetic and control flow: the shape-derived FLOP
count, the trace reducer, the manifest validator, the result line, the whole
window loop at a tiny size (the rehearsal cells of perf/rehearsal/), and that
the comparison behind `correct` fails what it must fail. The toy pair
(tests/perf_yardstick/toy/: a bigram language model, its data, the smallest
trainer) goes through the same harness as the detector's rehearsal cells: the
proof that what is the model's and the data's sits behind the two names of a
configuration's file. No time, rate or share from these runs means anything.
"""

import copy
import json
import os
import subprocess
import sys
import tokenize

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import compare, flops, harness, manifest, readings, xtrace  # noqa: E402
from perf.references import vocfeed  # noqa: E402

REHEARSAL = os.path.join(ROOT, "perf", "rehearsal", "BENCHMARK.json")
TOY = os.path.join(ROOT, "tests", "perf_yardstick", "toy")
TOY_MANIFEST = os.path.join(TOY, "BENCHMARK.json")
TOY_WIDE = os.path.join(TOY, "BENCHMARK.wide.json")  # chip only, by hand (toy/run_wide.py): no test runs it
SEED = 2_147_484_001  # more than 32 signed bits hold, as the driver's are


def _toy_program():
    return harness.load_file(os.path.join(TOY, "program.py"))


def _sizes(name):
    """A configuration of record, or one planned (sized, without limits)."""
    for where in ("configs", "planned"):
        path = os.path.join(ROOT, "perf", where, name + ".json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)["sizes"]
    raise FileNotFoundError(name)


# ------------------------------------------------------------------ FLOPs


def test_flops_hand_count_of_two_layers():
    by_name = {layer["name"]: layer for layer in flops.layers(_sizes("voc_resnet18"))}
    stem = by_name["trunk/conv1"]
    assert stem["out_hw"] == (300, 300) and not stem["input_grad"]
    assert flops.forward_flops(stem) == 2 * 300 * 300 * 7 * 7 * 3 * 64
    # layer4's first 3x3 runs on each of the 128 sampled 7x7 crops, stride 2 -> 4x4
    tail = by_name["head/tail/layer4.0/conv1"]
    assert tail["out_hw"] == (4, 4) and tail["count"] == 128
    assert flops.forward_flops(tail) == 2 * 4 * 4 * 3 * 3 * 256 * 512 * 128


@pytest.mark.parametrize("config", ["voc_resnet18", "voc_resnet50_fpn"])
def test_conv_flops_agree_with_the_programs_own_jaxpr(config):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import layer_cost_table

    _, convs = layer_cost_table.collect_convs(config, 1)
    _, tot, _ = layer_cost_table.analyze(convs)
    sizes = _sizes(config)
    mine_fwd = sum(
        flops.forward_flops(layer) for layer in flops.layers(sizes) if layer["kind"] == "conv"
    )
    assert mine_fwd == pytest.approx(tot["fwd"], rel=0.03)
    mine_train = flops.train_flops_per_image(sizes, ("conv",))
    assert mine_train == pytest.approx(tot["fwd"] + tot["dgrad"] + tot["wgrad"], rel=0.03)
    # dense layers are counted on top of the convolutions
    assert flops.train_flops_per_image(sizes) > mine_train


def test_conv_roofline_says_which_bound_binds():
    got = flops.conv_roofline_seconds(_sizes("voc_resnet18"), 32, 197e12, 819e9)
    assert got["least_s"] >= max(got["flops_s"], got["bytes_s"])
    assert got["least_s"] <= got["flops_s"] + got["bytes_s"]


# ----------------------------------------------------------- trace reducer


def _hand_trace():
    ms = 1e6
    chip0 = [
        ("%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p), kind=kOutput, calls=%fc.1", 0 * ms, 4 * ms, {}),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop, calls=%fc.2", 3 * ms, 2 * ms, {}),  # overlaps fusion.1
        ("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %g), replica_groups={}", 5 * ms, 3 * ms, {}),
        ("%convolution.3 = bf16[8,8]{1,0} convolution(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %b), window={}", 6 * ms, 1 * ms, {}),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop, calls=%fc.2", 12 * ms, 8 * ms, {}),
    ]
    host = [("bench/fetch", 8 * ms, 3 * ms, {}), ("bench/step", 11 * ms, 1 * ms, {})]
    return {"devices": {"/device:TPU:0": chip0, "/device:TPU:1": []}, "host": host}


def test_busy_union_idle_share_and_exposed_collective():
    assert xtrace.union([(0, 4), (3, 5), (7, 8)]) == [(0, 5), (7, 8)]
    assert xtrace.gaps([(0, 5), (7, 8)], (0, 10)) == [(5, 7), (8, 10)]
    assert xtrace.exposed([(5, 8)], [(0, 5), (6, 7)]) == 2
    got = xtrace.reduce(_hand_trace(), 0.021, conv_ops={"fusion.1"}, origin={"fusion.2": "jit(train_step)/jvp(FasterRCNN.propose)/sort"})
    assert got["chips"] == 1  # a plane without operations is not a chip in use
    assert got["trace_window_s"] == pytest.approx(0.020)
    assert got["busy_s"] == pytest.approx(0.016)  # [0,8) and [12,20)
    assert got["conv_s"] == pytest.approx(0.005)  # fusion.1 by the module's list, convolution.3 by opcode
    assert got["allreduce_exposed_s"] == pytest.approx(0.002)  # [5,8) less convolution.3's [6,7)
    assert got["device_ops"][0] == ["fusion.2 f32[8] fusion @ jvp(FasterRCNN.propose)/sort", pytest.approx(0.010)]
    gaps = dict((k, v) for k, v in got["idle_gaps"])
    assert gaps["bench/fetch"] == pytest.approx(0.003) and gaps["bench/step"] == pytest.approx(0.001)


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        xtrace.reduce({"devices": {"/device:TPU:0": []}, "host": []}, 1.0)


# ---------------------------------------------------------------- manifest


DETECTOR_LIMITS = {"rpn_cls_grad_gap", "change_norm_gap", "feed_box_gap", "feed_pixel_gap"}


@pytest.mark.parametrize("path", [os.path.join(ROOT, "BENCHMARK.json"), REHEARSAL, TOY_MANIFEST])
def test_manifests_are_sound_and_their_files_exist(path):
    limits = {"embed_grad_gap", "change_norm_gap", "feed_row_gap"} if path == TOY_MANIFEST else DETECTOR_LIMITS
    m = manifest.load(path)
    assert manifest.validate(m) == []
    cells = [(path, w["name"]) for w in m["workloads"]]
    if path == TOY_MANIFEST:
        # the toy's second manifest, the same program with parameters that fill a chip
        assert manifest.validate(manifest.load(TOY_WIDE)) == []
        cells.append((TOY_WIDE, "bigram_wide.fed"))
    for where, name in cells:
        cell = manifest.Cell(ROOT, where, name)
        assert cell.config["sizes"] and cell.mix["feed"] in ("loader", "staged")
        for metric in cell.per_layer:
            assert os.path.exists(cell.reader_path(metric["name"])), metric["name"]
        assert {"setup_s"} < {e["name"] for e in cell.end_to_end}
        assert set(cell.config["limits"]) >= limits
        for module in (harness.load_reference(cell), harness.load_feed_reference(cell)):
            assert (os.path.dirname(os.path.dirname(module.__file__)) == TOY) == (path == TOY_MANIFEST)


def _breach(edit):
    m = copy.deepcopy(manifest.load(REHEARSAL))
    edit(m)
    return manifest.validate(m)


@pytest.mark.parametrize(
    "edit, words",
    [
        (lambda m: m["end_to_end"][0].update(unit="images per second"), "bad unit"),
        (lambda m: m["end_to_end"][0].update(unit="x" * 17), "bad unit"),
        (lambda m: m["workloads"][0].update(name="a cell"), "bad name"),
        (lambda m: m["per_layer"][1].update(why="no such key"), "keys"),
        (lambda m: m["end_to_end"][0].update(workloads=["tiny.fed"]), "does not report"),
        (lambda m: m["workloads"][0].update(chips=4), "four-chip"),
        (lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
        (lambda m: m["configs"][0].update(reduced=["fpn_hidden_dim"]), "width"),
        (lambda m: m["workloads"].__delitem__(slice(2, None)), "has no cell"),
        (lambda m: m.update(run_seconds=52), "run_seconds"),
    ],
)
def test_manifest_breaches_are_found(edit, words):
    assert any(words in e for e in _breach(edit)), _breach(edit)


# ------------------------------------------------------- no chip, no result


def test_run_exits_nonzero_and_prints_no_result_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload", "r18c4.resident",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert got.returncode != 0
    assert "metrics" not in got.stdout and got.stdout.strip() == ""


# ------------------------------------------------------------- rehearsals


def _rehearse(tmp, workload, trace, break_step=None, seconds=1.0):
    """A detector rehearsal cell, or the toy's: its own manifest and program."""
    where, program = REHEARSAL, None
    if workload.startswith("bigram."):
        where, program = TOY_MANIFEST, (_toy_program().get_config, _toy_program().Trainer)
    result, code = harness.run_cell(
        ROOT, where, workload, SEED, seconds, trace, scratch=str(tmp),
        require_tpu=False, break_step=break_step, program=program,
    )
    assert code == 0
    return result


def _live_bytes():
    """Bytes of the buffers alive on the one device, each counted once (two
    arrays may share one: `device_put` onto the sharding a buffer has)."""
    import jax

    return sum({a.unsafe_buffer_pointer(): a.nbytes for a in jax.live_arrays() if not a.is_deleted()}.values())


def _tree_bytes(tree):
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree) if isinstance(x, jax.Array))


def _alive_at_each_set_up_step(alive, before):
    """No fault: hung on `break_step`, it records at the entry of each step
    call of the set-up what is alive on the devices beside the trainer's
    state, the staged batch and what was there before the run, and the
    bytes of one parameter set."""

    def probe(trainer, step_call):
        def probed(kw):
            if len(alive) < harness.WARM_STEPS:
                others = _live_bytes() - before - _tree_bytes(trainer.state) - _tree_bytes(kw)
                alive.append((others, _tree_bytes(trainer.state.params)))
            return step_call(kw)

        return probed

    return probe


@pytest.fixture(scope="module", params=["tiny.fed", "bigram.fed"])
def fed_traced(request, tmp_path_factory):
    import gc

    seconds = 9.0 if request.param == "tiny.fed" else 1.0
    gc.collect()
    alive = []
    result = _rehearse(
        tmp_path_factory.mktemp("fed"), request.param, True, seconds=seconds,
        break_step=_alive_at_each_set_up_step(alive, _live_bytes()),
    )
    return request.param, result, alive


def test_window_loop_and_result_line_keys(fed_traced, tmp_path):
    workload, r, alive = fed_traced
    # the harness's rule: while a step of the set-up runs, nothing parameter-sized
    # of the harness's own is on the device (no copy of the first parameters, of
    # Adam's first moment, of the parameters after step 3)
    assert len(alive) == harness.WARM_STEPS
    for others, one_parameter_set in alive:
        assert others < one_parameter_set, alive
    assert list(r)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device", "breakdown"} <= set(r)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > harness.WARM_STEPS
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s", "window_s"} <= set(r["device"])
    assert r["device"]["busy_s"] > 0 and r["device"]["count"] == 1
    # per-layer metrics of a traced run; a reader with nothing to read is left out
    if workload == "bigram.fed":
        # the harness's own readers, and one the toy brings over its own scopes
        assert {"step_device_ms", "train_mfu_pct.fed", "dispatch_ms.fed", "stage_tables_ms", "stage_unscoped_pct"} == set(r["metrics"])
        assert r["metrics"]["train_mfu_pct.fed"]["value"] > 0 and r["metrics"]["stage_tables_ms"]["value"] > 0
        assert r["compared"]["feed_row_gap"] == {"value": 0.0, "limit": 0}
        assert r["compared"]["embed_grad_gap"]["value"] <= r["compared"]["embed_grad_gap"]["limit"] == 1e-3
        assert r["notes"]["data_rows"] == 256
        # and untraced: its rate under the name and unit its manifest gives, the parts its reference names
        u = _rehearse(tmp_path, workload, False)
        assert u["correct"] is True and set(u["metrics"]) == {"train_rows_per_s", "setup_s"}
        assert u["metrics"]["train_rows_per_s"]["unit"] == "rows/s" and u["metrics"]["train_rows_per_s"]["value"] > 0
        assert list(u["compared"])[:4] == ["loss1_gap", "loss2_gap", "loss3_gap", "nll1_gap"]
    else:
        assert {"feed_wait_pct", "dispatch_ms.fed", "device_idle_pct.fed", "train_mfu_pct.fed"} == set(r["metrics"])
    for entry in r["metrics"].values():
        assert set(entry) == {"value", "unit"}
    assert len(r["breakdown"]["device_ops"]) <= 10 and len(r["breakdown"]["idle_gaps"]) <= 10
    for name, entry in r["compared"].items():
        assert set(entry) == {"value", "limit"}, name
    assert r["compared"]["recompiles"]["value"] == 0
    # the loader's rows against the feed's own reference, held to limits
    if workload == "tiny.fed":
        assert r["compared"]["feed_box_gap"] == {"value": 0.0, "limit": 0}
        assert 0 < r["compared"]["feed_pixel_gap"]["value"] < r["compared"]["feed_pixel_gap"]["limit"] == 2e-5
    json.dumps(r)


def test_a_resident_cell_reports_its_own_rate(tmp_path):
    r = _rehearse(tmp_path, "tiny.resident", False)
    assert r["correct"] is True and set(r["metrics"]) == {"resident_img_per_s", "setup_s"}
    cell = manifest.Cell(ROOT, REHEARSAL, "tiny.resident")
    assert {m["name"] for m in cell.per_layer} == {
        "dispatch_ms.resident", "step_device_ms", "train_mfu_pct.resident", "conv_roofline",
        "device_idle_pct.resident", "hbm_peak_gib",
    }
    # a quantity split by the rate it moves is read by the reader of its first part
    assert cell.reader_path("dispatch_ms.resident") == cell.reader_path("dispatch_ms.fed")
    assert all(m["moves"] == "resident_img_per_s" for m in cell.per_layer)


def test_dp4_cell_on_four_virtual_devices(tmp_path):
    r = _rehearse(tmp_path, "tiny.fpn.fed.dp4", False)
    assert r["device"]["count"] == 4 and r["correct"] is True
    assert set(r["metrics"]) == {"train_img_per_s", "setup_s"}
    assert r["metrics"]["setup_s"]["value"] > 0


# ------------------------------------------ what `correct` has to fail


def _nearest_pixel_resize(monkeypatch):
    """The loader resizes by the nearest source pixel in place of the blend."""
    import numpy as np

    from replication_faster_rcnn_tpu.data import native_ops

    def nearest(img, out_hw, mean, std):
        rows = np.clip(np.rint((np.arange(out_hw[0]) + 0.5) * img.shape[0] / out_hw[0] - 0.5), 0, img.shape[0] - 1)
        cols = np.clip(np.rint((np.arange(out_hw[1]) + 0.5) * img.shape[1] / out_hw[1] - 0.5), 0, img.shape[1] - 1)
        out = img[rows.astype(int)][:, cols.astype(int)].astype(np.float32)
        return ((out / 255.0 - np.asarray(mean, np.float32)) / np.asarray(std, np.float32)).astype(np.float32)

    monkeypatch.setattr(native_ops, "decode_jpeg_resize_normalize", lambda *a, **k: None)
    monkeypatch.setattr(native_ops, "resize_normalize", nearest)


def _boxes_not_scaled(monkeypatch):
    """Boxes left at the file's size while the pixels are resized."""
    from replication_faster_rcnn_tpu.data import native_ops

    monkeypatch.setattr(native_ops, "scale_boxes", lambda boxes, labels, row_scale, col_scale: boxes)


def _mirrored_pixels_only(monkeypatch):
    """A mirrored row whose boxes stay where they were."""
    import numpy as np

    from replication_faster_rcnn_tpu.data import augment

    monkeypatch.setattr(
        augment, "hflip_sample", lambda s: dict(s, image=np.ascontiguousarray(s["image"][:, ::-1, :]))
    )


@pytest.mark.parametrize(
    "plant, number", [(_nearest_pixel_resize, "feed_pixel_gap"), (_mirrored_pixels_only, "feed_pixel_gap"),
                      (_boxes_not_scaled, "feed_box_gap")],
)
def test_a_broken_feed_comes_out_not_correct(tmp_path, monkeypatch, plant, number):
    plant(monkeypatch)
    r = _rehearse(tmp_path, "tiny.fed", False)
    assert r["correct"] is False
    over = [k for k, v in r["compared"].items() if v["limit"] is not None and not v["value"] <= v["limit"]]
    assert over == [number], r["compared"]


@pytest.mark.parametrize("how, sound", [("bilinear", True), ("uint8", False), ("nearest", False)])
def test_the_feeds_reference_in_the_loaders_place(tmp_path, how, sound):
    """At the cell's own image sizes, the feed's limits of record separate
    the reference's own rows from its control and its fault."""
    import numpy as np

    cell = manifest.Cell(ROOT, os.path.join(ROOT, "BENCHMARK.json"), "r18c4.resident")
    sizes, limits = cell.config["sizes"], cell.config["limits"]
    kit = str(tmp_path / "kit")
    vocfeed.make(kit, SEED, dict(manifest.load(os.path.join(ROOT, "perf", "mixes", "fed.json")), n_images=4))
    known = vocfeed.annotations(kit, (600, 600), 32)
    rows = [(labels, boxes) for labels, found in known.items() for _, boxes in found]
    batch = {
        "image": np.zeros((len(rows), 600, 600, 3), np.float32),
        "labels": np.full((len(rows), 32), -1, np.int32), "boxes": np.full((len(rows), 32, 4), -1.0, np.float32),
    }
    for r, (labels, boxes) in enumerate(rows):
        batch["labels"][r, : len(labels)] = labels
        batch["boxes"][r, : len(labels)] = vocfeed.mirrored(boxes, 600) if r % 2 else boxes
    batch["mask"] = batch["labels"] >= 0
    nums = vocfeed.numbers(kit, [batch], sizes, in_place=how)
    assert nums["feed_box_gap"]["value"] == 0.0
    assert compare.judge(nums, limits) is sound, nums


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
    """Half of the batch left out, the mean taken over the rest: the second
    half of every batch is overwritten with the first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def broken(kw):
        (key, batch), = kw.items()
        half = next(iter(batch.values())).shape[0] // 2
        lib = jnp if key == "staged" else np
        with jax.transfer_guard("allow"):
            batch = {k: lib.concatenate([v[:half], v[:half]]) for k, v in batch.items()}
        return step_call({key: batch})

    return broken


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_a_broken_timed_path_comes_out_not_correct(tmp_path, fault):
    """On the detector's tiny cell and on the toy's: both faults are written
    over whatever keys a batch has and whatever state a trainer keeps."""
    for workload in ("tiny.resident", "bigram.fed"):
        r = _rehearse(tmp_path, workload, False, break_step=fault)
        assert r["correct"] is False, workload
        over = [k for k, v in r["compared"].items() if v["limit"] is not None and not v["value"] <= v["limit"]]
        assert over, (workload, r["compared"])


def test_the_control_in_float8_comes_out_not_correct(tmp_path):
    """The reference put in the program's place, computed in the nearest
    precision below the configuration's bfloat16, fails the comparison."""
    import numpy as np

    cell = manifest.Cell(ROOT, os.path.join(ROOT, "BENCHMARK.json"), "r18c4.resident")
    tiny = manifest.Cell(ROOT, REHEARSAL, "tiny.resident")
    ref = harness.load_reference(tiny)
    sz = ref.Sizes(tiny.config["sizes"], 4)
    rng = np.random.RandomState(3)
    batches = []
    for _ in range(harness.CHECK_STEPS):
        boxes = np.full((4, 32, 4), -1.0, np.float32)
        boxes[:, 0] = [8.0, 10.0, 40.0, 44.0]
        labels = np.full((4, 32), -1, np.int32)
        labels[:, 0] = rng.randint(1, 21, 4)
        batches.append(
            {"image": rng.randn(4, 64, 64, 3).astype(np.float32), "boxes": boxes,
             "labels": labels, "mask": labels >= 0}
        )
    # the reference runs at its own state's size: at the entry of each step,
    # parameters and both moments (the first parameters wait on the host, the
    # last step's gradient is dropped), and each step writes over what it is given
    import gc

    import jax

    entries = []

    class Probed(dict):
        """The `jitted` cache, each step program it is given wrapped."""

        def __setitem__(self, key, program):
            def probed(params, adam, batch, *rest):
                entries.append((_live_bytes() - before - _tree_bytes(batch), _tree_bytes(params)))
                out = program(params, adam, batch, *rest)
                # on the CPU the host's copy of the first parameters is a view of their
                # buffers, which the first step of a run can therefore not take
                given = adam if len(entries) % harness.CHECK_STEPS == 1 else (params, adam)
                assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(given)), "not donated"
                return out

            super().__setitem__(key, probed)

    gc.collect()
    before = _live_bytes()
    jitted = Probed()
    plain = harness.reference_numbers(ref, sz, SEED, batches, jitted=jitted)
    control = harness.reference_numbers(ref, sz, SEED, batches, precision="float8", jitted=jitted)
    assert len(entries) == 2 * harness.CHECK_STEPS
    slack = 1 << 16  # keys, the step's number, the losses
    for alive, one_parameter_set in entries:
        assert 3 * one_parameter_set <= alive <= 4 * one_parameter_set + slack, entries
    nums = compare.numbers(control, plain)
    # held to the limits of the cell of record, not to the rehearsal's
    assert compare.judge(nums, cell.config["limits"]) is False, nums
    same = compare.numbers(plain, plain)
    assert compare.judge(same, cell.config["limits"]) is True


def test_readings_judge_the_program_the_controls_and_the_faults(tmp_path):
    """The tool behind the limits, at the rehearsal's size: every set of
    numbers goes through `compare.judge` with the cell's own limits."""
    import jax

    cell = manifest.Cell(ROOT, REHEARSAL, "tiny.resident")
    out = str(tmp_path / "readings.jsonl")
    said = []
    readings.take(cell, jax.devices()[:1], [SEED, SEED + 7919], 1, str(tmp_path), out, say=lambda *a, **k: said.append(a))
    with open(out) as f:
        rows = [json.loads(line) for line in f]
    assert [set(r) & set(readings.KINDS) for r in rows] == [set(readings.KINDS), {"program"}]
    assert all(r["program"]["correct"] for r in rows)
    first = rows[0]
    assert first["control_feed_uint8"]["correct"] is False and first["feed_nearest_pixel"]["correct"] is False
    assert first["half_batch"]["correct"] is False
    for kind in readings.KINDS:
        assert all(set(v) == {"value", "limit"} for v in first[kind]["numbers"].values())
    assert any("has to be 0" in " ".join(map(str, line)) for line in said)


# ------------------------------------------------------------- the seam


HARNESS_FILES = ("harness", "compare", "traffic", "stagecut", "reckon_memory", "readings")
DETECTOR_WORDS = ("voc", "devkit", "rpn", "boxes", '"image"', "'image'", "frcnn.")


def test_the_harness_files_hold_none_of_the_detectors_words():
    """Outside comments (which may point at the modules that own them), what
    is the detector's is behind the configuration's two modules; and nothing
    under perf/ names the toy pair."""
    for name in HARNESS_FILES:
        with open(os.path.join(ROOT, "perf", name + ".py")) as f:
            code = " ".join(t.string for t in tokenize.generate_tokens(f.readline) if t.type != tokenize.COMMENT).lower()
        assert [w for w in DETECTOR_WORDS if w in code] == [], name
    for where, _, files in os.walk(os.path.join(ROOT, "perf")):
        for name in files:
            if name.endswith((".py", ".json")):
                with open(os.path.join(where, name)) as f:
                    text = f.read().lower()
                assert "toy" not in text and "bigram" not in text, os.path.join(where, name)


def test_modules_beside_a_cells_data_files_are_found_first_and_reckon_memory_lowers_from_them(tmp_path):
    """References and readers alike: `<data dir>/<kind>/<name>.py`, else the
    harness's own; the data directory is where `configs/` lies. And
    `reckon_memory.reckon` goes through the same lookup: it lowers the toy's
    step and its reference step over its data module's `batch_spec` and
    compiles them (for this host, where a test has no chip to describe);
    no device runs anything."""
    import jax

    from perf import reckon_memory

    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "frcnn.py").write_text("WHOSE = 'the cell'\n")
    data_dir = manifest.data_dir_of(str(tmp_path / "configs" / "any.json"))
    assert data_dir == str(tmp_path)
    assert manifest.beside(data_dir, "references", "frcnn") == str(tmp_path / "references" / "frcnn.py")
    assert harness.load_module(data_dir, "references", "frcnn").WHOSE == "the cell"
    shared = os.path.join(ROOT, "perf", "references", "vocfeed.py")
    assert manifest.beside(data_dir, "references", "vocfeed") == shared
    assert manifest.beside(data_dir, "metrics", "step_device_ms") == os.path.join(ROOT, "perf", "metrics", "step_device_ms.py")
    # a file is loaded once a process, whoever asks
    assert harness.load_file(shared) is harness.load_module(os.path.join(ROOT, "perf"), "references", "vocfeed")
    toy = manifest.Cell(ROOT, TOY_MANIFEST, "bigram.fed")
    assert toy.reader_path("stage_tables_ms").startswith(TOY) and not toy.reader_path("step_device_ms").startswith(TOY)
    assert harness.load_reference(toy).SCOPE_PREFIX == "bigram."
    assert harness.load_reference(manifest.Cell(ROOT, REHEARSAL, "tiny.fed")).SCOPE_PREFIX == "frcnn."

    program = _toy_program()
    out = reckon_memory.reckon(
        os.path.join(TOY, "configs", "bigram.json"), True, program=(program.get_config, program.step_and_state),
        chip=jax.sharding.SingleDeviceSharding(jax.devices()[0]),
    )
    assert out["per_chip_batch"] == 16 and "described" not in out["device"]
    # the reference step is lowered as the harness runs it: parameters and moments written over
    assert out["reference_step"]["alias_bytes"] >= 3 * 4 * (2 * 512 * 128 + 512)
    for step in ("train_step", "reference_step"):
        # the two tables, Adam's moments of them, and a batch of 16 x 64 tokens and targets
        assert out[step]["argument_bytes"] >= 3 * 4 * (2 * 512 * 128 + 512) + 2 * 4 * 16 * 64
        assert out[step]["total_bytes"] > 0
    spec = harness.load_module(TOY, "references", "tokens").batch_spec({"data.seq_len": 64}, 16)
    assert {k: v[0] for k, v in spec.items()} == {"tokens": (16, 64), "targets": (16, 64)}
