"""HLO program auditor (ISSUE 6 tentpole): fingerprint parsing, the
HX001-HX007 contract rules, bank round-trips, and the tier-1 audit gate.

Two tiers inside this file:

* pure unit tests over canned StableHLO / compiled-module text and
  synthetic fingerprint dicts — no lowering, milliseconds;
* the package gate: AOT-lower ONE program (train_spmd_k1 — the richest:
  donation aliasing, hand-placed psums, the bf16 all-reduce contract,
  memory analysis) in a module fixture and drive every audit arm off it —
  clean pass against the committed bank, a seeded contract violation and
  a seeded drift each exiting nonzero through the CLI naming the rule
  and program, and a deterministic --update re-bank. The cached-feed and
  eval contracts are asserted from the committed bank's records (no
  compile); the slow tier re-lowers those feeds live. The committed bank
  under analysis/fingerprints/ covers the full 7-program matrix (banked
  offline via `frcnn audit --update`).
"""

import copy
import json
import pathlib

import pytest

from replication_faster_rcnn_tpu.analysis import fingerprint as fp_mod
from replication_faster_rcnn_tpu.analysis import hlolint

GATE_PROGRAMS = ("train_spmd_k1",)
SLOW_PROGRAMS = ("train_cached_k1", "eval_infer")


# --------------------------------------------------------------- parsing unit

COMPILED_HEADER = """\
HloModule jit_step, input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias), {2, 0}: (3, {}, must-alias) }, entry_computation_layout={...}

ENTRY %main.42 (p0: f32[4], p1: f32[4], p2: s32[2], p3: f32[8]) -> (f32[4], f32[4]) {
  %p0 = f32[4] parameter(0)
}
"""

STABLEHLO_SPMD = """\
module @jit_train_step {
  func.func public @main(%arg0: tensor<4xbf16>) -> tensor<4xbf16> {
    %0 = "stablehlo.all_reduce"(%arg0) <{replica_groups = dense<> : tensor<0x0xi64>}> ({
    ^bb0(%a: tensor<bf16>, %b: tensor<bf16>):
      %s = stablehlo.add %a, %b : tensor<bf16>
      stablehlo.return %s : tensor<bf16>
    }) : (tensor<4xbf16>) -> tensor<4xbf16>
    %1 = "stablehlo.all_reduce"(%0) <{replica_groups = dense<> : tensor<0x0xi64>}> ({
    ^bb0(%a: tensor<bf16>, %b: tensor<bf16>):
      %s = stablehlo.add %a, %b : tensor<bf16>
      stablehlo.return %s : tensor<bf16>
    }) : (tensor<4xbf16>) -> tensor<4xbf16>
    %2 = "stablehlo.all_reduce"(%1) <{replica_groups = dense<> : tensor<0x0xi64>}> ({
    ^bb0(%a: tensor<f32>, %b: tensor<f32>):
      %s = stablehlo.add %a, %b : tensor<f32>
      stablehlo.return %s : tensor<f32>
    }) : (tensor<f32>) -> tensor<f32>
    %3 = "stablehlo.all_gather"(%2) <{all_gather_dim = 0 : i64}> : (tensor<4xbf16>) -> tensor<8xbf16>
    return %1 : tensor<4xbf16>
  }
}
"""


# COMPILED module with GSPMD-inserted collectives on a (2, 4) mesh:
# row-major device grid, so model-axis groups are consecutive runs and
# data-axis groups are strided — in both the explicit replica_groups
# form and the iota [G,S]<=[N] (optionally transposed) form
COMPILED_PARTITIONED = """\
HloModule jit_train, entry_computation_layout={...}

ENTRY %main {
  %ag = f32[64,4]{1,0} all-gather(%x), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={1}, use_global_device_ids=true
  %ar = f32[64]{0} all-reduce(%y), channel_id=2, replica_groups=[4,2]<=[2,4]T(1,0), use_global_device_ids=true, to_apply=%add
  %ar.1 = bf16[8]{0} all-reduce(%z), channel_id=3, replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %rs = f32[8]{0} reduce-scatter(%w), channel_id=4, replica_groups=[2,4]<=[8], dimensions={0}, to_apply=%add
}
"""

MESH_2x4 = {"data": 2, "model": 4}


class TestPartitionedCollectives:
    def test_axis_classification_on_2x4_mesh(self):
        inv = fp_mod.parse_partitioned_collectives(
            COMPILED_PARTITIONED, MESH_2x4
        )
        # explicit consecutive groups -> model axis
        assert inv["all-gather"] == {"count": 1, "axes": {"model": 1}}
        # iota [2,4]<=[8] reshapes to consecutive rows -> model axis
        assert inv["reduce-scatter"] == {"count": 1, "axes": {"model": 1}}
        # transposed iota -> strided {{0,4},{1,5},...} -> data axis;
        # the single 8-device group is 'all'
        assert inv["all-reduce"] == {
            "count": 2,
            "axes": {"all": 1, "data": 1},
        }

    def test_unknown_mesh_buckets_as_world(self):
        inv = fp_mod.parse_partitioned_collectives(COMPILED_PARTITIONED, None)
        assert all(
            set(entry["axes"]) == {"world"} for entry in inv.values()
        )

    def test_collective_free_module_is_empty(self):
        assert (
            fp_mod.parse_partitioned_collectives(COMPILED_HEADER, MESH_2x4)
            == {}
        )

    def test_instruction_names_not_double_counted(self):
        # `%all-reduce.1 = ... all-reduce(...)`: the NAME must not count
        text = (
            "  %all-reduce.1 = f32[4]{0} all-reduce(%x), "
            "replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add\n"
        )
        inv = fp_mod.parse_partitioned_collectives(text, MESH_2x4)
        assert inv == {"all-reduce": {"count": 1, "axes": {"model": 1}}}

    def test_replica_group_decoding(self):
        assert fp_mod._parse_replica_groups("{{0,1},{2,3}}") == [
            [0, 1],
            [2, 3],
        ]
        assert fp_mod._parse_replica_groups("[2,4]<=[8]") == [
            [0, 1, 2, 3],
            [4, 5, 6, 7],
        ]
        assert fp_mod._parse_replica_groups("[4,2]<=[2,4]T(1,0)") == [
            [0, 4],
            [1, 5],
            [2, 6],
            [3, 7],
        ]
        assert fp_mod._parse_replica_groups("garbage") is None


class TestParsing:
    def test_alias_map_entries(self):
        entries = fp_mod.parse_alias_map(COMPILED_HEADER)
        assert entries == [
            {"output": "0", "parameter": 0, "kind": "may-alias"},
            {"output": "1", "parameter": 1, "kind": "may-alias"},
            {"output": "2,0", "parameter": 3, "kind": "must-alias"},
        ]

    def test_alias_map_absent_header(self):
        assert fp_mod.parse_alias_map("HloModule jit_step\nENTRY %main") == []
        assert fp_mod.parse_alias_map("") == []

    def test_collectives_inventory_counts_and_types(self):
        inv = fp_mod.parse_collectives(STABLEHLO_SPMD)
        assert inv["all_reduce"]["count"] == 3
        # element type read per op: 2 bf16 + 1 f32 (scalar tensor form)
        assert inv["all_reduce"]["element_types"] == {"bf16": 2, "f32": 1}
        assert inv["all_gather"]["count"] == 1
        assert "reduce_scatter" not in inv

    def test_collective_free_module_is_empty_dict(self):
        assert fp_mod.parse_collectives("module @jit { func.func @main }") == {}

    def test_contains_f64(self):
        assert fp_mod.contains_f64("%0 = tensor<4xf64>")
        assert fp_mod.contains_f64("(tensor<f64>) -> tensor<f64>")
        assert not fp_mod.contains_f64("tensor<4xf32> tensor<bf16>")

    def test_custom_calls_both_print_forms(self):
        text = (
            '%0 = stablehlo.custom_call @tpu_custom_call(%arg0) : ...\n'
            '%1 = stablehlo.custom_call @tpu_custom_call(%0) : ...\n'
            '%2 = "stablehlo.custom_call"(%1) <{api_version = 2 : i32, '
            'call_target_name = "Sharding"}> : ...\n'
        )
        assert fp_mod.parse_custom_calls(text) == {
            "Sharding": 1,
            "tpu_custom_call": 2,
        }
        assert fp_mod.parse_custom_calls(STABLEHLO_SPMD) == {}

    def test_module_hash_is_short_stable_and_content_sensitive(self):
        h = fp_mod.module_hash(STABLEHLO_SPMD)
        assert len(h) == 16 and h == fp_mod.module_hash(STABLEHLO_SPMD)
        assert h != fp_mod.module_hash(STABLEHLO_SPMD + " ")

    def test_memory_stats_peak_math(self):
        class FakeMA:
            argument_size_in_bytes = 100.0
            output_size_in_bytes = 60.0
            alias_size_in_bytes = 40.0
            temp_size_in_bytes = 25.0
            generated_code_size_in_bytes = 5.0

        class FakeCompiled:
            def memory_analysis(self):
                return FakeMA()

        stats = fp_mod.memory_stats(FakeCompiled())
        assert stats["peak_bytes_estimate"] == 100.0 + 60.0 - 40.0 + 25.0

    def test_memory_stats_unavailable_is_none(self):
        class NoMA:
            def memory_analysis(self):
                raise NotImplementedError

        assert fp_mod.memory_stats(NoMA()) is None


# ------------------------------------------------------------------- bank I/O


class TestBankIO:
    def test_round_trip(self, tmp_path):
        bank = fp_mod.make_bank(
            programs={"train_spmd_k1": {"cost": {"flops": 1.0}}},
            platform="cpu",
            n_devices=8,
            config_summary={"batch_size": 2},
        )
        path = fp_mod.bank_path(str(tmp_path), "ci", "cpu")
        assert path.endswith("ci_cpu.json")
        fp_mod.save_bank(path, bank)
        loaded = fp_mod.load_bank(path)
        assert loaded == bank
        assert loaded["schema"] == fp_mod.SCHEMA

    def test_load_missing_or_bad_schema_is_none(self, tmp_path):
        assert fp_mod.load_bank(str(tmp_path / "nope.json")) is None
        bad = tmp_path / "bad.json"
        bad.write_text('{"schema": "something_else/v9", "programs": {}}')
        assert fp_mod.load_bank(str(bad)) is None
        notjson = tmp_path / "garbage.json"
        notjson.write_text("{not json")
        assert fp_mod.load_bank(str(notjson)) is None

    def test_save_is_deterministic(self, tmp_path):
        bank = fp_mod.make_bank({"b": {"x": 1}, "a": {"y": 2}}, "cpu", 8, {})
        p1, p2 = str(tmp_path / "one.json"), str(tmp_path / "two.json")
        fp_mod.save_bank(p1, bank)
        fp_mod.save_bank(p2, bank)
        assert pathlib.Path(p1).read_bytes() == pathlib.Path(p2).read_bytes()


# ----------------------------------------------------------------- drift unit


def _fp(**over):
    """A minimal, contract-clean synthetic fingerprint."""
    base = {
        "program": "train_spmd_k1",
        "feed": "spmd",
        "k": 1,
        "args": {"state": [{"path": ".params", "shape": [4], "dtype": "float32", "sharding": None}]},
        "params": {"state": [0, 4], "batch": [4, 6]},
        "outputs": [],
        "aliasing": [
            {"output": str(i), "parameter": i, "kind": "may-alias"}
            for i in range(4)
        ],
        "collectives": {
            "all_reduce": {"count": 3, "element_types": {"bf16": 2, "f32": 1}}
        },
        "has_f64": False,
        "cost": {"flops": 1e9, "bytes_accessed": 1e8},
        "memory": {"peak_bytes_estimate": 1e8},
        "meta": {"n_float_grad_leaves": 2},
    }
    base.update(over)
    return base


class TestDiffPrograms:
    def test_identical_is_clean(self):
        assert fp_mod.diff_programs(_fp(), _fp()) == []

    def test_cost_within_tolerance_is_clean(self):
        cur = _fp(cost={"flops": 1e9 * 1.01, "bytes_accessed": 1e8})
        assert fp_mod.diff_programs(cur, _fp()) == []

    def test_cost_drift_reported(self):
        cur = _fp(cost={"flops": 1e9 * 1.5, "bytes_accessed": 1e8})
        msgs = fp_mod.diff_programs(cur, _fp())
        assert any("cost.flops" in m for m in msgs)

    def test_structural_change_reported(self):
        cur = _fp(aliasing=[])
        msgs = fp_mod.diff_programs(cur, _fp())
        assert msgs == ["aliasing changed vs bank"]

    def test_memory_availability_change_reported(self):
        msgs = fp_mod.diff_programs(_fp(memory=None), _fp())
        assert any("memory analysis availability" in m for m in msgs)


# -------------------------------------------------------------- contract unit


def _cfg(grad_dt="bfloat16"):
    cfg = hlolint.audit_config()
    if grad_dt != cfg.train.grad_allreduce_dtype:
        import dataclasses

        cfg = dataclasses.replace(
            cfg,
            train=dataclasses.replace(cfg.train, grad_allreduce_dtype=grad_dt),
        )
    return cfg


BUDGET = 16 << 30


class TestContracts:
    def test_clean_fingerprint_passes(self):
        assert hlolint.check_contracts({"p": _fp()}, _cfg(), BUDGET) == []

    def test_hx001_lost_state_alias(self):
        fp = _fp(aliasing=_fp()["aliasing"][:2])  # leaves 2,3 lost
        [v] = hlolint.check_contracts({"p": fp}, _cfg(), BUDGET)
        assert v.rule == "HX001" and "donation did not survive" in v.message

    def test_hx001_cache_alias_leak(self):
        fp = _fp(
            feed="cached",
            params={"state": [0, 4], "cache": [4, 6], "sel": [6, 7]},
            aliasing=_fp()["aliasing"]
            + [{"output": "4", "parameter": 4, "kind": "may-alias"}],
            collectives={},
        )
        [v] = hlolint.check_contracts({"p": fp}, _cfg(), BUDGET)
        assert v.rule == "HX001" and "`cache`" in v.message

    def test_hx001_eval_must_not_alias(self):
        fp = _fp(
            feed="eval",
            params={"variables": [0, 4], "images": [4, 5]},
            aliasing=[{"output": "0", "parameter": 0, "kind": "may-alias"}],
            collectives={},
        )
        [v] = hlolint.check_contracts({"p": fp}, _cfg(), BUDGET)
        assert v.rule == "HX001" and "eval" in v.message

    def test_hx002_f64(self):
        [v] = hlolint.check_contracts({"p": _fp(has_f64=True)}, _cfg(), BUDGET)
        assert v.rule == "HX002" and "f64" in v.message

    def test_hx002_missing_bf16_allreduce(self):
        fp = _fp(
            collectives={
                "all_reduce": {"count": 3, "element_types": {"f32": 3}}
            }
        )
        [v] = hlolint.check_contracts({"p": fp}, _cfg("bfloat16"), BUDGET)
        assert v.rule == "HX002" and "bfloat16" in v.message

    def test_hx002_bf16_under_f32_config(self):
        [v] = hlolint.check_contracts({"p": _fp()}, _cfg("float32"), BUDGET)
        assert v.rule == "HX002" and "lost precision" in v.message

    def test_hx003_spmd_without_psums(self):
        fp = _fp(collectives={})
        viols = hlolint.check_contracts({"p": fp}, _cfg(), BUDGET)
        # losing the all_reduces also fails the HX002 bf16 count
        assert "HX003" in {v.rule for v in viols}

    def test_hx003_spmd_unexpected_kind(self):
        fp = _fp(
            collectives={
                "all_reduce": {"count": 3, "element_types": {"bf16": 2, "f32": 1}},
                "all_gather": {"count": 1},
            }
        )
        [v] = hlolint.check_contracts({"p": fp}, _cfg(), BUDGET)
        assert v.rule == "HX003" and "all_gather" in v.message

    def test_hx003_jit_feed_must_be_collective_free(self):
        fp = _fp(
            feed="loader",
            collectives={"all_reduce": {"count": 1, "element_types": {"f32": 1}}},
        )
        [v] = hlolint.check_contracts({"p": fp}, _cfg(), BUDGET)
        assert v.rule == "HX003" and "loader" in v.message

    def test_hx003_mp_requires_model_axis_exchange(self):
        fp = _fp(
            feed="mp",
            program="train_mp_k1",
            collectives={},
            partitioned_collectives={
                "all-reduce": {"count": 2, "axes": {"data": 2}}
            },
        )
        [v] = hlolint.check_contracts({"p": fp}, _cfg(), BUDGET)
        assert v.rule == "HX003" and "model-axis" in v.message

    def test_hx003_mp_with_model_gathers_is_clean(self):
        fp = _fp(
            feed="mp",
            program="train_mp_k1",
            collectives={},
            partitioned_collectives={
                "all-gather": {"count": 5, "axes": {"model": 5}},
                "all-reduce": {"count": 2, "axes": {"data": 2}},
            },
        )
        assert hlolint.check_contracts({"p": fp}, _cfg(), BUDGET) == []

    def test_hx003_dp_feed_must_not_touch_model_axis(self):
        fp = _fp(
            feed="loader",
            collectives={},
            partitioned_collectives={
                "all-gather": {"count": 1, "axes": {"model": 1}}
            },
        )
        [v] = hlolint.check_contracts({"p": fp}, _cfg(), BUDGET)
        assert v.rule == "HX003" and "only the mp feeds" in v.message

    def test_records_without_partitioned_field_skip_the_mp_rule(self):
        # pre-mp banked records have no partitioned_collectives: clean
        assert "partitioned_collectives" not in _fp()
        assert hlolint.check_contracts({"p": _fp()}, _cfg(), BUDGET) == []

    def test_hx004_over_budget(self):
        viols = hlolint.check_contracts({"p": _fp()}, _cfg(), 1)
        assert [v.rule for v in viols] == ["HX004"]

    def test_hx004_skipped_without_memory_analysis(self):
        assert (
            hlolint.check_contracts({"p": _fp(memory=None)}, _cfg(), 1) == []
        )


def _twin_pair(twin_over=None, base_over=None):
    """A clean (base, __pallas twin) fingerprint pair (eval feed: no
    aliasing/collective expectations to trip)."""
    base = _fp(
        program="eval_infer", feed="eval", params={"variables": [0, 4]},
        aliasing=[], collectives={}, custom_calls={}, module_hash="a" * 16,
        meta={},
    )
    base.update(base_over or {})
    twin = dict(
        base,
        program="eval_infer__pallas",
        custom_calls={},
        module_hash="b" * 16,
        meta={
            "ops_backend": "pallas",
            "pallas_interpret": True,
            "twin": "eval_infer",
        },
    )
    twin.update(twin_over or {})
    return {"eval_infer": base, "eval_infer__pallas": twin}


class TestHX007OpsBackend:
    def test_clean_interpret_twin_passes(self):
        assert hlolint.check_contracts(_twin_pair(), _cfg(), BUDGET) == []

    def test_pallas_custom_call_in_xla_program(self):
        fps = _twin_pair(base_over={"custom_calls": {"tpu_custom_call": 2}})
        [v] = hlolint.check_contracts(fps, _cfg(), BUDGET)
        assert v.rule == "HX007" and v.program == "eval_infer"
        assert "leaked" in v.message

    def test_interpret_twin_must_differ_from_base(self):
        fps = _twin_pair(twin_over={"module_hash": "a" * 16})
        [v] = hlolint.check_contracts(fps, _cfg(), BUDGET)
        assert v.rule == "HX007" and v.program == "eval_infer__pallas"
        assert "byte-identical" in v.message

    def test_interpret_twin_skips_hash_check_without_base(self):
        fps = _twin_pair(twin_over={"module_hash": "a" * 16})
        del fps["eval_infer"]
        assert hlolint.check_contracts(fps, _cfg(), BUDGET) == []

    def test_compiled_twin_requires_pallas_custom_call(self):
        fps = _twin_pair(
            twin_over={"meta": {
                "ops_backend": "pallas",
                "pallas_interpret": False,
                "twin": "eval_infer",
            }}
        )
        [v] = hlolint.check_contracts(fps, _cfg(), BUDGET)
        assert v.rule == "HX007" and "real accelerator" in v.message

    def test_compiled_twin_with_mosaic_call_passes(self):
        fps = _twin_pair(
            twin_over={
                "custom_calls": {"tpu_custom_call": 1},
                "meta": {
                    "ops_backend": "pallas",
                    "pallas_interpret": False,
                    "twin": "eval_infer",
                },
            }
        )
        assert hlolint.check_contracts(fps, _cfg(), BUDGET) == []

    def test_records_without_custom_calls_field_skip_the_rule(self):
        # banked records from before ISSUE 13 carry no custom_calls —
        # the rule must not fire on them (mirrors the mp-rule skip)
        fps = _twin_pair()
        for fp in fps.values():
            fp.pop("custom_calls")
        assert hlolint.check_contracts(fps, _cfg(), BUDGET) == []


class TestDriftRules:
    EXPECTED = ("p",)

    def test_missing_bank_is_hx006(self):
        [v] = hlolint.check_drift({}, None, "/x/ci_cpu.json", self.EXPECTED, "cpu", 8)
        assert v.rule == "HX006" and "--update" in v.message

    def test_platform_mismatch_is_hx006(self):
        bank = fp_mod.make_bank({"p": _fp()}, "tpu", 4, {})
        [v] = hlolint.check_drift(
            {"p": _fp()}, bank, "f", self.EXPECTED, "cpu", 8
        )
        assert v.rule == "HX006" and "topolog" in v.message

    def test_program_set_mismatch_is_hx006(self):
        bank = fp_mod.make_bank({"p": _fp(), "zombie": _fp()}, "cpu", 8, {})
        viols = hlolint.check_drift(
            {"p": _fp()}, bank, "f", self.EXPECTED, "cpu", 8
        )
        assert {v.rule for v in viols} == {"HX006"}
        assert any("zombie" in v.message for v in viols)

    def test_per_program_drift_is_hx005(self):
        bank = fp_mod.make_bank({"p": _fp()}, "cpu", 8, {})
        cur = _fp(cost={"flops": 2e9, "bytes_accessed": 1e8})
        viols = hlolint.check_drift(
            {"p": cur}, bank, "f", self.EXPECTED, "cpu", 8
        )
        assert [v.rule for v in viols] == ["HX005"]
        assert viols[0].program == "p"

    # one leaf's sharding as the bank's jax printed it and as jax 0.9.0 does
    BANKED_REPR = (
        "NamedSharding(mesh=Mesh('data': 2, 'model': 1), "
        "spec=PartitionSpec('data',), memory_kind=unpinned_host)"
    )
    LIVE_REPR = (
        "NamedSharding(mesh=Mesh('data': 2, 'model': 1, "
        "axis_types=(Auto, Auto)), spec=PartitionSpec('data',), "
        "memory_kind=device)"
    )

    @staticmethod
    def _with_leaf(sharding, shape=(2, 8, 4)):
        leaf = {
            "path": "['boxes']", "shape": list(shape), "dtype": "float32",
            "sharding": sharding,
        }
        return _fp(args={"batch": [leaf]})

    def _drift(self, cur):
        bank = fp_mod.make_bank(
            {"p": self._with_leaf(self.BANKED_REPR)}, "cpu", 8, {}
        )
        return hlolint.check_drift(
            {"p": cur}, bank, "f", self.EXPECTED, "cpu", 8
        )

    def test_how_jax_prints_a_sharding_is_no_drift(self):
        assert self.BANKED_REPR != self.LIVE_REPR
        assert self._drift(self._with_leaf(self.LIVE_REPR)) == []

    @pytest.mark.parametrize(
        "sharding, shape",
        [
            pytest.param(
                LIVE_REPR.replace("PartitionSpec('data',)", "PartitionSpec()"),
                (2, 8, 4), id="spec",
            ),
            pytest.param(
                LIVE_REPR.replace("'data': 2", "'data': 4"), (2, 8, 4),
                id="axis_size",
            ),
            pytest.param(LIVE_REPR, (4, 8, 4), id="leaf_shape"),
            pytest.param(None, (2, 8, 4), id="sharding_gone"),
        ],
    )
    def test_what_a_sharding_is_still_drifts(self, sharding, shape):
        [v] = self._drift(self._with_leaf(sharding, shape))
        assert v.rule == "HX005" and v.program == "p"
        assert "args changed vs bank" in v.message


# ----------------------------------------------------------- the package gate


@pytest.fixture(scope="module")
def collected():
    """AOT-lower + compile the tier-1 gate program once for the module:
    the spmd feed exercises every contract at once (state donation under
    shard_map, hand-placed psum all_reduces, the bf16 gradient-exchange
    dtype, memory analysis). One compile (~25 s CPU) is the whole budget
    this file spends; the remaining feeds are audited live in the slow
    tier and from the committed bank here."""
    return hlolint.collect_fingerprints(
        hlolint.audit_config(), programs=list(GATE_PROGRAMS)
    )


class TestAuditGate:
    def test_committed_bank_covers_full_matrix(self):
        import jax

        bank_file = hlolint.resolve_bank_file(hlolint.audit_config())
        bank = fp_mod.load_bank(bank_file)
        assert bank is not None, (
            f"missing committed fingerprint bank at {bank_file} — "
            "run `frcnn audit --update` and commit the result"
        )
        assert bank["platform"] == jax.default_backend()
        assert bank["n_devices"] == len(jax.devices())
        assert sorted(bank["programs"]) == sorted(
            hlolint.expected_program_names(config=hlolint.audit_config())
        )

    def test_audit_gate_clean_against_committed_bank(self, collected):
        result = hlolint.run_audit(fingerprints=collected)
        assert result.ok, "\n".join(str(v) for v in result.violations)
        assert sorted(result.programs) == sorted(GATE_PROGRAMS)

    def test_state_donated_live(self, collected):
        spmd = collected["train_spmd_k1"]
        s0, s1 = spmd["params"]["state"]
        aliased = {a["parameter"] for a in spmd["aliasing"]}
        assert set(range(s0, s1)) <= aliased

    def test_bf16_allreduce_per_grad_leaf_live(self, collected):
        spmd = collected["train_spmd_k1"]
        types = spmd["collectives"]["all_reduce"]["element_types"]
        assert types.get("bf16", 0) >= spmd["meta"]["n_float_grad_leaves"]

    def test_banked_cache_never_aliased_eval_clean(self):
        """The cache-not-donated and eval-no-aliasing contracts, read
        from the committed bank (no compile here; the slow tier and the
        offline banking run produce these records live)."""
        bank = fp_mod.load_bank(
            hlolint.resolve_bank_file(hlolint.audit_config())
        )
        assert bank is not None
        for name in ("train_cached_k1", "train_cached_k2"):
            fp = bank["programs"][name]
            aliased = {a["parameter"] for a in fp["aliasing"]}
            s0, s1 = fp["params"]["state"]
            assert set(range(s0, s1)) <= aliased
            for role in ("cache", "sel"):
                r0, r1 = fp["params"][role]
                assert not (aliased & set(range(r0, r1))), (name, role)
            assert fp["collectives"] == {}  # jit feeds: collective-free
        ev = bank["programs"]["eval_infer"]
        assert ev["aliasing"] == [] and ev["collectives"] == {}

    def test_cli_audit_exits_zero(self, capsys, monkeypatch, collected):
        from replication_faster_rcnn_tpu import cli

        monkeypatch.setattr(
            hlolint, "collect_fingerprints", lambda *a, **k: collected
        )
        rc = cli.main(
            ["audit", "--device", "cpu", "--json",
             "--programs", ",".join(GATE_PROGRAMS)]
        )
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert payload["ok"] is True
        assert sorted(payload["rules"]) == sorted(
            {**hlolint.HLO_RULES, **hlolint.AUDIT_SHARD_RULES}
        )
        assert "comm" in payload

    def test_seeded_contract_violation_exits_nonzero(
        self, capsys, monkeypatch, collected
    ):
        """Force the f32 all-reduce regression under a bf16 config: the
        audit must exit 1 naming HX002 and the program."""
        doctored = copy.deepcopy(collected)
        ar = doctored["train_spmd_k1"]["collectives"]["all_reduce"]
        types = ar["element_types"]
        types["f32"] = types.get("f32", 0) + types.pop("bf16", 0)
        from replication_faster_rcnn_tpu import cli

        monkeypatch.setattr(
            hlolint, "collect_fingerprints", lambda *a, **k: doctored
        )
        rc = cli.main(["audit", "--device", "cpu"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "HX002" in out and "train_spmd_k1" in out

    def test_seeded_drift_exits_nonzero(
        self, capsys, monkeypatch, tmp_path, collected
    ):
        """Doctor the banked flops of one program: the audit must exit 1
        naming HX005 and the program."""
        bank_file = hlolint.resolve_bank_file(hlolint.audit_config())
        bank = fp_mod.load_bank(bank_file)
        assert bank is not None
        doctored = copy.deepcopy(bank)
        doctored["programs"]["train_spmd_k1"]["cost"]["flops"] *= 1.5
        fp_mod.save_bank(
            fp_mod.bank_path(str(tmp_path), hlolint.AUDIT_BANK_NAME,
                             bank["platform"]),
            doctored,
        )
        from replication_faster_rcnn_tpu import cli

        monkeypatch.setattr(
            hlolint, "collect_fingerprints", lambda *a, **k: collected
        )
        rc = cli.main(
            ["audit", "--device", "cpu", "--fingerprint-dir", str(tmp_path)]
        )
        out = capsys.readouterr().out
        assert rc == 1
        assert "HX005" in out and "train_spmd_k1" in out

    def test_update_rebanks_deterministically(self, tmp_path, collected):
        bank_file = hlolint.resolve_bank_file(hlolint.audit_config())
        bank = fp_mod.load_bank(bank_file)
        assert bank is not None
        tmp_bank = fp_mod.bank_path(
            str(tmp_path), hlolint.AUDIT_BANK_NAME, bank["platform"]
        )
        fp_mod.save_bank(tmp_bank, bank)

        r1 = hlolint.run_audit(
            fingerprints=collected, update=True, fingerprint_dir=str(tmp_path)
        )
        assert r1.updated and r1.ok, [str(v) for v in r1.violations]
        first = pathlib.Path(tmp_bank).read_bytes()
        r2 = hlolint.run_audit(
            fingerprints=collected, update=True, fingerprint_dir=str(tmp_path)
        )
        assert r2.updated and r2.ok
        assert pathlib.Path(tmp_bank).read_bytes() == first

    def test_seeded_budget_violation(self, collected):
        result = hlolint.run_audit(fingerprints=collected, hbm_budget_bytes=1)
        rules = {v.rule for v in result.violations}
        assert "HX004" in rules


@pytest.mark.slow
class TestAuditGateSlowFeeds:
    """Live lowering of the feeds the fast tier audits only from the
    bank: the cached feed (cache/sel must never alias) and eval (no
    donation, no collectives) — plus the drift check against the
    committed bank for both."""

    def test_cached_and_eval_audited_live(self):
        collected = hlolint.collect_fingerprints(
            hlolint.audit_config(), programs=list(SLOW_PROGRAMS)
        )
        result = hlolint.run_audit(fingerprints=collected)
        assert result.ok, "\n".join(str(v) for v in result.violations)

        cached = collected["train_cached_k1"]
        aliased = {a["parameter"] for a in cached["aliasing"]}
        s0, s1 = cached["params"]["state"]
        assert set(range(s0, s1)) <= aliased
        c0, c1 = cached["params"]["cache"]
        assert not (aliased & set(range(c0, c1)))
        assert cached["collectives"] == {}
        ev = collected["eval_infer"]
        assert ev["aliasing"] == [] and ev["collectives"] == {}
