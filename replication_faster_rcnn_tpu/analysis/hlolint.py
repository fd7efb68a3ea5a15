"""HLO program auditor: contract rules over compiled-program fingerprints.

The third static gate. jaxlint (`frcnn check`) proves jit hygiene at the
Python-AST level and strict mode (`--strict`) polices the live process;
this auditor asserts what the COMPILER emitted for every registered
(feed × K) program of the step (train/warmup.py::build_program_specs)
before anything runs:

HX001  donation survives lowering as input/output aliasing for the state
       arg — and NEVER for the device cache / batch / eval inputs
       (train/train_step.py::make_cached_train_step's "cache must NOT be
       donated" contract, checked in the artifact).
HX002  dtype contracts: no silent f32→f64 promotion anywhere; the
       gradient all-reduce element type matches
       ``train.grad_allreduce_dtype`` (bf16 config ⇒ one bf16
       all_reduce per float grad leaf; f32 config ⇒ zero bf16).
HX003  collective inventory matches the backend: the shard_map feed
       carries hand-placed psums (all_reduce only); loader/cached/eval
       and the model-parallel (mp/mp_zero) programs lower collective-free
       IR (GSPMD inserts collectives after partitioning, never in the
       lowered module) — and on the COMPILED side, mp programs must show
       model-axis collectives (the GSPMD weight exchange) while every
       other feed must show none on the model axis.
HX004  compiled peak-memory estimate within ``analysis.hbm_budget_bytes``.
HX005  per-program drift vs the banked fingerprint: structural fields
       (shapes, shardings, aliasing, collectives) exactly, flops/bytes
       and memory within tolerance.
HX006  program set = expected bucket count: the bank covers exactly the
       registry's programs on this platform (recompile/bucket drift
       caught before runtime, complementing analysis/strict.py).
HX007  ops-backend provenance: a backend=xla program must contain NO
       pallas custom-call targets (tpu_custom_call / mosaic / triton);
       a backend=pallas program on a real TPU must contain at least one;
       off-TPU (interpret mode lowers pallas to plain StableHLO, so no
       custom call exists to witness) the twin's ``module_hash`` must
       differ from its base's — the backend scope demonstrably changed
       the lowered program.
HX008  quantization provenance: a ``serve_*__int8`` program whose plan
       keeps the head dense layers int8 must lower true-int8
       contractions (``stablehlo.dot_general`` over i8 operands), and NO
       other program may contain an i8 dot/conv — quantized weights in
       an uncalibrated program would be a silent numerics break.

SL005  (shardlint's comm-budget rule, live arm) the static collective
       wire-byte estimate (analysis/commcost.py) of a live program must
       stay within ``analysis.comm_budget_bytes`` AND within
       ``COMM_REL_TOL`` of its banked value — accidental collective
       growth fails the audit naming rule + program. The bank-only arm
       (and SL001-SL004/SL006) runs in `frcnn check` via
       analysis/shardlint.py.

`frcnn audit` drives this (``--json``, ``--update`` to re-bank, nonzero
exit on any violation); tests/test_hlolint.py gates a CPU subset in
tier 1 against the committed bank under ``analysis/fingerprints/``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

from replication_faster_rcnn_tpu.analysis import fingerprint as fp_mod
from replication_faster_rcnn_tpu.config import FasterRCNNConfig

HLO_RULES: Dict[str, str] = {
    "HX001": "donation lost or leaked: state arg must alias, cache/batch/eval must not",
    "HX002": "dtype contract: f64 in lowered IR, or all-reduce type != grad_allreduce_dtype",
    "HX003": "collective inventory does not match the backend's expectation",
    "HX004": "compiled peak-memory estimate exceeds the HBM budget",
    "HX005": "fingerprint drift vs the banked record",
    "HX006": "program set does not match the expected bucket count / bank missing",
    "HX007": "ops-backend provenance: pallas custom-calls in an xla program, or a pallas twin indistinguishable from its base",
    "HX008": "quantization provenance: int8 dot/conv missing from a quantized program, or present anywhere else",
}

# shardlint rules the audit enforces live (the rest are bank-static and
# run under `frcnn check`); merged into the audit's JSON rules payload
AUDIT_SHARD_RULES: Dict[str, str] = {
    "SL005": (
        "static collective wire bytes exceed analysis.comm_budget_bytes "
        "or drifted beyond tolerance vs the banked record"
    ),
}

# relative tolerance for live-vs-banked comm wire bytes: the partitioned
# half of the estimate wobbles with XLA's SPMD pass pipeline across
# versions, but a real collective regression moves the total far more
COMM_REL_TOL = 0.10

# custom-call targets that witness a pallas lowering (Mosaic on TPU,
# Triton on GPU) — matched as substrings of the call_target_name
PALLAS_CALL_MARKERS = ("tpu_custom_call", "mosaic", "triton")

# the audited program matrix: every feed the Trainer can run, single-step
# and fused — including the ZeRO-1 variant of the shard_map backend and
# its LAMB chain (sharded trust ratio), and the model-parallel auto-
# partitioned feeds on the audit (dp, mp) mesh — plus eval (15 programs)
# and the serving engine's bucket matrix (audit_config's 2 resolutions ×
# 2 batch sizes = 4 more) — plus the three ops.backend=pallas twins
# (train/warmup.py::pallas_twin_base_names: loader k=1, eval, one
# serving bucket), plus the multi-scale TRAIN bucket programs —
# EVERY train feed buckets (the shard_map/mp in/out specs shard batch
# dims only, so they are resolution-independent): audit_config's 2
# train_resolutions × all 7 feeds × both Ks = 28 more — plus the
# quantized serving twins (4 ``serve_*__int8`` bucket programs + 1 int8
# pallas twin), 55 programs total
AUDIT_FEEDS = ("loader", "cached", "spmd", "zero", "zero_lamb", "mp", "mp_zero")
AUDIT_KS = (1, 2)
AUDIT_BANK_NAME = "ci"
AUDIT_CACHE_N = 4


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    program: str
    message: str

    def to_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return f"{self.rule} [{self.program}] {self.message}"


@dataclasses.dataclass
class AuditResult:
    violations: List[Violation]
    programs: Dict[str, Dict[str, Any]]
    bank_file: str
    updated: bool = False
    # per-program comm-byte section: {program: {wire_bytes_per_device,
    # basis, banked_wire_bytes_per_device}} — the SL005 evidence
    comm: Dict[str, Dict[str, Any]] = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> Dict[str, Any]:
        return {
            "rules": {**HLO_RULES, **AUDIT_SHARD_RULES},
            "violations": [v.to_dict() for v in self.violations],
            "programs": self.programs,
            "bank_file": self.bank_file,
            "updated": self.updated,
            "comm": self.comm,
            "ok": self.ok,
        }


def audit_config() -> FasterRCNNConfig:
    """The audited config: the fast-tier 64×64 synthetic shape family
    (same trims as benchmarks/step_profile.py::tiny_config) on a 2-way
    data mesh with the bf16 gradient all-reduce ON — small enough to
    compile everywhere, wide enough that every contract (psums, bf16
    collectives, donation under out_shardings) is exercised for real."""
    from replication_faster_rcnn_tpu.config import (
        DataConfig,
        FasterRCNNConfig,
        MeshConfig,
        ModelConfig,
        ProposalConfig,
        ROITargetConfig,
        ServingConfig,
        TrainConfig,
    )

    return FasterRCNNConfig(
        model=ModelConfig(
            backbone="resnet18", roi_op="align", compute_dtype="float32"
        ),
        data=DataConfig(
            dataset="synthetic",
            image_size=(64, 64),
            max_boxes=8,
            # multi-scale train buckets: a downsample bucket plus the
            # identity bucket, so both the resample path and the no-op
            # path are audited and banked per (feed x K)
            train_resolutions=((32, 32), (64, 64)),
        ),
        train=TrainConfig(
            batch_size=2,
            n_epoch=4,
            grad_allreduce_dtype="bfloat16",
        ),
        mesh=MeshConfig(num_data=2),
        proposals=ProposalConfig(pre_nms_train=128, post_nms_train=32),
        roi_targets=ROITargetConfig(n_sample=8),
        # pinned (not derived) buckets so the audited serving matrix can't
        # shift under an image_size change without an explicit re-bank;
        # bf16 resident params = the serving default, exercised for real
        serving=ServingConfig(
            resolutions=((32, 32), (64, 64)),
            batch_sizes=(1, 2),
            params_dtype="bfloat16",
        ),
    )


def expected_program_names(
    feeds: Sequence[str] = AUDIT_FEEDS,
    ks: Sequence[int] = AUDIT_KS,
    include_eval: bool = True,
    config: Optional[FasterRCNNConfig] = None,
) -> List[str]:
    """The audited program set; with ``config`` the serving engine's
    bucket programs (serving.resolutions × batch_sizes), the multi-scale
    TRAIN bucket programs (data.train_resolutions × every feed × ks)
    and the ops.backend=pallas twin programs are included."""
    from replication_faster_rcnn_tpu.train.warmup import (
        LM_PROGRAM,
        bucket_train_program_names,
        int8_program_names,
        pallas_program_name,
        pallas_twin_base_names,
        program_name,
        serving_program_names,
    )

    names = [program_name(f, k) for f in feeds for k in ks]
    if include_eval:
        names.append("eval_infer")
    if config is not None:
        names.extend(serving_program_names(config))
        names.extend(bucket_train_program_names(config, feeds=feeds, ks=ks))
        names.extend(
            pallas_program_name(b) for b in pallas_twin_base_names(config)
        )
        names.extend(int8_program_names(config))
        names.append(LM_PROGRAM)
    return names


def collect_fingerprints(
    config: FasterRCNNConfig,
    programs: Optional[Sequence[str]] = None,
    cache_n: int = AUDIT_CACHE_N,
) -> Dict[str, Dict[str, Any]]:
    """Lower + compile the requested programs (default: the full matrix)
    and fingerprint each. This is the expensive arm — tens of seconds per
    program on CPU; the contract/drift rules below are pure functions
    over the returned dicts."""
    from replication_faster_rcnn_tpu.train.warmup import (
        build_int8_program_specs,
        build_lm_program_specs,
        build_pallas_program_specs,
        build_program_specs,
        build_serving_specs,
    )

    specs = build_program_specs(
        config, feeds=AUDIT_FEEDS, ks=AUDIT_KS, include_eval=True, cache_n=cache_n
    )
    specs = {
        **specs,
        **build_serving_specs(config),
        **build_pallas_program_specs(config),
        **build_int8_program_specs(config),
        **build_lm_program_specs(),
    }
    if programs is None:
        wanted = list(specs)
    else:
        unknown = set(programs) - set(specs)
        if unknown:
            raise ValueError(
                f"unknown programs {sorted(unknown)}; registry has {sorted(specs)}"
            )
        wanted = list(programs)
    return {name: fp_mod.fingerprint_program(specs[name]) for name in wanted}


# ------------------------------------------------------------ contract rules


def check_contracts(
    fingerprints: Dict[str, Dict[str, Any]],
    config: FasterRCNNConfig,
    hbm_budget_bytes: int,
) -> List[Violation]:
    """HX001–HX004 over live fingerprints (pure; no lowering here)."""
    out: List[Violation] = []
    want_dt = config.train.grad_allreduce_dtype
    for name, fp in sorted(fingerprints.items()):
        params: Dict[str, List[int]] = fp.get("params", {})
        aliased = {a["parameter"] for a in fp.get("aliasing", [])}

        # HX001 — donation as aliasing (serving programs share eval's
        # contract: pure inference, nothing may be donated/clobbered —
        # the engine's resident params survive every dispatch)
        if fp.get("feed") in ("eval", "serve"):
            if aliased:
                out.append(
                    Violation(
                        "HX001",
                        name,
                        f"{fp.get('feed')} program aliases params "
                        f"{sorted(aliased)[:8]} but nothing is donated to it",
                    )
                )
        elif "state" in params:
            s0, s1 = params["state"]
            missing = sorted(set(range(s0, s1)) - aliased)
            if missing:
                out.append(
                    Violation(
                        "HX001",
                        name,
                        f"donated state arg lost input/output aliasing for "
                        f"{len(missing)}/{s1 - s0} leaves (first params "
                        f"{missing[:8]}) — donation did not survive lowering",
                    )
                )
            for role, (r0, r1) in sorted(params.items()):
                if role == "state":
                    continue
                leaked = sorted(aliased & set(range(r0, r1)))
                if leaked:
                    out.append(
                        Violation(
                            "HX001",
                            name,
                            f"non-donated arg `{role}` is aliased (params "
                            f"{leaked[:8]}) — its buffer would be clobbered "
                            "by the dispatch",
                        )
                    )

        # HX002 — dtype contracts
        if fp.get("has_f64"):
            out.append(
                Violation(
                    "HX002",
                    name,
                    "f64 tensors in the lowered IR — silent x64 promotion "
                    "on a program that must stay f32/bf16",
                )
            )
        collectives = fp.get("collectives", {})
        ar = collectives.get("all_reduce")
        if fp.get("feed") in ("spmd", "zero", "zero_lamb"):
            # the gradient exchange: plain psum all_reduces on the
            # replicated backend, psum_scatter reduce_scatters under
            # ZeRO-1 — either way one bf16 collective per float grad leaf
            types: Dict[str, int] = {}
            for kind in ("all_reduce", "reduce_scatter"):
                for elem, n in (
                    collectives.get(kind, {}).get("element_types", {}).items()
                ):
                    types[elem] = types.get(elem, 0) + n
            n_bf16 = types.get("bf16", 0)
            n_grad = int(fp.get("meta", {}).get("n_float_grad_leaves", 1))
            if want_dt == "bfloat16" and n_bf16 < n_grad:
                out.append(
                    Violation(
                        "HX002",
                        name,
                        "grad-exchange element type: expected >= "
                        f"{n_grad} bf16 all_reduce/reduce_scatter ops (one "
                        f"per float grad leaf) under "
                        f"grad_allreduce_dtype=bfloat16, found "
                        f"{n_bf16} (types: {types or 'none'})",
                    )
                )
            elif want_dt == "float32" and n_bf16:
                out.append(
                    Violation(
                        "HX002",
                        name,
                        f"{n_bf16} bf16 grad-exchange collectives under "
                        "grad_allreduce_dtype=float32 — the gradient "
                        "exchange silently lost precision",
                    )
                )

        # HX003 — collective inventory per backend
        if fp.get("feed") == "spmd":
            if not ar or not ar.get("count"):
                out.append(
                    Violation(
                        "HX003",
                        name,
                        "no all_reduce in the lowered IR — the hand-placed "
                        "psums of parallel/spmd.py are gone",
                    )
                )
            other = sorted(set(collectives) - {"all_reduce"})
            if other:
                out.append(
                    Violation(
                        "HX003",
                        name,
                        f"unexpected collective kinds {other} — the "
                        "replicated shard_map backend emits psum "
                        "all_reduces only",
                    )
                )
        elif fp.get("feed") in ("zero", "zero_lamb"):
            # zero_lamb shares the inventory: LAMB's sharded trust-ratio
            # norm psums lower as additional all_reduce ops, a kind
            # already required here (their count is pinned by HX005)
            required = {"all_reduce", "reduce_scatter", "all_gather"}
            missing = sorted(required - set(collectives))
            if missing:
                out.append(
                    Violation(
                        "HX003",
                        name,
                        f"missing collective kinds {missing} — ZeRO-1 "
                        "needs reduce_scatter (grad exchange), all_gather "
                        "(param reassembly) and all_reduce (metrics/health "
                        "psums); the hand-placed collectives of "
                        "parallel/spmd.py are gone",
                    )
                )
            other = sorted(set(collectives) - required)
            if other:
                out.append(
                    Violation(
                        "HX003",
                        name,
                        f"unexpected collective kinds {other} — the ZeRO-1 "
                        "shard_map backend emits all_reduce, "
                        "reduce_scatter and all_gather only",
                    )
                )
        elif collectives:
            out.append(
                Violation(
                    "HX003",
                    name,
                    f"collectives {sorted(collectives)} in a "
                    f"{fp.get('feed')} program — the jit backend lowers "
                    "collective-free IR (GSPMD inserts collectives after "
                    "partitioning, not here)",
                )
            )

        # HX003 — model-axis partitioned collectives: the mp feeds' weight
        # exchange is GSPMD-inserted, so it only shows in the COMPILED
        # module's inventory (`partitioned_collectives`, classified per
        # mesh axis). mp programs must carry it; every other feed must
        # lower ZERO model-axis collectives. `.get` throughout: records
        # banked before the field existed simply skip this rule.
        pcoll = fp.get("partitioned_collectives")
        if pcoll is not None:
            model_ops = {
                kind: entry.get("axes", {}).get("model", 0)
                for kind, entry in pcoll.items()
                if entry.get("axes", {}).get("model", 0)
            }
            if fp.get("feed") in ("mp", "mp_zero"):
                if not model_ops:
                    out.append(
                        Violation(
                            "HX003",
                            name,
                            "no model-axis collectives in the compiled "
                            "module — GSPMD emitted no weight exchange, so "
                            "the 1/mp parameter sharding was optimized away "
                            f"(partitioned inventory: {sorted(pcoll) or 'empty'})",
                        )
                    )
            elif model_ops:
                out.append(
                    Violation(
                        "HX003",
                        name,
                        f"model-axis collectives {model_ops} in a "
                        f"{fp.get('feed')} program — only the mp feeds "
                        "shard over the model axis",
                    )
                )

        # HX007 — ops-backend provenance. Applied only to records that
        # carry the `custom_calls` field (live fingerprints and post-
        # ISSUE-13 banks; older banked records simply skip the rule).
        cc = fp.get("custom_calls")
        if cc is not None:
            pallas_cc = {
                t: n
                for t, n in cc.items()
                if any(m in t.lower() for m in PALLAS_CALL_MARKERS)
            }
            meta = fp.get("meta", {})
            if meta.get("ops_backend", "xla") != "pallas":
                if pallas_cc:
                    out.append(
                        Violation(
                            "HX007",
                            name,
                            f"pallas custom-calls {pallas_cc} in a "
                            "backend=xla program — the ops dispatch leaked "
                            "a pallas kernel into the default lowering",
                        )
                    )
            elif not meta.get("pallas_interpret"):
                if not pallas_cc:
                    out.append(
                        Violation(
                            "HX007",
                            name,
                            "no pallas custom-call in a backend=pallas "
                            "program compiled for a real accelerator — the "
                            "backend scope did not reach the lowering "
                            f"(custom calls: {sorted(cc) or 'none'})",
                        )
                    )
            else:
                # interpret mode: no custom call exists to witness the
                # backend, so require the twin's module to differ from
                # its base's (skipped when the base wasn't collected in
                # this audit — e.g. an explicit --programs subset)
                base = fingerprints.get(meta.get("twin", ""))
                if (
                    base is not None
                    and fp.get("module_hash")
                    and fp.get("module_hash") == base.get("module_hash")
                ):
                    out.append(
                        Violation(
                            "HX007",
                            name,
                            "interpret-mode pallas twin lowered a module "
                            f"byte-identical to its base {meta.get('twin')!r} "
                            "— the backend scope changed nothing",
                        )
                    )

        # HX008 — quantization provenance. Like HX007, applied only to
        # records carrying the `int8_ops` field (live fingerprints and
        # post-ISSUE-17 banks; older banked records skip the rule).
        int8_ops = fp.get("int8_ops")
        if int8_ops is not None:
            meta = fp.get("meta", {})
            n_int8 = sum(int8_ops.values())
            if meta.get("params_dtype") == "int8" and meta.get("int8_dense"):
                if not n_int8:
                    out.append(
                        Violation(
                            "HX008",
                            name,
                            "no int8 dot_general/convolution in a quantized "
                            "program whose plan keeps the head dense layers "
                            "int8 — the QuantDense GEMMs were dequantized "
                            "away before the contraction",
                        )
                    )
            elif n_int8:
                out.append(
                    Violation(
                        "HX008",
                        name,
                        f"int8 contraction ops {int8_ops} in a "
                        f"params_dtype={meta.get('params_dtype', 'float32')!r} "
                        "program — quantized weights leaked outside the "
                        "serve_*__int8 twins",
                    )
                )

        # HX004 — memory budget
        mem = fp.get("memory")
        if mem is not None:
            peak = float(mem.get("peak_bytes_estimate", 0.0))
            if peak > hbm_budget_bytes:
                out.append(
                    Violation(
                        "HX004",
                        name,
                        f"peak-memory estimate {peak / 2**30:.2f} GiB "
                        f"exceeds analysis.hbm_budget_bytes "
                        f"({hbm_budget_bytes / 2**30:.2f} GiB)",
                    )
                )
    return out


def check_drift(
    fingerprints: Dict[str, Dict[str, Any]],
    bank: Optional[Dict[str, Any]],
    bank_file: str,
    expected: Sequence[str],
    platform: str,
    n_devices: int,
) -> List[Violation]:
    """HX005 (per-program drift) + HX006 (bank presence / program set)."""
    out: List[Violation] = []
    if bank is None:
        out.append(
            Violation(
                "HX006",
                "<bank>",
                f"no banked fingerprints at {bank_file} — run "
                "`frcnn audit --update` to bank the current programs",
            )
        )
        return out
    if bank.get("platform") != platform or bank.get("n_devices") != n_devices:
        out.append(
            Violation(
                "HX006",
                "<bank>",
                f"bank was recorded on {bank.get('platform')}/"
                f"{bank.get('n_devices')} devices but this audit runs on "
                f"{platform}/{n_devices} — fingerprints do not transfer "
                "across topologies; re-bank per platform",
            )
        )
        return out
    banked = bank.get("programs", {})
    missing = sorted(set(expected) - set(banked))
    extra = sorted(set(banked) - set(expected))
    if missing:
        out.append(
            Violation(
                "HX006",
                "<bank>",
                f"bank is missing programs {missing} of the expected "
                f"{len(expected)}-program matrix — run `frcnn audit --update`",
            )
        )
    if extra:
        out.append(
            Violation(
                "HX006",
                "<bank>",
                f"bank has unexpected programs {extra} — stale bucket "
                "(recompile drift) or a renamed program; re-bank",
            )
        )
    for name, fp in sorted(fingerprints.items()):
        if name not in banked:
            continue  # HX006 above already owns set mismatches
        for msg in fp_mod.diff_programs(fp, banked[name]):
            out.append(Violation("HX005", name, msg))
    return out


def check_comm(
    fingerprints: Dict[str, Dict[str, Any]],
    bank: Optional[Dict[str, Any]],
    comm_budget_bytes: int,
    comm_tol: float = COMM_REL_TOL,
):
    """SL005's live arm: every program's statically-priced collective
    wire bytes must fit the absolute budget, and (when a banked comm
    record exists — pass bank=None while re-banking) stay within
    ``comm_tol`` of the bank. Returns (violations, per-program comm
    summary). Records without a `comm` field (legacy banks passed in as
    pre-collected fingerprints) skip the rule, mirroring HX007/HX008."""
    banked_programs = (bank or {}).get("programs", {})
    out: List[Violation] = []
    summary: Dict[str, Dict[str, Any]] = {}
    for name, fp in sorted(fingerprints.items()):
        comm = fp.get("comm")
        if comm is None:
            continue
        wire = int(comm.get("wire_bytes_per_device", 0) or 0)
        bcomm = (banked_programs.get(name) or {}).get("comm") or {}
        banked_wire = bcomm.get("wire_bytes_per_device")
        summary[name] = {
            "wire_bytes_per_device": wire,
            "basis": comm.get("basis", "none"),
            "banked_wire_bytes_per_device": banked_wire,
        }
        if wire > comm_budget_bytes:
            out.append(
                Violation(
                    "SL005",
                    name,
                    f"static collective cost {wire / 2**20:.1f} MiB/device/"
                    "step exceeds analysis.comm_budget_bytes "
                    f"({comm_budget_bytes / 2**20:.1f} MiB)",
                )
            )
        if banked_wire is not None:
            d = fp_mod._rel_delta(float(wire), float(banked_wire))
            if d > comm_tol:
                out.append(
                    Violation(
                        "SL005",
                        name,
                        f"collective wire bytes drifted {d:+.1%} vs bank "
                        f"(now {wire}, banked {int(banked_wire)}, tol "
                        f"{comm_tol:.0%}) — the collective volume per "
                        "step changed; re-bank if intended",
                    )
                )
    return out, summary


# -------------------------------------------------------------------- driver


def resolve_bank_file(
    config: FasterRCNNConfig,
    fingerprint_dir: Optional[str] = None,
    bank_name: str = AUDIT_BANK_NAME,
) -> str:
    import jax

    directory = (
        fingerprint_dir
        or config.analysis.fingerprint_dir
        or fp_mod.default_fingerprint_dir()
    )
    return fp_mod.bank_path(directory, bank_name, jax.default_backend())


def run_audit(
    config: Optional[FasterRCNNConfig] = None,
    programs: Optional[Sequence[str]] = None,
    update: bool = False,
    fingerprint_dir: Optional[str] = None,
    hbm_budget_bytes: Optional[int] = None,
    fingerprints: Optional[Dict[str, Dict[str, Any]]] = None,
    bank_name: str = AUDIT_BANK_NAME,
    cache_n: int = AUDIT_CACHE_N,
) -> AuditResult:
    """The audit gate: collect (or accept pre-collected) fingerprints,
    enforce HX001–HX004 contracts, then either re-bank (``update``) or
    check HX005/HX006 drift against the committed bank. Violations in the
    result ⇒ the CLI exits nonzero."""
    import jax

    if config is None:
        config = audit_config()
    expected = expected_program_names(config=config)
    if fingerprints is None:
        fingerprints = collect_fingerprints(config, programs, cache_n=cache_n)
    budget = (
        hbm_budget_bytes
        if hbm_budget_bytes is not None
        else config.analysis.hbm_budget_bytes
    )
    violations = check_contracts(fingerprints, config, budget)
    bank_file = resolve_bank_file(config, fingerprint_dir, bank_name)
    platform = jax.default_backend()
    n_devices = len(jax.devices())
    bank = fp_mod.load_bank(bank_file)
    bank_matches = (
        bank is not None
        and bank.get("platform") == platform
        and bank.get("n_devices") == n_devices
    )
    # SL005 live arm: absolute budget always; drift vs bank only when a
    # matching bank exists and we are not about to overwrite it
    comm_violations, comm_summary = check_comm(
        fingerprints,
        bank if (bank_matches and not update) else None,
        config.analysis.comm_budget_bytes,
    )
    violations.extend(comm_violations)
    updated = False
    if update:
        banked_programs: Dict[str, Any] = {}
        if bank_matches:
            banked_programs = dict(bank.get("programs", {}))
        banked_programs.update(fingerprints)
        fp_mod.save_bank(
            bank_file,
            fp_mod.make_bank(
                banked_programs,
                platform,
                n_devices,
                config_summary={
                    "image_size": list(config.data.image_size),
                    "batch_size": config.train.batch_size,
                    "grad_allreduce_dtype": config.train.grad_allreduce_dtype,
                    "backbone": config.model.backbone,
                    "num_data": config.mesh.num_data,
                    "cache_n": cache_n,
                },
            ),
        )
        updated = True
        missing = sorted(set(expected) - set(banked_programs))
        if missing:
            violations.append(
                Violation(
                    "HX006",
                    "<bank>",
                    f"re-banked {len(fingerprints)} programs but the bank "
                    f"still misses {missing} — run `frcnn audit --update` "
                    "without --programs to bank the full matrix",
                )
            )
    else:
        violations.extend(
            check_drift(
                fingerprints, bank, bank_file, expected, platform, n_devices
            )
        )
    return AuditResult(
        violations=violations,
        programs=fingerprints,
        bank_file=bank_file,
        updated=updated,
        comm=comm_summary,
    )
