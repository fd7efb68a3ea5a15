"""Compiled-program fingerprints: what the compiler actually emitted.

jaxlint (analysis/jaxlint.py) reasons about Python source; strict mode
(analysis/strict.py) observes the live process. This module captures the
layer between them — the AOT artifacts: for each registered program
(train/warmup.py::build_program_specs) it extracts, from the LOWERED
StableHLO and the COMPILED executable,

* the abstract arg/output shapes, dtypes and shardings,
* the input/output aliasing map (did ``donate_argnums`` survive?),
* the collective inventory (which psums, at which element types — read
  from the lowered IR, because XLA:CPU legalizes bf16 all-reduces to f32
  in the compiled module and would mask the contract),
* HloCostAnalysis flops/bytes (`lowered_cost_analysis`, the same
  pricing the step-profile harness banks), and
* the executable's memory analysis with a peak-HBM estimate
  (arguments + outputs − aliased + temporaries).

Fingerprints serialize to committed JSON banks under
``analysis/fingerprints/`` (`save_bank` / `load_bank`, atomic replace);
`diff_programs` reports field-level drift between a live fingerprint and
a banked one. The contract rules over these records live in
analysis/hlolint.py (HLO contracts + drift) and analysis/shardlint.py
(sharding & collective-cost, over the committed bank only).

jax is imported lazily: everything except `summarize_abstract`,
`lowered_cost` and `fingerprint_program` is pure text/JSON work, and the
static consumers (shardlint, commcost) reuse the parsers here without
touching a backend.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

SCHEMA = "hlo_fingerprint/v1"

# kinds of StableHLO collective ops inventoried from the lowered IR
COLLECTIVE_KINDS = (
    "all_reduce",
    "all_gather",
    "all_to_all",
    "reduce_scatter",
    "collective_permute",
    "collective_broadcast",
)

# `"stablehlo.all_reduce"(%x) <{...}> ({ region }) : (tensor<10x20xbf16>)
# -> ...` — the result element type follows the region close; DOTALL
# because the reduction region spans lines. reduce_scatter carries the
# same reduction-region syntax; all_gather is region-free, so its operand
# type follows the attribute dict directly.
_ALL_REDUCE_RE = re.compile(
    r'"stablehlo\.all_reduce"\(.*?\}\) : \(tensor<([^>]*)>', re.S
)
_ELEMENT_TYPE_RES = {
    "all_reduce": _ALL_REDUCE_RE,
    "reduce_scatter": re.compile(
        r'"stablehlo\.reduce_scatter"\(.*?\}\) : \(tensor<([^>]*)>', re.S
    ),
    "all_gather": re.compile(
        r'"stablehlo\.all_gather"\([^)]*\)\s*<\{.*?\}>\s*:\s*\(tensor<([^>]*)>',
        re.S,
    ),
}
# compiled-module header: `input_output_alias={ {0}: (0, {}, may-alias),
# {1,2}: (3, {}, must-alias), ... }`
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{[^}]*\},\s*(may-alias|must-alias)\)"
)
# element types are the last 'x'-separated token of a tensor type
# (`tensor<4xf64>`) or the whole body for scalars (`tensor<f64>`)
_F64_RE = re.compile(r"[<x]f64>")
# custom calls print either as the pretty form `stablehlo.custom_call
# @target(...)` or the generic form with an explicit attribute
# `call_target_name = "target"`; the same module never mixes both for
# one op, so counting both patterns cannot double-count
_CUSTOM_CALL_RES = (
    re.compile(r"stablehlo\.custom_call\s+@([\w.$-]+)"),
    re.compile(r'call_target_name\s*=\s*"([^"]+)"'),
)


def parse_custom_calls(stablehlo_text: str) -> Dict[str, int]:
    """{call_target_name: count} over a lowered module's custom calls.

    The ops-backend provenance signal for hlolint's HX007: on TPU the
    pallas kernels lower to ``tpu_custom_call`` (Mosaic) targets, while a
    backend=xla program must contain none of them. Empty dict == no
    custom calls at all."""
    counts: Dict[str, int] = {}
    for pattern in _CUSTOM_CALL_RES:
        for target in pattern.findall(stablehlo_text):
            counts[target] = counts.get(target, 0) + 1
    return dict(sorted(counts.items()))


def parse_int8_ops(stablehlo_text: str) -> Dict[str, int]:
    """{op_kind: count} of dot_general/convolution ops with an int8
    operand in a lowered module.

    The quantization provenance signal for hlolint's HX008: a
    ``serve_*__int8`` program with true-int8 GEMMs must show i8 dots,
    and NO other program may contain any — an i8 contraction outside the
    quantized twins means quantized weights leaked into a program whose
    numerics were never calibrated for them."""
    counts: Dict[str, int] = {}
    for line in stablehlo_text.splitlines():
        if "xi8>" not in line:
            continue
        for kind in ("dot_general", "convolution"):
            if f"stablehlo.{kind}" in line:
                counts[kind] = counts.get(kind, 0) + 1
    return dict(sorted(counts.items()))


def module_hash(stablehlo_text: str) -> str:
    """sha256[:16] of the lowered module text — a whole-program identity
    cheap enough to bank. Interpret-mode pallas twins contain no custom
    call on CPU, so this is the only artifact-level evidence that the
    backend scope actually changed the lowered program (HX007 compares a
    twin's hash against its base's)."""
    return hashlib.sha256(stablehlo_text.encode()).hexdigest()[:16]


def parse_alias_map(compiled_text: str) -> List[Dict[str, Any]]:
    """The input/output aliasing entries of a compiled module's text:
    [{"output": "0", "parameter": 0, "kind": "may-alias"}, ...]. Empty
    when the header is absent (nothing donated, or a backend that prints
    no alias table — absence is indistinguishable from no aliasing, which
    is the conservative reading for the donation contract)."""
    if "input_output_alias" not in compiled_text:
        return []
    # the `{out}: (param, {}, kind)` entry shape (with the literal alias
    # kind) only occurs in the module header's alias table; scanning the
    # pre-ENTRY header avoids bracket-matching the nested braces
    header = compiled_text.split("ENTRY", 1)[0]
    out = []
    for om, pm, kind in _ALIAS_ENTRY_RE.findall(header):
        out.append(
            {
                "output": om.replace(" ", ""),
                "parameter": int(pm),
                "kind": kind,
            }
        )
    return out


def parse_collectives(stablehlo_text: str) -> Dict[str, Any]:
    """Inventory of collective ops in a lowered module's StableHLO text.

    {"all_reduce": {"count": N, "element_types": {"bf16": i, "f32": j}},
     "<other kind>": {"count": M}, ...} — kinds with zero occurrences are
    omitted, so an empty dict means a collective-free program."""
    inv: Dict[str, Any] = {}
    for kind in COLLECTIVE_KINDS:
        n = len(re.findall(rf'"?stablehlo\.{kind}"?\(', stablehlo_text))
        if n:
            inv[kind] = {"count": n}
    for kind, pattern in _ELEMENT_TYPE_RES.items():
        if kind not in inv:
            continue
        types: Dict[str, int] = {}
        for tensor in pattern.findall(stablehlo_text):
            elem = tensor.split("x")[-1]
            types[elem] = types.get(elem, 0) + 1
        inv[kind]["element_types"] = dict(sorted(types.items()))
    return inv


# ------------------------------------------------- partitioned collectives
#
# The lowered StableHLO only shows collectives the *program* wrote
# (shard_map bodies). Auto-partitioned programs (pjit with shardings)
# get theirs inserted by GSPMD/ShardingPropagation *after* lowering, so
# the model-parallel weight all-gathers are only visible in the COMPILED
# module's HLO text. Inventory those separately and classify each op's
# replica groups against the (data, model) mesh axes: with the row-major
# device grid `make_mesh` builds, model-axis groups are consecutive runs
# ({{0,1,2,3},{4,5,6,7}} on a (2,4) mesh) and data-axis groups are
# strided ({{0,4},{1,5},{2,6},{3,7}}).

# `%all-reduce.1 = f32[8]{0} all-reduce(%x), channel_id=1,
#  replica_groups={{0,1},{2,3}}, ...` — opcode after `= <shape>`, so the
# instruction *name* (%all-reduce.1) is not double-counted
_PARTITIONED_OP_RE = re.compile(
    r"=\s+\S+\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[\d,{}\s]*\}\}|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)"
)


def _parse_replica_groups(text: str) -> Optional[List[List[int]]]:
    """Decode one ``replica_groups=`` value into a list of device-id
    groups. Handles the explicit ``{{0,1},{2,3}}`` form and the iota
    form ``[G,S]<=[d0,d1,...]T(perm)`` (reshape iota(prod d) to ``d``,
    transpose by ``perm``, regroup as G rows of S)."""
    text = text.strip()
    if text.startswith("{{"):
        groups = []
        for grp in re.findall(r"\{([\d,\s]*)\}", text[1:-1]):
            ids = [int(t) for t in grp.replace(" ", "").split(",") if t]
            if ids:
                groups.append(ids)
        return groups or None
    m = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?$", text)
    if not m:
        return None
    gshape = [int(t) for t in m.group(1).split(",")]
    dshape = [int(t) for t in m.group(2).split(",")]
    n = 1
    for d in dshape:
        n *= d
    flat = list(range(n))
    # reshape to dshape, apply transpose, flatten (row-major throughout)
    if m.group(3):
        perm = [int(t) for t in m.group(3).split(",")]
        strides = [0] * len(dshape)
        acc = 1
        for i in range(len(dshape) - 1, -1, -1):
            strides[i] = acc
            acc *= dshape[i]
        tshape = [dshape[p] for p in perm]
        tstrides = [strides[p] for p in perm]
        out = []

        def _walk(dim: int, off: int) -> None:
            if dim == len(tshape):
                out.append(off)
                return
            for i in range(tshape[dim]):
                _walk(dim + 1, off + i * tstrides[dim])

        _walk(0, 0)
        flat = out
    if len(gshape) != 2 or gshape[0] * gshape[1] != len(flat):
        return None
    size = gshape[1]
    return [flat[i * size : (i + 1) * size] for i in range(gshape[0])]


def _classify_groups(
    groups: List[List[int]], mesh_shape: Dict[str, int]
) -> str:
    """Which mesh axis a replica-group set spans: 'model' (consecutive
    runs of the minor axis), 'data' (strided over the major axis), 'all'
    (one group of every device), else 'other'. 'world' when the mesh
    shape is unknown/degenerate."""
    n_data = int(mesh_shape.get("data", 0) or 0)
    n_model = int(mesh_shape.get("model", 0) or 0)
    got = {frozenset(g) for g in groups}
    if n_data <= 0 or n_model <= 0:
        return "world"
    n = n_data * n_model
    if got == {frozenset(range(n))}:
        return "all"
    model_axis = {
        frozenset(r * n_model + c for c in range(n_model))
        for r in range(n_data)
    }
    if got == model_axis:
        return "model"
    data_axis = {
        frozenset(r * n_model + c for r in range(n_data))
        for c in range(n_model)
    }
    if got == data_axis:
        return "data"
    return "other"


def parse_partitioned_collectives(
    compiled_text: str, mesh_shape: Optional[Dict[str, int]] = None
) -> Dict[str, Any]:
    """Inventory of collective ops in a COMPILED module's HLO text, with
    per-mesh-axis classification of each op's replica groups:

    {"all-gather": {"count": N, "axes": {"model": i, "data": j}}, ...}

    Kinds with zero occurrences are omitted. ``axes`` buckets: 'model' /
    'data' (one mesh axis each), 'all' (every device in one group),
    'world' (mesh shape unknown), 'other' (anything else)."""
    inv: Dict[str, Any] = {}
    mesh_shape = mesh_shape or {}
    for line in compiled_text.splitlines():
        m = _PARTITIONED_OP_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        entry = inv.setdefault(kind, {"count": 0, "axes": {}})
        entry["count"] += 1
        gm = _REPLICA_GROUPS_RE.search(line)
        groups = _parse_replica_groups(gm.group(1)) if gm else None
        axis = _classify_groups(groups, mesh_shape) if groups else "world"
        entry["axes"][axis] = entry["axes"].get(axis, 0) + 1
    for entry in inv.values():
        entry["axes"] = dict(sorted(entry["axes"].items()))
    return dict(sorted(inv.items()))


def contains_f64(stablehlo_text: str) -> bool:
    """True when any tensor in the lowered IR has element type f64 — the
    silent x64-promotion the dtype contract (HX002) forbids."""
    return _F64_RE.search(stablehlo_text) is not None


def memory_stats(compiled) -> Optional[Dict[str, float]]:
    """The executable's memory analysis as plain floats, plus
    ``peak_bytes_estimate`` = arguments + outputs − aliased + temporaries
    (donated buffers are counted once). None when the backend exposes no
    memory analysis — callers must treat that as "unknown", not "fits"."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    fields = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "alias_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    out = {}
    for f in fields:
        v = getattr(ma, f, None)
        if v is None:
            return None
        out[f] = float(v)
    out["peak_bytes_estimate"] = (
        out["argument_size_in_bytes"]
        + out["output_size_in_bytes"]
        - out["alias_size_in_bytes"]
        + out["temp_size_in_bytes"]
    )
    return out


def summarize_abstract(tree) -> List[Dict[str, Any]]:
    """Flattened [{path, shape, dtype, sharding}] for one abstract
    argument (or output) pytree, in XLA's flat-parameter order."""
    import jax

    leaves = jax.tree_util.tree_leaves_with_path(tree)
    out = []
    for path, leaf in leaves:
        sharding = getattr(leaf, "sharding", None)
        out.append(
            {
                "path": jax.tree_util.keystr(path),
                "shape": list(getattr(leaf, "shape", ())),
                "dtype": str(jax.numpy.dtype(leaf.dtype)),
                "sharding": repr(sharding) if sharding is not None else None,
            }
        )
    return out


def lowered_cost_analysis(lowered):
    """{flops, bytes_accessed} of an already-lowered program, from XLA's
    HloCostAnalysis. Shared by the step-profile harness and the HLO
    auditor (:func:`fingerprint_program`) so both price programs
    identically.

    The CPU client analyses the lowered module without compiling it. The
    TPU client has no pre-compile analysis (`Lowered.cost_analysis()` is
    None there — seen on the v5e, libtpu 0.0.34), so there the program is
    compiled and the executable's own analysis is read."""
    ca = lowered.cost_analysis()
    if ca is None:
        ca = lowered.compile().cost_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }


def lowered_cost(fn, *abstract_args):
    """{flops, bytes_accessed} of ``fn`` from HloCostAnalysis of its
    abstract lowering (see :func:`lowered_cost_analysis`)."""
    import jax

    return lowered_cost_analysis(jax.jit(fn).lower(*abstract_args))


# ------------------------------------------------- sharding repr parsing

# `NamedSharding(mesh=Mesh('data': 2, 'model': 1),
#  spec=PartitionSpec(None, 'data'), memory_kind=unpinned_host)` — the
# repr summarize_abstract banks. PartitionSpec entries may be None, a
# quoted axis name, or a tuple of names (one nesting level).
_MESH_RE = re.compile(r"mesh=Mesh\(([^)]*)\)")
_MESH_AXIS_RE = re.compile(r"'(\w+)':\s*(\d+)")
_SPEC_RE = re.compile(r"spec=PartitionSpec\(((?:[^()]|\([^()]*\))*)\)")


@dataclasses.dataclass(frozen=True)
class ShardingView:
    """A parsed NamedSharding repr: mesh axis sizes + normalized per-dim
    spec (each entry None or a tuple of axis names, trailing Nones
    trimmed)."""

    mesh: Tuple[Tuple[str, int], ...]
    spec: Tuple[Optional[Tuple[str, ...]], ...]

    @property
    def axes_used(self) -> frozenset:
        names: set = set()
        for entry in self.spec:
            if entry:
                names.update(entry)
        return frozenset(names)

    def spec_str(self) -> str:
        if not self.spec:
            return "P()"
        toks = []
        for entry in self.spec:
            if entry is None:
                toks.append("None")
            elif len(entry) == 1:
                toks.append(f"'{entry[0]}'")
            else:
                toks.append("(" + ", ".join(f"'{a}'" for a in entry) + ")")
        return f"P({', '.join(toks)})"


def _parse_spec_body(body: str) -> Tuple[Optional[Tuple[str, ...]], ...]:
    # split on top-level commas only: tuple entries `('a', 'b')` nest one
    # paren level
    parts: List[str] = []
    depth = 0
    token = ""
    for ch in body:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append(token)
            token = ""
        else:
            token += ch
    parts.append(token)
    entries: List[Optional[Tuple[str, ...]]] = []
    for part in parts:
        part = part.strip()
        if not part:
            continue
        if part == "None":
            entries.append(None)
            continue
        names = re.findall(r"'(\w+)'", part)
        if names:
            entries.append(tuple(names))
    while entries and entries[-1] is None:
        entries.pop()
    return tuple(entries)


def parse_sharding(repr_str: Optional[str]) -> Optional[ShardingView]:
    """ShardingView for a banked NamedSharding repr; None for anything
    else (null, SingleDeviceSharding, unparseable) — callers skip those
    leaves rather than guess."""
    if not repr_str or "NamedSharding" not in repr_str:
        return None
    mm = _MESH_RE.search(repr_str)
    sm = _SPEC_RE.search(repr_str)
    if not mm or not sm:
        return None
    mesh = tuple(
        (name, int(size)) for name, size in _MESH_AXIS_RE.findall(mm.group(1))
    )
    return ShardingView(mesh=mesh, spec=_parse_spec_body(sm.group(1)))


def fingerprint_program(spec) -> Dict[str, Any]:
    """AOT-lower and compile one ProgramSpec; return its fingerprint.

    The dtype/collective facts come from the LOWERED StableHLO (the
    program as written — CPU legalization would otherwise rewrite bf16
    collectives out of sight); aliasing and memory from the COMPILED
    executable (the program as it will run); costs from the shared
    HloCostAnalysis helper."""
    import jax

    from replication_faster_rcnn_tpu.analysis import commcost

    jitted, args = spec.build()
    lowered = jitted.lower(*args)
    stablehlo = lowered.as_text()
    compiled = lowered.compile()
    try:
        compiled_text = compiled.as_text()
    except Exception:  # pragma: no cover - some backends hide HLO text
        compiled_text = ""

    sizes = [len(jax.tree_util.tree_leaves(a)) for a in args]
    params: Dict[str, List[int]] = {}
    start = 0
    for role, n in zip(spec.arg_roles, sizes):
        params[role] = [start, start + n]
        start += n

    try:
        out_tree = jax.eval_shape(jitted, *args)
    except Exception:  # pragma: no cover - defensive; specs are jittable
        out_tree = ()

    # the compiled executable's flat output shardings (repr strings), the
    # ground truth shardlint's SL002/SL004 read; None when the backend
    # doesn't expose them
    try:
        out_shardings = [
            repr(s)
            for s in jax.tree_util.tree_leaves(compiled.output_shardings)
        ]
    except Exception:
        out_shardings = None

    return {
        "program": spec.name,
        "feed": spec.feed,
        "k": spec.k,
        "args": {role: summarize_abstract(a) for role, a in zip(spec.arg_roles, args)},
        "params": params,
        "outputs": summarize_abstract(out_tree),
        "aliasing": parse_alias_map(compiled_text),
        "collectives": parse_collectives(stablehlo),
        "partitioned_collectives": parse_partitioned_collectives(
            compiled_text, spec.meta.get("mesh_shape")
        ),
        "comm": commcost.collect_comm(
            stablehlo, compiled_text, spec.meta.get("mesh_shape")
        ),
        "out_shardings": out_shardings,
        "has_f64": contains_f64(stablehlo),
        "custom_calls": parse_custom_calls(stablehlo),
        "int8_ops": parse_int8_ops(stablehlo),
        "module_hash": module_hash(stablehlo),
        "cost": lowered_cost_analysis(lowered),
        "memory": memory_stats(compiled),
        "meta": dict(spec.meta),
    }


# ------------------------------------------------------------------- bank IO


def default_fingerprint_dir() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "fingerprints")


def bank_path(directory: str, name: str, platform: str) -> str:
    return os.path.join(directory, f"{name}_{platform}.json")


def load_bank(path: str) -> Optional[Dict[str, Any]]:
    """The banked fingerprint record, or None when absent/unreadable
    (callers surface that as the HX006 missing-bank violation)."""
    try:
        with open(path) as f:
            bank = json.load(f)
    except (OSError, ValueError):
        return None
    if bank.get("schema") != SCHEMA:
        return None
    return bank


def save_bank(path: str, bank: Dict[str, Any]) -> None:
    """Atomic write (tmp + os.replace) so a killed re-bank can't leave a
    half-written record for the next audit to choke on."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(bank, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def make_bank(
    programs: Dict[str, Dict[str, Any]],
    platform: str,
    n_devices: int,
    config_summary: Dict[str, Any],
) -> Dict[str, Any]:
    return {
        "schema": SCHEMA,
        "platform": platform,
        "n_devices": n_devices,
        "config": config_summary,
        "programs": programs,
    }


# --------------------------------------------------------------------- drift

# relative tolerances per numeric field: costs are deterministic for an
# unchanged program (any real change moves them), memory estimates wobble
# with XLA's buffer assignment across versions
COST_REL_TOL = 0.02
MEMORY_REL_TOL = 0.25

# structural fields compared exactly. `partitioned_collectives` is
# deliberately absent: pre-existing banks predate the field, and the
# post-partitioning inventory wobbles with XLA's SPMD pass pipeline —
# the hlolint HX003 mp cells assert on the live value instead.
# `custom_calls` / `module_hash` are likewise excluded: banks recorded
# before those fields stay valid, and module text wobbles with the jax
# version — the HX007 ops-backend rule asserts on the live values.
# `int8_ops` follows the same pattern: the HX008 quantization-provenance
# rule asserts on the live inventory, so pre-ISSUE-17 bank entries stay
# bitwise valid. `comm` / `out_shardings` (ISSUE 20) are excluded too:
# the SL005 comm-budget arm compares live-vs-banked wire bytes with its
# own tolerance (the partitioned half wobbles with the SPMD pipeline),
# and out_shardings reprs wobble with the jax version — shardlint parses
# the banked values structurally instead of comparing text. `args` holds
# the same reprs leaf by leaf, so `diff_programs` compares each leaf's
# sharding by what it parses to (`_args_as_banked_facts`).
_EXACT_FIELDS = ("args", "params", "outputs", "aliasing", "collectives", "has_f64")


def _rel_delta(cur: float, banked: float) -> float:
    if banked == 0.0:
        return 0.0 if cur == 0.0 else float("inf")
    return abs(cur - banked) / abs(banked)


def _args_as_banked_facts(args):
    """``args`` with each leaf's sharding reduced to (mesh axis sizes,
    spec): how jax prints a mesh's axis types or a memory kind changes
    with its version, what the sharding is does not. A repr that does not
    parse (null, a SingleDeviceSharding) is compared as the text it is."""
    if not isinstance(args, dict):
        return args

    def fact(leaf):
        text = leaf.get("sharding")
        return {**leaf, "sharding": parse_sharding(text) or text}

    return {role: [fact(leaf) for leaf in leaves] for role, leaves in args.items()}


def diff_programs(
    current: Dict[str, Any],
    banked: Dict[str, Any],
    cost_tol: float = COST_REL_TOL,
    memory_tol: float = MEMORY_REL_TOL,
) -> List[str]:
    """Field-level drift between one program's live fingerprint and its
    banked record: [] when they agree, else human-readable mismatches."""
    out: List[str] = []
    for field in _EXACT_FIELDS:
        cur, bank = current.get(field), banked.get(field)
        if field == "args":
            cur, bank = _args_as_banked_facts(cur), _args_as_banked_facts(bank)
        if cur != bank:
            out.append(f"{field} changed vs bank")
    for key in ("flops", "bytes_accessed"):
        cur = float(current.get("cost", {}).get(key, 0.0))
        bank = float(banked.get("cost", {}).get(key, 0.0))
        d = _rel_delta(cur, bank)
        if d > cost_tol:
            out.append(
                f"cost.{key} drifted {d:+.1%} (now {cur:.4g}, banked "
                f"{bank:.4g}, tol {cost_tol:.0%})"
            )
    cur_mem, bank_mem = current.get("memory"), banked.get("memory")
    if (cur_mem is None) != (bank_mem is None):
        out.append("memory analysis availability changed vs bank")
    elif cur_mem is not None:
        d = _rel_delta(
            float(cur_mem.get("peak_bytes_estimate", 0.0)),
            float(bank_mem.get("peak_bytes_estimate", 0.0)),
        )
        if d > memory_tol:
            out.append(
                f"memory.peak_bytes_estimate drifted {d:+.1%} "
                f"(tol {memory_tol:.0%})"
            )
    return out
