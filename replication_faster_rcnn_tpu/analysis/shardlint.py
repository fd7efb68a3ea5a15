"""shardlint — static sharding & collective-cost analyzer (fifth gate).

jaxlint reads Python source, threadlint the host concurrency, obslint the
metrics surfaces, hlolint the live AOT artifacts. This gate reads the
COMMITTED fingerprint bank (``analysis/fingerprints/*.json``, written by
`frcnn audit --update`): every banked program carries its abstract arg
shardings, input/output aliasing, collective inventories and the
commcost wire-byte estimate, which is exactly the placement story the
Plan layer promised — so placement regressions are lintable from JSON,
with no jax lowering, on every ``frcnn check``.

Rules (findings name rule + program; `func` IS the program name, so the
shared ``baseline.toml`` waivers address programs, with fnmatch globs —
``func = "train_mp_k*"`` waives a family):

  SL001  a large arg buffer (>= analysis.replicated_bytes_threshold)
         replicated over a >1 MODEL axis although `zero.shard_dim` finds
         a divisible dim — HBM burned on copies the mp layout already
         knows how to split. (The data axis is exempt: replicating
         params over dp IS data parallelism.)
  SL002  sharding disagreement for the same logical state tree — across
         programs of one feed (k1 vs k2, resolution buckets), or between
         a program's own state in_specs and its compiled out_shardings:
         either way a hidden reshard on the train->checkpoint->serve
         chain.
  SL003  mesh-axis misuse: collectives in a program whose mesh has no >1
         axis, a partitioned collective classified onto a mesh axis of
         size <= 1, or a declared >1 axis that no in_spec shards and no
         collective spans (the mesh is a lie — shrink it or use it).
  SL004  a donated (aliased) input whose sharding differs from its
         aliased output's — XLA inserts a copy instead of aliasing, so
         the donation (HX001 checks its *existence*) buys nothing.
  SL005  collective wire bytes per device per step, statically priced by
         analysis/commcost.py over the banked inventory, exceed
         analysis.comm_budget_bytes — or the banked total no longer
         matches its own per-kind tallies (hand-edited bank). The live
         drift arm of this rule runs in `frcnn audit` (hlolint).
  SL006  ZeRO layout fallback: on a shard_opt_state feed an optimizer
         leaf deviates from `zero.compose_spec` — most importantly a
         leaf silently left replicated although `shard_dim` finds a
         divisible dim.

The ZeRO layout rule is recomputed here from a pure reimplementation of
`parallel/zero.py::shard_dim` / `compose_spec` (tested for parity) so
linting stays import-light; feed intent comes from
`parallel/plan.py::FEED_STATE_INTENT` / `ZERO_INTENT_FEEDS` — the same
declarative table the Plan decision cells document.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from replication_faster_rcnn_tpu.analysis import commcost
from replication_faster_rcnn_tpu.analysis import fingerprint as _fp
from replication_faster_rcnn_tpu.analysis.fingerprint import parse_sharding
from replication_faster_rcnn_tpu.analysis.jaxlint import (
    Baseline,
    Finding,
    Waiver,
    default_baseline_path,
    load_baseline,
    package_root,
)
from replication_faster_rcnn_tpu.config import AnalysisConfig
from replication_faster_rcnn_tpu.parallel.plan import ZERO_INTENT_FEEDS

RULES: Dict[str, str] = {
    "SL001": (
        "large buffer replicated over a >1 model axis despite a "
        "shardable dim (route it through zero.param_shardings)"
    ),
    "SL002": (
        "sharding mismatch for the same logical state tree across "
        "programs or between in_specs and out_shardings (hidden reshard)"
    ),
    "SL003": (
        "mesh-axis misuse: collective over a degenerate axis, or a "
        "declared >1 axis nothing shards over"
    ),
    "SL004": (
        "donated arg sharding differs from its aliased output's "
        "(XLA copies instead of aliasing)"
    ),
    "SL005": (
        "static collective wire bytes exceed analysis.comm_budget_bytes "
        "(or banked comm record is self-inconsistent)"
    ),
    "SL006": (
        "optimizer leaf deviates from the zero.compose_spec layout on a "
        "shard_opt_state feed (silent replicated fallback)"
    ),
}

MODEL_AXIS = "model"
DATA_AXIS = "data"

# replica-group buckets that span (or may span) every mesh axis — they
# count as "using" any axis for SL003's dead-axis check. On a (2,1) mesh
# the data-axis groups ARE all devices, so 'all' is the common bucket.
_WHOLE_MESH_AXES = ("all", "world", "other")

# relative slack for SL005's banked-total-vs-tallies self-consistency
_COMM_CONSISTENCY_TOL = 0.01


# --------------------------------------------------- pure zero.py layout

def shard_dim(shape: Sequence[int], n: int) -> int:
    """Pure reimplementation of `parallel.zero.shard_dim` (parity-tested
    in tests/test_shardlint.py): the largest dim divisible by ``n``, or
    -1 when the leaf must stay replicated."""
    if n <= 1 or not shape:
        return -1
    divisible = [d for d, s in enumerate(shape) if s % n == 0 and s >= n]
    if not divisible:
        return -1
    return max(divisible, key=lambda d: shape[d])


def compose_spec_dims(
    shape: Sequence[int],
    n_data: int,
    n_model: int,
    data_axis: str = DATA_AXIS,
    model_axis: str = MODEL_AXIS,
) -> Tuple[Optional[str], ...]:
    """Pure `parallel.zero.compose_spec`, as a per-dim tuple with
    trailing Nones trimmed (the normalized form specs compare in)."""
    mp_d = shard_dim(shape, n_model)
    spec: List[Optional[str]] = [None] * len(shape)
    if mp_d >= 0:
        spec[mp_d] = model_axis
    if n_data > 1:
        cands = [
            d
            for d, s in enumerate(shape)
            if d != mp_d and s % n_data == 0 and s >= n_data
        ]
        if cands:
            spec[max(cands, key=lambda d: shape[d])] = data_axis
    while spec and spec[-1] is None:
        spec.pop()
    return tuple(spec)


# --------------------------------------------------------- program views

_NP_DTYPE_BYTES = {"bool": 1, "bool_": 1}


def _dtype_nbytes(name: str) -> int:
    if name in _NP_DTYPE_BYTES:
        return _NP_DTYPE_BYTES[name]
    m = re.search(r"(\d+)$", name)
    if not m:
        return 4  # unknown dtype: assume word-sized rather than skip
    return max(1, int(m.group(1)) // 8)


def _leaf_nbytes(leaf: Dict[str, Any]) -> int:
    elems = 1
    for s in leaf.get("shape", ()):
        elems *= int(s)
    return elems * _dtype_nbytes(str(leaf.get("dtype", "")))


@dataclasses.dataclass
class ProgramView:
    """One banked program, parsed once for all rules."""

    name: str
    feed: str
    mesh: Dict[str, int]
    args: Dict[str, List[Dict[str, Any]]]
    params: Dict[str, List[int]]
    record: Dict[str, Any]

    @classmethod
    def from_record(cls, name: str, rec: Dict[str, Any]) -> "ProgramView":
        return cls(
            name=name,
            feed=str(rec.get("feed", "")),
            mesh=dict((rec.get("meta") or {}).get("mesh_shape") or {}),
            args=rec.get("args") or {},
            params=rec.get("params") or {},
            record=rec,
        )

    def leaves(self, role: str):
        for leaf in self.args.get(role, []):
            yield leaf, parse_sharding(leaf.get("sharding"))

    def flat_leaf(self, index: int) -> Optional[Dict[str, Any]]:
        """The arg leaf at one flat (XLA parameter-order) index, via the
        banked role ranges."""
        for role, (start, end) in self.params.items():
            if start <= index < end:
                leaves = self.args.get(role, [])
                if index - start < len(leaves):
                    return leaves[index - start]
        return None

    def state_role(self) -> Optional[str]:
        for role in ("state", "variables", "qvariables"):
            if role in self.args:
                return role
        return None


# --------------------------------------------------------------- the rules


def _fmt_bytes(n: float) -> str:
    return f"{n / (1 << 20):.1f} MiB"


def _check_sl001(
    pv: ProgramView, path: str, threshold: int
) -> List[Finding]:
    n_model = int(pv.mesh.get(MODEL_AXIS, 1) or 1)
    if n_model <= 1:
        return []
    out: List[Finding] = []
    for role in pv.args:
        hits: List[Tuple[str, int]] = []
        total = 0
        for leaf, sh in pv.leaves(role):
            if sh is None or MODEL_AXIS in sh.axes_used:
                continue
            nbytes = _leaf_nbytes(leaf)
            if nbytes < threshold:
                continue
            if shard_dim(leaf.get("shape", ()), n_model) < 0:
                continue
            hits.append((leaf["path"], nbytes))
            total += nbytes
        if hits:
            out.append(
                Finding(
                    rule="SL001",
                    path=path,
                    line=0,
                    col=0,
                    func=pv.name,
                    message=(
                        f"{len(hits)} {role} leaf(s) totaling "
                        f"{_fmt_bytes(total)} replicated over the "
                        f"{n_model}-way model axis despite shardable dims "
                        f"(first: {hits[0][0]}, {_fmt_bytes(hits[0][1])})"
                    ),
                )
            )
    return out


def _state_spec_map(pv: ProgramView) -> Dict[str, str]:
    role = pv.state_role()
    if role is None:
        return {}
    out = {}
    for leaf, sh in pv.leaves(role):
        if sh is not None:
            out[leaf["path"]] = sh.spec_str()
    return out


def _check_sl002_cross(
    views: List[ProgramView], path: str
) -> List[Finding]:
    """Same-feed programs must agree on the state tree's in_specs."""
    by_feed: Dict[Tuple[str, Tuple], List[ProgramView]] = {}
    for pv in views:
        if pv.state_role() is None or not pv.mesh:
            continue
        key = (pv.feed, tuple(sorted(pv.mesh.items())))
        by_feed.setdefault(key, []).append(pv)
    out: List[Finding] = []
    for (_feed, _mesh), group in sorted(by_feed.items()):
        if len(group) < 2:
            continue
        group = sorted(group, key=lambda pv: pv.name)
        ref = group[0]
        ref_specs = _state_spec_map(ref)
        for pv in group[1:]:
            diffs = []
            for p, spec in _state_spec_map(pv).items():
                if p in ref_specs and ref_specs[p] != spec:
                    diffs.append((p, ref_specs[p], spec))
            if diffs:
                p0, a, b = diffs[0]
                out.append(
                    Finding(
                        rule="SL002",
                        path=path,
                        line=0,
                        col=0,
                        func=pv.name,
                        message=(
                            f"{len(diffs)} state leaf spec(s) differ from "
                            f"{ref.name}'s for the same tree (first: {p0} "
                            f"is {b} here, {a} there) — a checkpoint moving "
                            "between them reshards"
                        ),
                    )
                )
    return out


def _check_sl002_inout(pv: ProgramView, path: str) -> List[Finding]:
    """A train program's state out_shardings must match its in_specs —
    under donation anything else reshards the state every step."""
    out_sh = pv.record.get("out_shardings")
    role = pv.state_role()
    if not out_sh or role != "state" or role not in pv.params:
        return []
    leaves = pv.args.get(role, [])
    if len(out_sh) < len(leaves):
        return []
    diffs = []
    for i, leaf in enumerate(leaves):
        in_v = parse_sharding(leaf.get("sharding"))
        out_v = parse_sharding(out_sh[i])
        if in_v is None or out_v is None:
            continue
        if in_v.spec != out_v.spec:
            diffs.append((leaf["path"], in_v.spec_str(), out_v.spec_str()))
    if not diffs:
        return []
    p0, a, b = diffs[0]
    return [
        Finding(
            rule="SL002",
            path=path,
            line=0,
            col=0,
            func=pv.name,
            message=(
                f"{len(diffs)} state leaf(s) change sharding across the "
                f"step (first: {p0} enters as {a}, leaves as {b}) — "
                "hidden per-step reshard under donation"
            ),
        )
    ]


def _check_sl003(pv: ProgramView, path: str) -> List[Finding]:
    if not pv.mesh:
        return []
    out: List[Finding] = []
    sizes = {a: int(s or 1) for a, s in pv.mesh.items()}
    collectives = pv.record.get("collectives") or {}
    partitioned = pv.record.get("partitioned_collectives")
    # (a) collectives over axes the mesh does not have
    if collectives and all(s <= 1 for s in sizes.values()):
        out.append(
            Finding(
                rule="SL003",
                path=path,
                line=0,
                col=0,
                func=pv.name,
                message=(
                    f"lowered collectives {sorted(collectives)} in a "
                    f"program whose mesh {sizes} has no >1 axis"
                ),
            )
        )
    for kind, entry in (partitioned or {}).items():
        for axis, n_ops in (entry.get("axes") or {}).items():
            if axis in sizes and sizes[axis] <= 1 and n_ops:
                out.append(
                    Finding(
                        rule="SL003",
                        path=path,
                        line=0,
                        col=0,
                        func=pv.name,
                        message=(
                            f"{n_ops} {kind} op(s) classified on mesh "
                            f"axis '{axis}' of size {sizes[axis]}"
                        ),
                    )
                )
    # (b) a declared >1 axis nothing uses. `partitioned_collectives` may
    # legitimately be absent on legacy records — unknown is not unused.
    for axis, size in sorted(sizes.items()):
        if size <= 1:
            continue
        used = False
        for role in pv.args:
            for _leaf, sh in pv.leaves(role):
                if sh is not None and axis in sh.axes_used:
                    used = True
                    break
            if used:
                break
        if not used and collectives and axis == DATA_AXIS:
            # hand-written shard_map collectives run over the data axis
            used = True
        if not used and partitioned is None:
            used = True
        if not used:
            for entry in (partitioned or {}).values():
                axes = entry.get("axes") or {}
                if axes.get(axis) or any(
                    axes.get(b) for b in _WHOLE_MESH_AXES
                ):
                    used = True
                    break
        if not used:
            out.append(
                Finding(
                    rule="SL003",
                    path=path,
                    line=0,
                    col=0,
                    func=pv.name,
                    message=(
                        f"mesh declares '{axis}': {size} but no in_spec "
                        "shards over it and no collective spans it — "
                        "dead mesh axis"
                    ),
                )
            )
    return out


def _check_sl004(pv: ProgramView, path: str) -> List[Finding]:
    out_sh = pv.record.get("out_shardings")
    if not out_sh:
        return []
    diffs = []
    for entry in pv.record.get("aliasing") or []:
        oidx = str(entry.get("output", ""))
        if not oidx.isdigit() or int(oidx) >= len(out_sh):
            continue
        leaf = pv.flat_leaf(int(entry.get("parameter", -1)))
        if leaf is None:
            continue
        in_v = parse_sharding(leaf.get("sharding"))
        out_v = parse_sharding(out_sh[int(oidx)])
        if in_v is None or out_v is None:
            continue
        if in_v.spec != out_v.spec:
            diffs.append(
                (leaf["path"], in_v.spec_str(), out_v.spec_str())
            )
    if not diffs:
        return []
    p0, a, b = diffs[0]
    return [
        Finding(
            rule="SL004",
            path=path,
            line=0,
            col=0,
            func=pv.name,
            message=(
                f"{len(diffs)} donated input(s) alias outputs with a "
                f"different sharding (first: {p0} donated as {a}, output "
                f"is {b}) — XLA copies instead of aliasing"
            ),
        )
    ]


def _check_sl005(
    pv: ProgramView, path: str, budget: int
) -> List[Finding]:
    comm = pv.record.get("comm")
    if not comm:
        return []
    out: List[Finding] = []
    try:
        wire = int(comm.get("wire_bytes_per_device", 0))
    except (TypeError, ValueError):
        wire = 0
    if wire > budget:
        out.append(
            Finding(
                rule="SL005",
                path=path,
                line=0,
                col=0,
                func=pv.name,
                message=(
                    f"static collective cost {_fmt_bytes(wire)}/device/"
                    f"step exceeds analysis.comm_budget_bytes "
                    f"({_fmt_bytes(budget)})"
                ),
            )
        )
    resum = commcost.recompute_wire_total(comm)
    if resum is not None and wire and (
        abs(resum - wire) > _COMM_CONSISTENCY_TOL * max(wire, 1)
    ):
        out.append(
            Finding(
                rule="SL005",
                path=path,
                line=0,
                col=0,
                func=pv.name,
                message=(
                    f"banked wire_bytes_per_device ({wire}) disagrees "
                    f"with its own per-kind tallies ({resum}) — "
                    "hand-edited comm record"
                ),
            )
        )
    return out


def _check_sl006(pv: ProgramView, path: str) -> List[Finding]:
    if pv.feed not in ZERO_INTENT_FEEDS:
        return []
    role = pv.state_role()
    if role is None or not pv.mesh:
        return []
    n_data = int(pv.mesh.get(DATA_AXIS, 1) or 1)
    n_model = (
        int(pv.mesh.get(MODEL_AXIS, 1) or 1)
        if pv.feed == "mp_zero"
        else 1
    )
    diffs = []
    fallbacks = 0
    for leaf, sh in pv.leaves(role):
        if ".opt_state" not in leaf["path"] or sh is None:
            continue
        expected = compose_spec_dims(leaf.get("shape", ()), n_data, n_model)
        actual = sh.spec
        exp_norm = tuple(
            None if e is None else (e,) for e in expected
        )
        if actual != exp_norm:
            diffs.append((leaf["path"], exp_norm, actual))
            if exp_norm and not actual:
                fallbacks += 1
    if not diffs:
        return []
    p0, exp, act = diffs[0]
    return [
        Finding(
            rule="SL006",
            path=path,
            line=0,
            col=0,
            func=pv.name,
            message=(
                f"{len(diffs)} opt_state leaf(s) deviate from the "
                f"zero.compose_spec layout ({fallbacks} silently "
                f"replicated despite a divisible dim; first: {p0} "
                f"expected {exp}, got {act})"
            ),
        )
    ]


# ------------------------------------------------------------ lint driver


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]
    suppressed: List[Tuple[Finding, str]]
    excluded: List[Finding]
    stale_waivers: List[Waiver]

    def to_dict(self) -> Dict[str, Any]:
        return {
            "rules": RULES,
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [
                {**f.to_dict(), "reason": r} for f, r in self.suppressed
            ],
            "excluded_count": len(self.excluded),
            "stale_waivers": [dataclasses.asdict(w) for w in self.stale_waivers],
            "ok": not self.findings and not self.stale_waivers,
        }


def _rel(path: str, pkg_root: str) -> str:
    repo_root = os.path.dirname(os.path.abspath(pkg_root))
    ap = os.path.abspath(path)
    if ap.startswith(repo_root + os.sep):
        return os.path.relpath(ap, repo_root).replace(os.sep, "/")
    return os.path.basename(ap)


def lint_bank(
    bank: Dict[str, Any],
    rel_path: str,
    replicated_bytes_threshold: int,
    comm_budget_bytes: int,
) -> List[Finding]:
    """All raw SL findings for one loaded fingerprint bank."""
    views = [
        ProgramView.from_record(name, rec)
        for name, rec in sorted((bank.get("programs") or {}).items())
    ]
    raw: List[Finding] = []
    for pv in views:
        raw.extend(_check_sl001(pv, rel_path, replicated_bytes_threshold))
        raw.extend(_check_sl002_inout(pv, rel_path))
        raw.extend(_check_sl003(pv, rel_path))
        raw.extend(_check_sl004(pv, rel_path))
        raw.extend(_check_sl005(pv, rel_path, comm_budget_bytes))
        raw.extend(_check_sl006(pv, rel_path))
    raw.extend(_check_sl002_cross(views, rel_path))
    return sorted(raw, key=lambda f: (f.func, f.rule, f.message))


def _waive(base: Baseline, f: Finding) -> Optional[Waiver]:
    """Waiver resolution with fnmatch on func (the program name) —
    `func = "train_mp_k*"` addresses a program family. Exact-func and
    "*" waivers behave identically to jaxlint's matcher."""
    for w in base.waivers:
        if (
            w.rule == f.rule
            and w.path == f.path
            and fnmatch.fnmatchcase(f.func, w.func)
        ):
            w.used = True
            return w
    return None


def lint_paths(
    paths: Sequence[str],
    baseline: Optional[str] = None,
    pkg_root: Optional[str] = None,
    replicated_bytes_threshold: Optional[int] = None,
    comm_budget_bytes: Optional[int] = None,
) -> LintResult:
    """Lint explicit fingerprint-bank JSON paths. Non-bank files (other
    suffixes, wrong schema) are skipped — when `frcnn check` fans a mixed
    path list over all analyzers, banks are this one's share."""
    defaults = AnalysisConfig()
    threshold = (
        replicated_bytes_threshold
        if replicated_bytes_threshold is not None
        else defaults.replicated_bytes_threshold
    )
    budget = (
        comm_budget_bytes
        if comm_budget_bytes is not None
        else defaults.comm_budget_bytes
    )
    root = pkg_root or package_root()
    raw: List[Finding] = []
    for path in paths:
        if not str(path).endswith(".json"):
            continue
        bank = _fp.load_bank(str(path))
        if bank is None:
            continue
        raw.extend(lint_bank(bank, _rel(str(path), root), threshold, budget))
    base = (
        load_baseline(baseline).restricted(RULES) if baseline else Baseline()
    )
    findings: List[Finding] = []
    suppressed: List[Tuple[Finding, str]] = []
    excluded: List[Finding] = []
    for f in raw:
        if base.excluded(f):
            excluded.append(f)
            continue
        w = _waive(base, f)
        if w is not None:
            suppressed.append((f, w.reason))
        else:
            findings.append(f)
    stale = [w for w in base.waivers if not w.used]
    return LintResult(findings, suppressed, excluded, stale)


def lint_package(baseline: Optional[str] = "default") -> LintResult:
    """Lint every committed bank under analysis/fingerprints/."""
    if baseline == "default":
        baseline = default_baseline_path()
        if not os.path.exists(baseline):
            baseline = None
    banks = sorted(
        glob.glob(os.path.join(_fp.default_fingerprint_dir(), "*.json"))
    )
    return lint_paths(banks, baseline=baseline)
