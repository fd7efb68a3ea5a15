"""jaxlint — AST lint for JAX jit hygiene, tuned to this codebase.

The perf work (fused dispatch, critical-path overlap) is silently undone
whenever a stray host sync, tracer branch, or avoidable recompile creeps
back into a jitted path; benchmarks catch that only after the fact. This
module catches it at review time, with project-specific rules:

  JX001  host-sync hazard: ``float()`` / ``int()`` / ``.item()`` /
         ``np.asarray()`` applied to a tracer-typed (jnp) value — inside a
         jit-reachable function that forces a device sync per call, and in
         host code it forces a sync of un-jitted device math (the classic
         per-step ``float(schedule(step))`` pull).
  JX002  Python ``if``/``while`` branching on a tracer value inside a
         jit-reachable function (a trace-time crash or, worse, a silent
         constant-fold on the tracing value).
  JX003  donated-buffer reuse: reading an argument again after passing it
         to a dispatch that donates it (``donate_argnums``).
  JX004  mutable/non-hashable value (list/dict/set) passed — or defaulted —
         for a parameter marked static (``static_argnums``/``argnames``):
         every call re-hashes, a changed value silently recompiles, an
         unhashable one throws at dispatch.
  JX005  ``jax.random`` key reused by two sampling calls without an
         intervening ``split`` (identical randomness; ``fold_in`` derives
         fresh keys and is exempt).
  JX006  ``block_until_ready`` / ``jax.device_get`` outside a telemetry
         span: unattributed sync time that telemetry reports then book to
         the wrong phase (the spans contract from PR 1).

Jit-reachability is computed by walking the call graph from every
``jax.jit`` / ``shard_map`` entry point in the package (the known roots
live in train/train_step.py, parallel/spmd.py, eval/evaluator.py; the
discovery scans every module so new roots are picked up automatically).
The call-graph machinery itself — module indexing, name/callee
resolution, factory-return and alias following, edge building — lives in
:mod:`analysis.callgraph`, shared with :mod:`analysis.threadlint` (which
walks the same graph from *thread* entry points instead of jit roots).
The walker follows factory returns (``jax.jit(make_train_step(...))``),
tuple-assignment aliasing (``body, spec = per_shard_multi, P(...)``),
``self.attr`` bindings (``self.jitted_step = jax.jit(...)``) and
function-reference arguments (``lax.scan(body, ...)``,
``value_and_grad(loss_fn)``). ``flax`` module dispatch is resolved by
method name for ``.apply(..., method="name")`` call sites.

Findings resolve against a committed suppression file
(``analysis/baseline.toml``): every pre-existing violation is either fixed
or explicitly waived with a reason. The baseline file is shared with
threadlint; each analyzer only matches (and stale-checks) waivers for its
own rule set. ``frcnn check`` runs this standalone (``--json`` for
machine-readable output, nonzero exit on unsuppressed findings) and
tests/test_jaxlint.py asserts the package lints clean.

Known limits (deliberate — this is a reviewer, not a verifier): taint is
per-function and flow-insensitive across branches; dynamic dispatch other
than the patterns above is not followed; runtime truth is the job of
analysis/strict.py.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from replication_faster_rcnn_tpu.analysis.callgraph import (  # noqa: F401
    _JIT_NAMES,
    _REMAT_NAMES,
    _SHARD_MAP_NAMES,
    _STATIC_ANNOTATION_HEADS,
    _STATIC_PARAM_NAMES,
    FunctionInfo,
    Index,
    ModuleInfo,
    _ann_str,
    _annotation_static,
    _callable_from_expr,
    _dotted,
    _int_tuple,
    _local_aliases,
    _resolve_callee,
    _resolve_dotted_prefix,
    _resolve_name,
    _str_tuple,
    build_edges,
    parse_modules,
    reachable_from,
)

RULES: Dict[str, str] = {
    "JX001": "host-sync hazard: float()/int()/.item()/np.asarray on a jnp value",
    "JX002": "Python if/while branches on a tracer value in jit-reachable code",
    "JX003": "donated buffer read again after a donating dispatch",
    "JX004": "mutable/non-hashable value for a static jit argument",
    "JX005": "jax.random key reused without split",
    "JX006": "block_until_ready/device_get outside a telemetry span",
    "JX007": "implicit-dtype array creation in jit-reachable code",
}

PACKAGE = "replication_faster_rcnn_tpu"

# attribute reads that are static under tracing (no device value involved)
_SHAPE_ATTRS = {"shape", "dtype", "ndim", "size", "aval", "sharding", "weak_type"}
# dotted-call prefixes whose results are tracer-typed
_TRACER_CALL_PREFIXES = (
    "jax.numpy.",
    "jax.lax.",
    "jax.random.",
    "jax.nn.",
    "jax.scipy.",
)
# external callables that just map over their arguments (taint passes through)
_PASSTHROUGH_CALLS = {
    "jax.tree_util.tree_map",
    "jax.tree.map",
    "optax.apply_updates",
    "jax.checkpoint",
    "jax.remat",
}
_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready"}
# jnp creation calls whose result dtype follows weak-type/x64 promotion
# unless pinned; value = index of the positional dtype parameter (the
# package idiom `jnp.zeros((), jnp.int32)` counts as explicit)
_IMPLICIT_DTYPE_CALLS = {
    "jax.numpy.array": 1,
    "jax.numpy.asarray": 1,
    "jax.numpy.zeros": 1,
    "jax.numpy.ones": 1,
    "jax.numpy.empty": 1,
    "jax.numpy.full": 2,
    "jax.numpy.arange": 3,
}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative posix path
    line: int
    col: int
    func: str  # function qualname within the module ("<module>" at top level)
    message: str

    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.func)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} [{self.func}] {self.message}"


@dataclasses.dataclass
class Waiver:
    rule: str
    path: str
    func: str  # "*" matches any function in the file
    reason: str
    used: bool = False
    line: int = 0  # 1-based line of this [[waiver]] header in the TOML

    def matches(self, f: Finding) -> bool:
        return (
            self.rule == f.rule
            and self.path == f.path
            and (self.func == "*" or self.func == f.func)
        )


@dataclasses.dataclass
class Baseline:
    waivers: List[Waiver] = dataclasses.field(default_factory=list)
    # rule -> excluded path prefixes (measurement/tooling modules where the
    # rule's premise does not apply)
    excludes: Dict[str, List[str]] = dataclasses.field(default_factory=dict)

    def excluded(self, f: Finding) -> bool:
        return any(f.path.startswith(p) for p in self.excludes.get(f.rule, ()))

    def waive(self, f: Finding) -> Optional[Waiver]:
        for w in self.waivers:
            if w.matches(f):
                w.used = True
                return w
        return None

    def restricted(self, rules: "Set[str] | Dict[str, str]") -> "Baseline":
        """A view keeping only waivers/excludes for ``rules`` — the shared
        baseline.toml carries entries for several analyzers; each must
        stale-check only its own."""
        return Baseline(
            waivers=[w for w in self.waivers if w.rule in rules],
            excludes={r: p for r, p in self.excludes.items() if r in rules},
        )


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]  # unsuppressed
    suppressed: List[Tuple[Finding, str]]  # (finding, waiver reason)
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


def load_baseline(path: str) -> Baseline:
    try:
        import tomllib  # py >= 3.11
    except ModuleNotFoundError:  # pragma: no cover - py 3.10 image
        import tomli as tomllib
    with open(path, "rb") as f:
        raw = f.read().decode("utf-8")
    data = tomllib.loads(raw)
    # tomllib keeps array-of-tables in document order, so the Nth parsed
    # waiver belongs to the Nth `[[waiver]]` header — that line number
    # makes stale-waiver reports point at the exact entry to delete
    header_lines = [
        i + 1
        for i, ln in enumerate(raw.splitlines())
        if ln.strip().startswith("[[waiver]]")
    ]
    waivers = []
    for n, w in enumerate(data.get("waiver", [])):
        if not w.get("reason"):
            raise ValueError(
                f"baseline waiver {w.get('rule')}:{w.get('path')} has no "
                "reason — every suppression must say why"
            )
        waivers.append(
            Waiver(
                rule=w["rule"],
                path=w["path"],
                func=w.get("func", "*"),
                reason=w["reason"],
                line=header_lines[n] if n < len(header_lines) else 0,
            )
        )
    excludes = {
        rule: list(paths) for rule, paths in data.get("excludes", {}).items()
    }
    return Baseline(waivers=waivers, excludes=excludes)


# ----------------------------------------------------------- index + roots


def build_index(paths: Sequence[str], package_root: str) -> Index:
    """Parse, discover jit/shard_map roots, build edges, mark
    jit-reachability. The parsing/resolution half lives in callgraph."""
    idx = parse_modules(list(paths), package_root)
    _discover(idx)
    build_edges(idx)
    for f in reachable_from(idx, idx.roots):
        f.jit_reachable = True
    return idx


def _discover(idx: Index) -> None:
    """Find jit/shard_map roots, donating callables, and static-arg specs."""
    for mi in idx.modules.values():
        # decorators
        for fi in mi.functions.values():
            for dec in getattr(fi.node, "decorator_list", []):
                d = _dotted(dec) if not isinstance(dec, ast.Call) else _dotted(dec.func)
                if d is None:
                    continue
                rd = _resolve_dotted_prefix(mi, d)
                if rd in _JIT_NAMES:
                    idx.roots.add(fi)
                    if isinstance(dec, ast.Call):
                        _record_static(idx, mi, fi, dec.keywords)
                elif rd.endswith("functools.partial") and isinstance(dec, ast.Call):
                    inner = dec.args[0] if dec.args else None
                    di = _dotted(inner) if inner is not None else None
                    if di is not None and _resolve_dotted_prefix(mi, di) in _JIT_NAMES:
                        idx.roots.add(fi)
                        _record_static(idx, mi, fi, dec.keywords)
        # call sites
        for qual, fi in list(mi.functions.items()):
            aliases = _local_aliases(idx, fi)
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                callee = _resolve_callee(idx, fi, mi, node.func, aliases)
                dotted = [t for t in callee if isinstance(t, str)]
                if any(d in _JIT_NAMES or d in _SHARD_MAP_NAMES for d in dotted):
                    if node.args:
                        fis, donate = _callable_from_expr(
                            idx, fi, mi, node.args[0], aliases
                        )
                        idx.roots.update(fis)
                        for kw in node.keywords:
                            if kw.arg == "donate_argnums":
                                donate = _int_tuple(kw.value) or donate
                        if donate:
                            for f in fis:
                                idx.donating[
                                    f"{f.module.modname}.{f.qualname}"
                                ] = donate
                if any(d in _REMAT_NAMES for d in dotted) and node.args:
                    fis, _ = _callable_from_expr(idx, fi, mi, node.args[0], aliases)
                    for kw in node.keywords:
                        if kw.arg in ("static_argnums", "static_argnames"):
                            for f in fis:
                                _record_static_for(idx, f, kw)
        # module-level jit sites (`jitted = jax.jit(step, ...)` at top
        # level): not inside any function, so the walk above misses them
        for stmt in mi.tree.body:
            for node in ast.walk(stmt):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    break  # function bodies were handled with local scope
                if not isinstance(node, ast.Call):
                    continue
                callee = _resolve_callee(idx, None, mi, node.func)
                dotted = [t for t in callee if isinstance(t, str)]
                if any(d in _JIT_NAMES or d in _SHARD_MAP_NAMES for d in dotted) and node.args:
                    fis, donate = _callable_from_expr(idx, None, mi, node.args[0])
                    idx.roots.update(fis)
                    for kw in node.keywords:
                        if kw.arg == "donate_argnums":
                            donate = _int_tuple(kw.value) or donate
                    if donate:
                        for f in fis:
                            idx.donating[f"{f.module.modname}.{f.qualname}"] = donate
                        if (
                            isinstance(stmt, ast.Assign)
                            and len(stmt.targets) == 1
                            and isinstance(stmt.targets[0], ast.Name)
                        ):
                            # calls through the module-level binding donate too
                            idx.donating[
                                f"{mi.modname}.{stmt.targets[0].id}"
                            ] = donate


def _record_static(idx: Index, mi: ModuleInfo, fi: FunctionInfo, keywords) -> None:
    for kw in keywords:
        if kw.arg in ("static_argnums", "static_argnames"):
            _record_static_for(idx, fi, kw)


def _record_static_for(idx: Index, fi: FunctionInfo, kw: ast.keyword) -> None:
    key = f"{fi.module.modname}.{fi.qualname}"
    names = idx.static_args.setdefault(key, set())
    if kw.arg == "static_argnames":
        names.update(_str_tuple(kw.value))
    else:
        nums = _int_tuple(kw.value) or ()
        for n in nums:
            if 0 <= n < len(fi.params):
                names.add(fi.params[n])


# ----------------------------------------------------------- taint + rules


class _Env:
    __slots__ = ("tainted", "containers", "keys", "key_uses", "dead", "in_span")

    def __init__(self) -> None:
        self.tainted: Set[str] = set()
        # names bound to Python containers (list/tuple/dict literals or
        # comprehensions): their *truthiness* is a host length check even
        # when the elements are tracers
        self.containers: Set[str] = set()
        self.keys: Set[str] = set()
        self.key_uses: Dict[str, int] = {}
        self.dead: Dict[str, int] = {}  # donated name -> line of donation
        self.in_span = 0


class _RuleWalker:
    """Single in-order pass over one function's statements."""

    def __init__(self, idx: Index, fi: FunctionInfo, findings: List[Finding]):
        self.idx = idx
        self.fi = fi
        self.mi = fi.module
        self.findings = findings
        self.aliases = _local_aliases(idx, fi)
        self.env = _Env()
        if fi.jit_reachable:
            for p in fi.params:
                if p not in fi.static_params:
                    self.env.tainted.add(p)

    # ---------------- helpers

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        self.findings.append(
            Finding(
                rule=rule,
                path=self.mi.relpath,
                line=getattr(node, "lineno", 0),
                col=getattr(node, "col_offset", 0),
                func=self.fi.qualname,
                message=message,
            )
        )

    def _callee_dotted(self, call: ast.Call) -> List[str]:
        out = []
        for t in _resolve_callee(self.idx, self.fi, self.mi, call.func, self.aliases):
            if isinstance(t, str):
                out.append(t)
        d = _dotted(call.func)
        if d is not None:
            out.append(_resolve_dotted_prefix(self.mi, d))
            out.append(d)
        return out

    def _callee_fns(self, call: ast.Call) -> List[FunctionInfo]:
        return [
            t
            for t in _resolve_callee(self.idx, self.fi, self.mi, call.func, self.aliases)
            if isinstance(t, FunctionInfo)
        ]

    def _returns_tracer(self, fn: FunctionInfo, _depth: int = 0) -> bool:
        if fn._returns_tracer is not None:
            return fn._returns_tracer
        if _depth > 4:
            return False
        fn._returns_tracer = False  # cut recursion cycles
        w = _RuleWalker(self.idx, fn, [])  # throwaway: taint only
        result = False
        for elts in fn.returns():
            for e in elts:
                if e is not None and w.tainted(e):
                    result = True
        fn._returns_tracer = result
        return result

    # ---------------- taint

    def tainted(self, node: Optional[ast.AST]) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.env.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _SHAPE_ATTRS:
                return False
            return self.tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self.tainted(node.value)
        if isinstance(node, ast.Call):
            return self.call_tainted(node)
        if isinstance(node, (ast.BinOp,)):
            return self.tainted(node.left) or self.tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.tainted(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.tainted(v) for v in node.values)
        if isinstance(node, ast.Compare):
            ops = node.ops
            if all(isinstance(o, (ast.Is, ast.IsNot, ast.In, ast.NotIn)) for o in ops):
                return False
            if any(
                isinstance(c, ast.Constant) and c.value is None
                for c in [node.left] + node.comparators
            ):
                return False
            return self.tainted(node.left) or any(
                self.tainted(c) for c in node.comparators
            )
        if isinstance(node, ast.IfExp):
            return self.tainted(node.body) or self.tainted(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.tainted(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(self.tainted(v) for v in node.values if v is not None)
        if isinstance(node, ast.Starred):
            return self.tainted(node.value)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return self.tainted(node.elt) or any(
                self.tainted(g.iter) for g in node.generators
            )
        if isinstance(node, ast.DictComp):
            return self.tainted(node.value) or any(
                self.tainted(g.iter) for g in node.generators
            )
        if isinstance(node, ast.JoinedStr):
            return False
        return False

    def call_tainted(self, call: ast.Call) -> bool:
        dotted = self._callee_dotted(call)
        # host conversions return host values (JX001 flags them separately)
        if isinstance(call.func, ast.Name) and call.func.id in (
            "float", "int", "bool", "str", "len", "repr",
        ):
            return False
        if isinstance(call.func, ast.Attribute) and call.func.attr == "item":
            return False
        if any(d in _SYNC_CALLS for d in dotted):
            return False
        for d in dotted:
            if d.startswith(_TRACER_CALL_PREFIXES) and not d.startswith(
                ("jax.random.PRNGKey",)
            ):
                return True
            if d in _PASSTHROUGH_CALLS:
                return any(self.tainted(a) for a in call.args)
        if any(d.startswith("jax.random.") for d in dotted):
            return True
        for fn in self._callee_fns(call):
            if self._returns_tracer(fn):
                return True
        # method call on a tainted object (x.sum(), x.astype(...))
        if isinstance(call.func, ast.Attribute) and self.tainted(call.func.value):
            return True
        return False

    # ---------------- statement walk

    def walk(self) -> None:
        self._walk_stmts(getattr(self.fi.node, "body", []))

    def _walk_stmts(self, stmts: Sequence[ast.stmt]) -> None:
        for s in stmts:
            self._stmt(s)

    def _stmt(self, s: ast.stmt) -> None:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            return  # nested defs are walked as their own functions
        if isinstance(s, ast.Assign):
            self._expr(s.value)
            self._assign(s.targets, s.value, s)
        elif isinstance(s, ast.AugAssign):
            self._expr(s.value)
            if isinstance(s.target, ast.Name):
                if self.tainted(s.value):
                    self.env.tainted.add(s.target.id)
                self._revive(s.target.id)
        elif isinstance(s, ast.AnnAssign):
            if s.value is not None:
                self._expr(s.value)
                self._assign([s.target], s.value, s)
        elif isinstance(s, (ast.If, ast.While)):
            # `not isinstance(x, ...Tracer) and <rest>` is the idiomatic
            # "host value only" guard: x is proven concrete for the rest
            # of the test and the body — narrow its taint there.
            guarded = self._tracer_guarded_names(s.test)
            re_taint = guarded & self.env.tainted
            self.env.tainted -= guarded
            self._expr(s.test)
            if self.fi.jit_reachable and self._truth_tainted(s.test):
                kind = "if" if isinstance(s, ast.If) else "while"
                self._emit(
                    "JX002",
                    s,
                    f"`{kind}` branches on a tracer value inside jit-reachable "
                    f"`{self.fi.qualname}` — use jnp.where/lax.cond, or mark "
                    "the argument static",
                )
            self._walk_stmts(s.body)
            self.env.tainted |= re_taint
            self._walk_stmts(s.orelse)
        elif isinstance(s, ast.For):
            self._expr(s.iter)
            if isinstance(s.target, ast.Name) and self.tainted(s.iter):
                self.env.tainted.add(s.target.id)
            self._walk_stmts(s.body)
            self._walk_stmts(s.orelse)
        elif isinstance(s, ast.With):
            spanned = any(self._is_span(item.context_expr) for item in s.items)
            for item in s.items:
                self._expr(item.context_expr)
            if spanned:
                self.env.in_span += 1
            self._walk_stmts(s.body)
            if spanned:
                self.env.in_span -= 1
        elif isinstance(s, ast.Try):
            self._walk_stmts(s.body)
            for h in s.handlers:
                self._walk_stmts(h.body)
            self._walk_stmts(s.orelse)
            self._walk_stmts(s.finalbody)
        elif isinstance(s, ast.Return):
            if s.value is not None:
                self._expr(s.value)
        elif isinstance(s, ast.Expr):
            self._expr(s.value)
            if isinstance(s.value, ast.Call):
                self._donating_call(s.value, targets=[])
        elif isinstance(s, (ast.Raise, ast.Assert)):
            for sub in ast.walk(s):
                if isinstance(sub, ast.Call):
                    self._expr(sub)
                    break
        elif isinstance(s, ast.Delete):
            for t in s.targets:
                if isinstance(t, ast.Name):
                    self.env.tainted.discard(t.id)
                    self.env.dead.pop(t.id, None)

    def _tracer_guarded_names(self, test: ast.AST) -> Set[str]:
        """Names proven non-tracer by a ``not isinstance(x, ...Tracer)``
        conjunct in ``test``."""
        out: Set[str] = set()
        conjuncts = test.values if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And) else [test]
        for c in conjuncts:
            if not (isinstance(c, ast.UnaryOp) and isinstance(c.op, ast.Not)):
                continue
            call = c.operand
            if not (
                isinstance(call, ast.Call)
                and isinstance(call.func, ast.Name)
                and call.func.id == "isinstance"
                and len(call.args) == 2
                and isinstance(call.args[0], ast.Name)
            ):
                continue
            cls = _dotted(call.args[1])
            if cls is not None and cls.endswith("Tracer"):
                out.add(call.args[0].id)
        return out

    def _truth_tainted(self, test: ast.AST) -> bool:
        """Like ``tainted`` but for truthiness: ``if xs`` / ``if not xs``
        on a Python container is a host length check even when the
        elements are tracers."""
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._truth_tainted(test.operand)
        if isinstance(test, ast.Name) and test.id in self.env.containers:
            return False
        if isinstance(test, ast.BoolOp):
            return any(self._truth_tainted(v) for v in test.values)
        return self.tainted(test)

    def _is_span(self, expr: ast.AST) -> bool:
        if not isinstance(expr, ast.Call):
            return False
        f = expr.func
        if isinstance(f, ast.Attribute) and f.attr == "span":
            return True
        if isinstance(f, ast.Name) and "span" in f.id.lower():
            return True
        return False

    def _assign(self, targets, value: ast.AST, stmt: ast.stmt) -> None:
        names = [t.id for t in ast.walk(ast.Tuple(elts=list(targets), ctx=ast.Store())) if isinstance(t, ast.Name)]
        tgt_dotted = set()
        for t in targets:
            for sub in ast.walk(t):
                d = _dotted(sub)
                if d is not None:
                    tgt_dotted.add(d)
        if isinstance(value, ast.Call):
            self._donating_call(value, targets=sorted(tgt_dotted))
        value_tainted = self.tainted(value)
        # pairwise tuple-to-tuple assignment keeps taint per element
        if (
            len(targets) == 1
            and isinstance(targets[0], ast.Tuple)
            and isinstance(value, ast.Tuple)
            and len(targets[0].elts) == len(value.elts)
        ):
            for t, v in zip(targets[0].elts, value.elts):
                if isinstance(t, ast.Name):
                    self._set_taint(t.id, self.tainted(v))
                    self._track_key(t.id, v)
            return
        container = isinstance(
            value,
            (ast.List, ast.Tuple, ast.Set, ast.Dict, ast.ListComp, ast.SetComp, ast.DictComp),
        )
        for name in names:
            self._set_taint(name, value_tainted)
            if container:
                self.env.containers.add(name)
            else:
                self.env.containers.discard(name)
            self._track_key(name, value)

    def _set_taint(self, name: str, tainted: bool) -> None:
        if tainted:
            self.env.tainted.add(name)
        else:
            self.env.tainted.discard(name)
        self._revive(name)

    def _revive(self, name: str) -> None:
        self.env.dead.pop(name, None)
        # a rebind of a key name resets its use count
        if name in self.env.keys:
            self.env.key_uses[name] = 0

    def _track_key(self, name: str, value: ast.AST) -> None:
        if not isinstance(value, ast.Call):
            return
        dotted = self._callee_dotted(value)
        if any(
            d in ("jax.random.PRNGKey", "jax.random.split", "jax.random.fold_in", "jax.random.key")
            for d in dotted
        ):
            self.env.keys.add(name)
            self.env.key_uses[name] = 0

    def _donating_call(self, call: ast.Call, targets: List[str]) -> None:
        """JX003 bookkeeping: mark donated args dead unless reassigned."""
        donate: Optional[Tuple[int, ...]] = None
        f = call.func
        d = _dotted(f)
        if d is not None and d.startswith("self.") and self.fi.cls is not None:
            donate = self.idx.donating.get(f"{self.fi.cls}.{d[len('self.'):]}")
        if donate is None and isinstance(f, ast.Name):
            # a module-level jitted binding (`jitted = jax.jit(fn, ...)`)
            donate = self.idx.donating.get(f"{self.mi.modname}.{f.id}")
        if donate is None and isinstance(f, ast.Name):
            for t in _resolve_name(self.idx, self.fi, self.mi, f.id, self.aliases):
                if isinstance(t, FunctionInfo):
                    donate = self.idx.donating.get(
                        f"{t.module.modname}.{t.qualname}"
                    )
                    if donate:
                        break
                elif isinstance(t, str):
                    donate = self.idx.donating.get(t)
                    if donate:
                        break
            # locally-jitted donating callable: `step = jax.jit(f, donate_...)`
            if donate is None and f.id in self.aliases:
                pass
        if not donate:
            return
        for i in donate:
            if i >= len(call.args):
                continue
            arg = call.args[i]
            ad = _dotted(arg)
            if ad is None:
                continue
            if ad in targets:
                continue  # donated buffer is rebound by this statement: safe
            self.env.dead[ad] = getattr(call, "lineno", 0)

    # ---------------- expression rules

    def _expr(self, node: Optional[ast.AST]) -> None:
        if node is None:
            return
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                self._check_call(sub)
            elif isinstance(sub, (ast.Name, ast.Attribute)) and isinstance(
                getattr(sub, "ctx", None), ast.Load
            ):
                d = _dotted(sub)
                if d is not None and d in self.env.dead:
                    self._emit(
                        "JX003",
                        sub,
                        f"`{d}` was donated to a dispatch at line "
                        f"{self.env.dead[d]} and read again — its buffer may "
                        "already be reused; rebind the result "
                        "(`x, out = jitted(x, ...)`) or pass a copy",
                    )
                    self.env.dead.pop(d, None)  # one report per donation

    def _check_call(self, call: ast.Call) -> None:
        dotted = self._callee_dotted(call)
        # ---- JX001: host conversion of a tracer value
        conv = None
        if isinstance(call.func, ast.Name) and call.func.id in ("float", "int"):
            conv = call.func.id
            arg = call.args[0] if call.args else None
        elif isinstance(call.func, ast.Attribute) and call.func.attr == "item" and not call.args:
            conv = ".item()"
            arg = call.func.value
        elif any(d in ("numpy.asarray", "numpy.array", "np.asarray", "np.array") for d in dotted):
            conv = "np.asarray"
            arg = call.args[0] if call.args else None
        else:
            arg = None
        if conv is not None and arg is not None and self.tainted(arg):
            where = (
                "inside jit-reachable code (device sync per call)"
                if self.fi.jit_reachable
                else "in host code (forces a device sync of un-jitted jnp math)"
            )
            self._emit(
                "JX001",
                call,
                f"`{conv}` applied to a jnp value {where} — keep the math in "
                "jnp, or fetch once at a sync boundary via jax.device_get",
            )
        # ---- JX005: key reuse
        if any(d.startswith("jax.random.") for d in dotted) and not any(
            d in ("jax.random.PRNGKey", "jax.random.key", "jax.random.fold_in")
            for d in dotted
        ):
            if call.args and isinstance(call.args[0], ast.Name):
                name = call.args[0].id
                if name in self.env.keys:
                    self.env.key_uses[name] = self.env.key_uses.get(name, 0) + 1
                    if self.env.key_uses[name] >= 2:
                        self._emit(
                            "JX005",
                            call,
                            f"key `{name}` consumed by a second jax.random "
                            "call without an intervening split — identical "
                            "randomness; split (or fold_in) first",
                        )
        # ---- JX006: un-spanned sync
        sync = None
        if isinstance(call.func, ast.Attribute) and call.func.attr == "block_until_ready":
            sync = "block_until_ready"
        elif any(d in _SYNC_CALLS for d in dotted):
            sync = next(d for d in dotted if d in _SYNC_CALLS).split(".")[-1]
        if sync is not None and not self.env.in_span:
            self._emit(
                "JX006",
                call,
                f"`{sync}` outside a telemetry span — sync time is "
                "unattributed; wrap in `tracer.span(...)` (telemetry/spans.py) "
                "or waive with a reason if a caller holds the span",
            )
        # ---- JX007: implicit-dtype creation in jit-reachable code
        if self.fi.jit_reachable:
            self._check_implicit_dtype(call, dotted)
        # ---- JX004: mutable static args
        self._check_static_args(call, dotted)

    def _check_implicit_dtype(self, call: ast.Call, dotted: List[str]) -> None:
        hit = next((d for d in dotted if d in _IMPLICIT_DTYPE_CALLS), None)
        if hit is None:
            return
        if any(kw.arg == "dtype" for kw in call.keywords):
            return
        if len(call.args) > _IMPLICIT_DTYPE_CALLS[hit]:
            return  # positional dtype argument present
        short = hit.replace("jax.numpy.", "jnp.")
        if short in ("jnp.array", "jnp.asarray"):
            # converting a tracer keeps its dtype; only host values
            # (Python scalars/lists) take the weak-type promotion path
            if call.args and self.tainted(call.args[0]):
                return
        self._emit(
            "JX007",
            call,
            f"`{short}` with no explicit dtype in jit-reachable code — the "
            "result dtype follows weak-type/x64 promotion (f32 today, f64 "
            "under jax_enable_x64) and can silently drift a compiled "
            "program's dtypes; pass dtype= explicitly",
        )

    def _check_static_args(self, call: ast.Call, dotted: List[str]) -> None:
        static: Set[str] = set()
        target: Optional[FunctionInfo] = None
        for t in self._callee_fns(call):
            key = f"{t.module.modname}.{t.qualname}"
            if key in self.idx.static_args:
                static = self.idx.static_args[key]
                target = t
                break
        if not static or target is None:
            return

        def mutable(expr: ast.AST) -> bool:
            if isinstance(expr, (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)):
                return True
            return False

        for kw in call.keywords:
            if kw.arg in static and mutable(kw.value):
                self._emit(
                    "JX004",
                    call,
                    f"static arg `{kw.arg}` of `{target.name}` gets a "
                    "mutable (unhashable) value — jit static args must be "
                    "hashable; pass a tuple",
                )
        for i, arg in enumerate(call.args):
            if i < len(target.params) and target.params[i] in static and mutable(arg):
                self._emit(
                    "JX004",
                    call,
                    f"static arg `{target.params[i]}` of `{target.name}` gets "
                    "a mutable (unhashable) value — jit static args must be "
                    "hashable; pass a tuple",
                )


def _static_defaults(idx: Index, findings: List[Finding]) -> None:
    """JX004 at the definition: a static param defaulting to a mutable."""
    for key, static in idx.static_args.items():
        fi = idx.by_dotted.get(key)
        if fi is None:
            continue
        args = getattr(fi.node, "args", None)
        if args is None:
            continue
        pos = list(args.posonlyargs) + list(args.args)
        defaults = list(args.defaults)
        for a, d in zip(pos[len(pos) - len(defaults):], defaults):
            if a.arg in static and isinstance(d, (ast.List, ast.Dict, ast.Set)):
                findings.append(
                    Finding(
                        rule="JX004",
                        path=fi.module.relpath,
                        line=d.lineno,
                        col=d.col_offset,
                        func=fi.qualname,
                        message=(
                            f"static param `{a.arg}` defaults to a mutable "
                            "(unhashable) literal — use a tuple"
                        ),
                    )
                )
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is not None and a.arg in static and isinstance(d, (ast.List, ast.Dict, ast.Set)):
                findings.append(
                    Finding(
                        rule="JX004",
                        path=fi.module.relpath,
                        line=d.lineno,
                        col=d.col_offset,
                        func=fi.qualname,
                        message=(
                            f"static param `{a.arg}` defaults to a mutable "
                            "(unhashable) literal — use a tuple"
                        ),
                    )
                )


# ----------------------------------------------------------------- drivers


def package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "baseline.toml")


def iter_package_files(root: Optional[str] = None) -> List[str]:
    root = root or package_root()
    out = []
    for dirpath, _dirnames, filenames in os.walk(root):
        for name in sorted(filenames):
            if name.endswith(".py"):
                out.append(os.path.join(dirpath, name))
    return sorted(out)


def lint_paths(
    paths: Sequence[str],
    baseline: Optional[str] = None,
    pkg_root: Optional[str] = None,
) -> LintResult:
    """Lint explicit files. ``baseline`` is a path to a suppression TOML
    (None = no suppressions)."""
    idx = build_index(list(paths), pkg_root or package_root())
    raw: List[Finding] = []
    for mi in idx.modules.values():
        for fi in mi.functions.values():
            _RuleWalker(idx, fi, raw).walk()
    _static_defaults(idx, raw)
    raw.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    base = load_baseline(baseline).restricted(RULES) if baseline else Baseline()
    findings: List[Finding] = []
    suppressed: List[Tuple[Finding, str]] = []
    excluded: List[Finding] = []
    for f in raw:
        if base.excluded(f):
            excluded.append(f)
            continue
        w = base.waive(f)
        if w is not None:
            suppressed.append((f, w.reason))
        else:
            findings.append(f)
    stale = [w for w in base.waivers if not w.used]
    return LintResult(findings, suppressed, excluded, stale)


def lint_package(baseline: Optional[str] = "default") -> LintResult:
    """Lint every module of the installed package against the committed
    baseline (pass ``baseline=None`` for raw findings)."""
    if baseline == "default":
        baseline = default_baseline_path()
        if not os.path.exists(baseline):
            baseline = None
    return lint_paths(iter_package_files(), baseline=baseline)
