"""Shared AST call-graph machinery for the analysis/ analyzers.

Extracted from `analysis/jaxlint.py` (PR 5) so that analyzers with
different *roots* can share one resolution engine: jaxlint walks the
graph from every ``jax.jit``/``shard_map`` entry point, threadlint from
every thread entry point (``threading.Thread(target=...)``, ``Thread``
subclass ``run``, HTTP handler methods, pool-submitted callables). The
machinery here is root-agnostic:

* **Module index** — per-module import tables (absolute, relative and
  aliased imports; module-level simple aliases like
  ``_shard_map = jax.shard_map``), every function/method/nested def as a
  :class:`FunctionInfo` with qualname, scope chain and parameter list.
* **Resolution** — a name or attribute expression to the
  :class:`FunctionInfo`\\ (s) it can denote: local scope, module top
  level, imports (including package ``__init__`` re-exports),
  ``self.attr`` bindings recorded in ``ModuleInfo.class_attrs``, factory
  returns (``jax.jit(make_step(...))``), tuple-assignment aliasing and
  ``functools.partial`` wrappers.
* **Edges + reachability** — a call-graph edge set per function that
  also follows function-reference arguments (``lax.scan(body, ...)``,
  ``value_and_grad(loss_fn)``, ``tree_map(keep, ...)``) and flax
  ``.apply(..., method="name")`` dynamic dispatch, plus a BFS helper.

Analyzer-specific discovery (which functions are roots, what donation or
static-arg metadata means) stays in the analyzers; they populate
``Index.roots`` / ``Index.donating`` / ``Index.static_args`` themselves.

The jit/shard_map wrapper names live here (not in jaxlint) because
:func:`_callable_from_expr` must see through ``jax.jit(fn)`` to resolve
the underlying callable — that is a resolution concern, independent of
which rules run over the result.
"""

from __future__ import annotations

import ast
import os
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

# parameters that are static by convention even without an annotation
# (cfg/config are the repo's frozen host dataclasses)
_STATIC_PARAM_NAMES = {"self", "cls", "train", "training", "deterministic", "cfg", "config"}
# annotation heads that mark a parameter host-static
_STATIC_ANNOTATION_HEADS = {"bool", "int", "str", "float", "Sequence", "Tuple", "tuple", "List", "list", "Dict", "dict"}

_JIT_NAMES = {"jax.jit"}
_SHARD_MAP_NAMES = {"jax.shard_map"}
_REMAT_NAMES = {"flax.linen.remat", "nn.remat", "jax.checkpoint", "jax.remat"}


def _annotation_static(ann: Optional[str]) -> bool:
    """True when the annotation names a host-side (non-array) type:
    scalars, host containers, Optional/| None of those, and the repo's
    frozen ``*Config`` dataclasses."""
    if ann is None:
        return False
    ann = ann.strip()
    if ann.startswith("Optional[") and ann.endswith("]"):
        ann = ann[len("Optional["):-1].strip()
    if ann.endswith("| None"):
        ann = ann[: -len("| None")].strip()
    head = ann.split("[", 1)[0].split(".")[-1]
    return head in _STATIC_ANNOTATION_HEADS or head.endswith("Config")


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain; 'self.x' for self attributes."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    if isinstance(node, ast.Call):
        # e.g. tspans.current_tracer().span — dotted of the outer attrs only
        inner = _dotted(node.func)
        if inner is not None and parts:
            return inner + "()." + ".".join(reversed(parts))
    return None


def _ann_str(node: Optional[ast.AST]) -> Optional[str]:
    if node is None:
        return None
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - defensive
        return None


class FunctionInfo:
    def __init__(self, module: "ModuleInfo", qualname: str, node: ast.AST,
                 parent: Optional["FunctionInfo"], cls: Optional[str]):
        self.module = module
        self.qualname = qualname
        self.node = node
        self.parent = parent
        self.cls = cls  # enclosing class name, if a method
        self.nested: Dict[str, FunctionInfo] = {}
        self.jit_reachable = False
        self._returns_tracer: Optional[bool] = None
        self._return_elts: Optional[List[List[Optional[ast.AST]]]] = None
        # static params: annotated host types, conventional names, and any
        # marked by a static_argnums/argnames jit/remat wrapper
        self.params: List[str] = []
        self.static_params: Set[str] = set()
        args = getattr(node, "args", None)
        if args is not None:
            allargs = (
                list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
            )
            for a in allargs:
                self.params.append(a.arg)
                if a.arg in _STATIC_PARAM_NAMES or _annotation_static(
                    _ann_str(a.annotation)
                ):
                    self.static_params.add(a.arg)

    @property
    def name(self) -> str:
        return self.qualname.rsplit(".", 1)[-1]

    def owner_class(self) -> Optional[str]:
        """The class this function belongs to, walking out of nested defs
        (a closure inside a method belongs to the method's class)."""
        fi: Optional[FunctionInfo] = self
        while fi is not None:
            if fi.cls is not None:
                return fi.cls
            fi = fi.parent
        return None

    def returns(self) -> List[List[Optional[ast.AST]]]:
        """Per-return list of element exprs ([expr] or tuple elements)."""
        if self._return_elts is None:
            elts: List[List[Optional[ast.AST]]] = []
            body = getattr(self.node, "body", [])
            for stmt in ast.walk(ast.Module(body=list(body), type_ignores=[])):
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                    continue  # walk() still descends; nested returns filtered below
            for stmt in _returns_of(self.node):
                v = stmt.value
                if isinstance(v, ast.Tuple):
                    elts.append(list(v.elts))
                else:
                    elts.append([v])
            self._return_elts = elts
        return self._return_elts


def _returns_of(fn_node: ast.AST) -> List[ast.Return]:
    """Return statements belonging to fn_node itself (not nested defs)."""
    out: List[ast.Return] = []

    def visit(stmts):
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                continue
            if isinstance(s, ast.Return):
                out.append(s)
            for attr in ("body", "orelse", "finalbody"):
                visit(getattr(s, attr, []))
            for h in getattr(s, "handlers", []):
                visit(h.body)

    visit(getattr(fn_node, "body", []))
    return out


class ModuleInfo:
    def __init__(self, path: str, relpath: str, modname: str, tree: ast.Module):
        self.path = path
        self.relpath = relpath
        self.modname = modname  # dotted, e.g. pkg.train.trainer
        self.tree = tree
        self.imports: Dict[str, str] = {}  # local name -> dotted target
        self.functions: Dict[str, FunctionInfo] = {}  # qualname -> info
        self.toplevel: Dict[str, FunctionInfo] = {}
        # class name -> attr name -> list of resolution dicts
        self.class_attrs: Dict[str, Dict[str, List[Dict[str, Any]]]] = {}
        # class name -> list of base-class dotted names (import-resolved)
        self.class_bases: Dict[str, List[str]] = {}


class Index:
    """Cross-module symbol index + call graph + root reachability."""

    def __init__(self) -> None:
        self.modules: Dict[str, ModuleInfo] = {}  # modname -> info
        self.by_dotted: Dict[str, FunctionInfo] = {}  # pkg.mod.qualname -> fn
        self.methods_by_name: Dict[str, List[FunctionInfo]] = {}
        self.edges: Dict[FunctionInfo, Set[FunctionInfo]] = {}
        self.roots: Set[FunctionInfo] = set()
        # donating callables: identifier -> donated positional indices.
        # identifiers: "Class.attr" for self-attrs, "mod.qual" for locals
        self.donating: Dict[str, Tuple[int, ...]] = {}
        # static-arg callables: dotted fn -> static param names
        self.static_args: Dict[str, Set[str]] = {}
        # memo caches (also cycle-breakers for mutually-recursive factories)
        self._returned_memo: Dict[Any, Tuple[List[FunctionInfo], Optional[Tuple[int, ...]]]] = {}
        self._aliases_memo: Dict["FunctionInfo", Dict[str, List[Any]]] = {}


def _module_name(path: str, package_root: str) -> str:
    rel = os.path.relpath(path, os.path.dirname(package_root))
    mod = rel[:-3] if rel.endswith(".py") else rel
    mod = mod.replace(os.sep, ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def _collect_imports(mi: ModuleInfo) -> None:
    pkg_parts = mi.modname.split(".")
    for node in ast.walk(mi.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                mi.imports[alias.asname or alias.name.split(".")[0]] = (
                    alias.name if alias.asname else alias.name.split(".")[0]
                )
                if alias.asname:
                    mi.imports[alias.asname] = alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.level:  # relative import
                base = pkg_parts[: -(node.level)]
                mod = ".".join(base + ([node.module] if node.module else []))
            else:
                mod = node.module or ""
            for alias in node.names:
                mi.imports[alias.asname or alias.name] = f"{mod}.{alias.name}"
    # module-level simple aliases (e.g. `_shard_map = jax.shard_map`)
    for stmt in mi.tree.body:
        if isinstance(stmt, (ast.If, ast.Try)):
            bodies = [stmt.body] + [getattr(stmt, "orelse", [])]
            for b in bodies:
                for s in b:
                    _maybe_module_alias(mi, s)
        else:
            _maybe_module_alias(mi, stmt)


def _maybe_module_alias(mi: ModuleInfo, stmt: ast.stmt) -> None:
    if (
        isinstance(stmt, ast.Assign)
        and len(stmt.targets) == 1
        and isinstance(stmt.targets[0], ast.Name)
    ):
        d = _dotted(stmt.value)
        if d is not None:
            root = d.split(".")[0]
            resolved = mi.imports.get(root)
            if resolved is not None:
                d = resolved + d[len(root):]
            mi.imports.setdefault(stmt.targets[0].id, d)


def _collect_functions(mi: ModuleInfo) -> None:
    def visit(stmts, prefix: str, parent: Optional[FunctionInfo], cls: Optional[str]):
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{s.name}" if prefix else s.name
                fi = FunctionInfo(mi, qual, s, parent, cls)
                mi.functions[qual] = fi
                if parent is None and cls is None:
                    mi.toplevel[s.name] = fi
                elif parent is not None:
                    parent.nested[s.name] = fi
                visit(s.body, qual + ".", fi, None)
            elif isinstance(s, ast.ClassDef):
                mi.class_bases[s.name] = [
                    _resolve_dotted_prefix(mi, d)
                    for d in (_dotted(b) for b in s.bases)
                    if d is not None
                ]
                visit(s.body, f"{prefix}{s.name}.", None, s.name)
            elif isinstance(s, (ast.If, ast.Try, ast.With, ast.For, ast.While)):
                for attr in ("body", "orelse", "finalbody"):
                    visit(getattr(s, attr, []), prefix, parent, cls)
                for h in getattr(s, "handlers", []):
                    visit(h.body, prefix, parent, cls)

    visit(mi.tree.body, "", None, None)


def parse_modules(paths: Sequence[str], package_root: str) -> Index:
    """Parse ``paths`` into an :class:`Index` with modules, the dotted
    symbol table, the method-name table and resolved ``self.attr``
    bindings — everything except roots/edges, which are the analyzer's
    job (call :func:`build_edges` after populating ``idx.roots``)."""
    idx = Index()
    repo_root = os.path.dirname(os.path.abspath(package_root))
    for path in paths:
        with open(path) as f:
            src = f.read()
        tree = ast.parse(src, filename=path)
        ap = os.path.abspath(path)
        if ap.startswith(repo_root + os.sep):
            rel = os.path.relpath(ap, repo_root)
        else:
            rel = os.path.basename(ap)
        mi = ModuleInfo(ap, rel.replace(os.sep, "/"), _module_name(ap, package_root), tree)
        _collect_imports(mi)
        _collect_functions(mi)
        idx.modules[mi.modname] = mi
        for qual, fi in mi.functions.items():
            idx.by_dotted[f"{mi.modname}.{qual}"] = fi
            idx.methods_by_name.setdefault(fi.name, []).append(fi)
    _resolve_class_attrs(idx)
    return idx


# ------------------------------------------------------------- resolution


def _resolve_dotted_prefix(mi: ModuleInfo, dotted: str) -> str:
    """Substitute the leading import alias in a dotted chain."""
    root, _, rest = dotted.partition(".")
    target = mi.imports.get(root)
    if target is None:
        return dotted
    return f"{target}.{rest}" if rest else target


def _resolve_name(
    idx: Index, fn: Optional[FunctionInfo], mi: ModuleInfo, name: str,
    aliases: Optional[Dict[str, List[Any]]] = None, _depth: int = 0,
) -> List[Any]:
    """Resolve a bare name to FunctionInfo(s) or a dotted external string."""
    if _depth > 6:
        return []
    if aliases and name in aliases:
        out: List[Any] = []
        for tgt in aliases[name]:
            if isinstance(tgt, str):
                out.extend(
                    _resolve_name(idx, fn, mi, tgt, aliases=None, _depth=_depth + 1)
                )
            else:
                out.append(tgt)
        if out:
            return out
    scope = fn
    while scope is not None:
        if name in scope.nested:
            return [scope.nested[name]]
        if scope.cls is None and scope.parent is None and name == scope.name:
            break
        scope = scope.parent
    if name in mi.toplevel:
        return [mi.toplevel[name]]
    if name in mi.imports:
        dotted = mi.imports[name]
        target = idx.by_dotted.get(dotted)
        if target is not None:
            return [target]
        # maybe a re-export through an __init__: try "<mod>.<name>" tails
        for modname, m in idx.modules.items():
            if dotted == f"{modname}.{name}" and name in m.toplevel:
                return [m.toplevel[name]]
        # package __init__ re-export: resolve one indirection
        mod_part = dotted.rsplit(".", 1)[0]
        m = idx.modules.get(mod_part)
        if m is not None and name in m.imports:
            return _resolve_name(idx, None, m, name, _depth=_depth + 1)
        return [dotted]
    return []


def _resolve_callee(
    idx: Index, fn: Optional[FunctionInfo], mi: ModuleInfo, node: ast.AST,
    aliases: Optional[Dict[str, List[Any]]] = None,
) -> List[Any]:
    """Resolve a call target expr to FunctionInfo(s) and/or dotted strings."""
    if isinstance(node, ast.Name):
        return _resolve_name(idx, fn, mi, node.id, aliases)
    if isinstance(node, ast.Attribute):
        d = _dotted(node)
        if d is None:
            return []
        if d.startswith("self.") and fn is not None and fn.cls is not None:
            entries = mi.class_attrs.get(fn.cls, {}).get(d[len("self."):], [])
            out = []
            for e in entries:
                if e.get("func") is not None:
                    out.append(e["func"])
            return out or [d]
        resolved = _resolve_dotted_prefix(mi, d)
        target = idx.by_dotted.get(resolved)
        if target is not None:
            return [target]
        # a method path like pkg.mod.Class.method
        return [resolved]
    return []


def _callable_from_expr(
    idx: Index, fn: Optional[FunctionInfo], mi: ModuleInfo, expr: ast.AST,
    aliases: Optional[Dict[str, List[Any]]] = None, _depth: int = 0,
) -> Tuple[List[FunctionInfo], Optional[Tuple[int, ...]]]:
    """(functions, donate) for an expr that evaluates to a callable.

    Handles: a bare function reference, ``jax.jit(fn, ...)``,
    ``shard_map(fn, ...)``, ``partial(jax.jit, ...)`` decorators, a
    factory call whose return is a nested def, and aliases of any of
    those. ``donate`` is the donate_argnums tuple if a jit wrapper in the
    chain donates.
    """
    if _depth > 6:
        return [], None
    donate: Optional[Tuple[int, ...]] = None
    if isinstance(expr, (ast.Name, ast.Attribute)):
        targets = _resolve_callee(idx, fn, mi, expr, aliases)
        return [t for t in targets if isinstance(t, FunctionInfo)], None
    if isinstance(expr, ast.Call):
        callee = _resolve_callee(idx, fn, mi, expr.func, aliases)
        dotted = [t for t in callee if isinstance(t, str)]
        fis = [t for t in callee if isinstance(t, FunctionInfo)]
        if any(d in _JIT_NAMES for d in dotted):
            for kw in expr.keywords:
                if kw.arg == "donate_argnums":
                    donate = _int_tuple(kw.value)
            if expr.args:
                inner, inner_donate = _callable_from_expr(
                    idx, fn, mi, expr.args[0], aliases, _depth + 1
                )
                return inner, donate if donate is not None else inner_donate
            return [], donate
        if any(d in _SHARD_MAP_NAMES for d in dotted):
            if expr.args:
                return (
                    _callable_from_expr(idx, fn, mi, expr.args[0], aliases, _depth + 1)[0],
                    None,
                )
            return [], None
        if any(d.endswith("functools.partial") or d == "partial" for d in dotted):
            if expr.args:
                return _callable_from_expr(
                    idx, fn, mi, expr.args[0], aliases, _depth + 1
                )
            return [], None
        # factory call: follow the factory's returned function(s)
        out: List[FunctionInfo] = []
        for factory in fis:
            rf, rd = _returned_functions(idx, factory, index=None)
            out.extend(rf)
            donate = donate if donate is not None else rd
        return out, donate
    return [], None


def _int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                vals.append(e.value)
        return tuple(vals)
    return None


def _str_tuple(node: ast.AST) -> Tuple[str, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(
            e.value
            for e in node.elts
            if isinstance(e, ast.Constant) and isinstance(e.value, str)
        )
    return ()


def _returned_functions(
    idx: Index, factory: FunctionInfo, index: Optional[int]
) -> Tuple[List[FunctionInfo], Optional[Tuple[int, ...]]]:
    """Functions a factory returns (element ``index`` of tuple returns,
    or any element when None); plus donate info from a jit wrapper."""
    memo_key = (factory, index)
    if memo_key in idx._returned_memo:
        return idx._returned_memo[memo_key]
    # seed with the empty answer to cut cycles (mutually-recursive
    # factories resolve to nothing rather than recursing forever)
    idx._returned_memo[memo_key] = ([], None)
    out: List[FunctionInfo] = []
    donate: Optional[Tuple[int, ...]] = None
    aliases = _local_aliases(idx, factory)
    for elts in factory.returns():
        chosen = elts if index is None else (
            [elts[index]] if index < len(elts) else []
        )
        for e in chosen:
            if e is None:
                continue
            fis, d = _callable_from_expr(
                idx, factory, factory.module, e, aliases, _depth=1
            )
            out.extend(fis)
            if d is not None:
                donate = d
    idx._returned_memo[memo_key] = (out, donate)
    return out, donate


def _local_aliases(idx: Index, fn: FunctionInfo) -> Dict[str, List[Any]]:
    """name -> [FunctionInfo|name] for simple aliasing assignments inside
    ``fn`` (incl. tuple-assign pairs like ``body, spec = f, P(...)``)."""
    if fn in idx._aliases_memo:
        return idx._aliases_memo[fn]
    aliases: Dict[str, List[Any]] = {}
    idx._aliases_memo[fn] = aliases  # pre-register to cut cycles

    def add(name: str, value: ast.AST) -> None:
        if isinstance(value, ast.Name):
            aliases.setdefault(name, []).append(value.id)
        elif isinstance(value, (ast.Attribute, ast.Call)):
            fis, _ = _callable_from_expr(idx, fn, fn.module, value, None)
            for f in fis:
                aliases.setdefault(name, []).append(f)

    for stmt in ast.walk(fn.node):
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
            tgt, val = stmt.targets[0], stmt.value
            if isinstance(tgt, ast.Name):
                add(tgt.id, val)
            elif (
                isinstance(tgt, ast.Tuple)
                and isinstance(val, ast.Tuple)
                and len(tgt.elts) == len(val.elts)
            ):
                for t, v in zip(tgt.elts, val.elts):
                    if isinstance(t, ast.Name):
                        add(t.id, v)
    return aliases


def _resolve_class_attrs(idx: Index) -> None:
    """Fill ModuleInfo.class_attrs: ``self.x = ...`` bindings resolved to
    functions where possible (jit wrappers recording donate_argnums)."""
    for mi in idx.modules.values():
        for qual, fi in mi.functions.items():
            if fi.cls is None:
                continue
            table = mi.class_attrs.setdefault(fi.cls, {})
            for stmt in ast.walk(fi.node):
                if not isinstance(stmt, ast.Assign):
                    continue
                targets = stmt.targets
                if len(targets) != 1:
                    continue
                tgt = targets[0]
                if isinstance(tgt, ast.Attribute) and isinstance(tgt.value, ast.Name) and tgt.value.id == "self":
                    fis, donate = _callable_from_expr(idx, fi, mi, stmt.value)
                    entry: Dict[str, Any] = {
                        "func": fis[0] if fis else None,
                        "funcs": fis,
                        "donate": donate,
                    }
                    # value may instead be a tracer-returning call result
                    table.setdefault(tgt.attr, []).append(entry)
                    if donate:
                        idx.donating[f"{fi.cls}.{tgt.attr}"] = donate
                elif isinstance(tgt, ast.Tuple) and isinstance(stmt.value, ast.Call):
                    # self.a, self.b = factory(...)
                    callee = _resolve_callee(idx, fi, mi, stmt.value.func)
                    factories = [t for t in callee if isinstance(t, FunctionInfo)]
                    for i, t in enumerate(tgt.elts):
                        if not (
                            isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"
                        ):
                            continue
                        fis: List[FunctionInfo] = []
                        donate = None
                        for fac in factories:
                            rf, rd = _returned_functions(idx, fac, index=i)
                            fis.extend(rf)
                            donate = donate if donate is not None else rd
                        table.setdefault(t.attr, []).append(
                            {"func": fis[0] if fis else None, "funcs": fis, "donate": donate}
                        )
                        if donate:
                            idx.donating[f"{fi.cls}.{t.attr}"] = donate


# ------------------------------------------------------- edges + reachability


def build_edges(idx: Index) -> None:
    """Populate ``idx.edges``: direct calls, function-reference arguments
    (``lax.scan(body, ...)``, ``value_and_grad(loss_fn)``), flax
    ``X.apply(..., method="name")`` dynamic dispatch, and nested defs."""
    for mi in idx.modules.values():
        for fi in mi.functions.values():
            aliases = _local_aliases(idx, fi)
            edges = idx.edges.setdefault(fi, set())
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                for t in _resolve_callee(idx, fi, mi, node.func, aliases):
                    if isinstance(t, FunctionInfo):
                        edges.add(t)
                # function-reference arguments: lax.scan(body, ...),
                # value_and_grad(loss_fn), tree_map(keep, ...)
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(arg, ast.Name):
                        for t in _resolve_name(idx, fi, mi, arg.id, aliases):
                            if isinstance(t, FunctionInfo):
                                edges.add(t)
                # flax dynamic dispatch: X.apply(..., method="name")
                fd = _dotted(node.func)
                if fd is not None and fd.endswith(".apply"):
                    method = None
                    for kw in node.keywords:
                        if kw.arg == "method" and isinstance(kw.value, ast.Constant):
                            method = kw.value.value
                    for m in idx.methods_by_name.get(method or "__call__", []):
                        if m.cls is not None:
                            edges.add(m)
            # nested defs are reachable from their parent by construction
            edges.update(fi.nested.values())


def reachable_from(idx: Index, roots) -> Set[FunctionInfo]:
    """BFS the (pre-built) call graph from ``roots``; returns the closure
    including the roots themselves."""
    seen: Set[FunctionInfo] = set()
    frontier = list(roots)
    while frontier:
        f = frontier.pop()
        if f in seen:
            continue
        seen.add(f)
        frontier.extend(idx.edges.get(f, ()))
    return seen
