"""The arrows of the package point down: a layer under the entry points
imports nothing that sits above it. Read from the source with `ast`; no
module is imported and jax is never touched."""

import ast
import pathlib

REPO = pathlib.Path(__file__).resolve().parents[1]
PACKAGE = "replication_faster_rcnn_tpu"

LOWER_LAYERS = (
    "train", "analysis", "models", "ops", "targets", "data", "parallel",
    "telemetry", "eval", "quant",
)
# what only an entry point, a script or the benchmark of record may import
ABOVE = (
    f"{PACKAGE}.cli", f"{PACKAGE}.benchmark", "perf", "benchmarks", "bench",
)


def _imported_modules(path):
    """Every module a file imports, at module level or inside a function,
    as absolute dotted names."""
    rel = path.relative_to(REPO).with_suffix("").parts
    package = rel[:-1]  # an __init__.py's package is its own directory
    for node in ast.walk(ast.parse(path.read_text(), filename=str(path))):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package[: len(package) - (node.level - 1)]
                base = ".".join(up + ((base,) if base else ()))
            yield node.lineno, base
            # `from package import benchmark` names a module, not an attribute
            for alias in node.names:
                yield node.lineno, f"{base}.{alias.name}"


def _is_above(module):
    return any(module == top or module.startswith(top + ".") for top in ABOVE)


def test_no_lower_layer_imports_an_entry_point_or_a_benchmark():
    upward = []
    for layer in LOWER_LAYERS:
        files = sorted((REPO / PACKAGE / layer).rglob("*.py"))
        assert files, f"layer {layer!r} has no source: the list above is stale"
        for path in files:
            upward += [
                f"{path.relative_to(REPO)}:{lineno} imports {module}"
                for lineno, module in _imported_modules(path)
                if _is_above(module)
            ]
    assert upward == []
