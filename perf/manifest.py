"""`BENCHMARK.json` and the data files it names: loading, and the checks a
manifest must pass before any run (names, units, which cell reports what).

The harness finds everything by name from here: a cell's configuration
file (`configs[].file`); beside it (`<dir of that file>/../`) its traffic mix
(`mixes/<traffic>.json`); and, beside it first and else with the harness
(`beside`), each per-layer metric's reader (`metrics/<name up to its first
dot>.py`) and the two modules the configuration's file names: `reference`,
which owns all that is the model's, and `feed_reference`, all that is the
data's (`references/<name>.py`; perf/harness.py lists what each states and
what the harness asks of the program). Adding a configuration, a mix, a
cell, a metric, or another kind of model with its data therefore adds files
and manifest entries and edits nothing. A configuration may size its
parameters to the chip at 16 B each: the comparison adds none (the rule is
in perf/harness.py, the reckoning in perf/reckon_memory.py).
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state_size", "proj", "head_dim", "expansion")


class ManifestError(ValueError):
    pass


def data_dir_of(config_file: str) -> str:
    """Where a configuration's mixes, readers and references sit: beside `configs/`."""
    return os.path.dirname(os.path.dirname(os.path.abspath(config_file)))


def beside(data_dir: str, kind: str, stem: str) -> str:
    """`<kind>/<stem>.py` beside a cell's data files, else with the harness
    (the rehearsal cells read through the harness's own)."""
    own = os.path.join(data_dir, kind, stem + ".py")
    return own if os.path.exists(own) else os.path.join(os.path.dirname(os.path.abspath(__file__)), kind, stem + ".py")


def load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _line(s: Any, what: str, errs: List[str]) -> None:
    if not isinstance(s, str) or not 1 <= len(s) <= 200 or "\n" in s or "\t" in s:
        errs.append(f"{what}: want 1-200 characters on one line, got {s!r}")


def _name(s: Any, what: str, errs: List[str]) -> None:
    if not isinstance(s, str) or not NAME_RE.match(s):
        errs.append(f"{what}: bad name {s!r}")


def _keys(entry: Dict[str, Any], want: set, optional: set, what: str, errs: List[str]) -> None:
    got = set(entry)
    if not want <= got or not got <= want | optional:
        errs.append(f"{what}: keys {sorted(got)} != {sorted(want)} (+{sorted(optional)})")


def validate(m: Dict[str, Any]) -> List[str]:
    """Every breach of the manifest's contract, as text; empty when sound."""
    errs: List[str] = []
    if set(m) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return errs
    if len(json.dumps(m)) > 64 * 1024:
        errs.append("manifest over 64 KiB")
    paths = m["paths"]
    if not 1 <= len(paths) <= 16:
        errs.append("paths: want 1-16 directories")
    for p in paths:
        if not PATH_RE.match(p) or p.startswith("/") or ".." in p.split("/"):
            errs.append(f"paths: bad path {p!r}")
    cmd = m["command"]
    if not isinstance(cmd, list) or not 1 <= len(cmd) <= 32:
        errs.append("command: want a list of 1-32 strings")
    for word in cmd:
        _line(word, "command word", errs)
        if isinstance(word, str) and (word.startswith("/") or ".." in word.split("/")):
            errs.append(f"command: {word!r} leaves the repo")
    if not isinstance(m["run_seconds"], int) or not 1 <= m["run_seconds"] <= 51:
        errs.append("run_seconds: want a whole number from 1 to 51")

    def under_paths(f: str) -> bool:
        return any(f == p or f.startswith(p.rstrip("/") + "/") for p in paths)

    configs: Dict[str, Dict[str, Any]] = {}
    files = set()
    if not 1 <= len(m["configs"]) <= 24:
        errs.append("configs: want 1-24")
    for c in m["configs"]:
        _keys(c, {"name", "source", "file", "reduced", "why"}, set(), f"config {c.get('name')}", errs)
        _name(c.get("name"), "config name", errs)
        _line(c.get("source"), "config source", errs)
        _line(c.get("why"), "config why", errs)
        f = c.get("file", "")
        if not PATH_RE.match(f) or not under_paths(f) or f in files:
            errs.append(f"config {c.get('name')}: file {f!r} not under paths, or used twice")
        files.add(f)
        red = c.get("reduced", [])
        if len(red) > 16:
            errs.append(f"config {c.get('name')}: over 16 reduced keys")
        for key in red:
            _name(key, "reduced key", errs)
            if key.endswith(("_dim", "_rank")) or any(wd in key for wd in WIDTH_WORDS):
                errs.append(f"config {c.get('name')}: reduced may not name a width ({key})")
        if c.get("name") in configs:
            errs.append(f"config name {c.get('name')} twice")
        configs[c.get("name")] = c

    cells: Dict[str, Dict[str, Any]] = {}
    pairs = set()
    if not 1 <= len(m["workloads"]) <= 24:
        errs.append("workloads: want 1-24")
    for w in m["workloads"]:
        _keys(w, {"name", "config", "traffic", "chips", "why"}, set(), f"cell {w.get('name')}", errs)
        for key in ("name", "config", "traffic"):
            _name(w.get(key), f"cell {key}", errs)
        _line(w.get("why"), "cell why", errs)
        if w.get("config") not in configs:
            errs.append(f"cell {w.get('name')}: unknown config {w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            errs.append(f"cell {w.get('name')}: chips must be 1 or 4")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            errs.append(f"cell {w.get('name')}: config and traffic pair appears twice")
        pairs.add(pair)
        if w.get("name") in cells:
            errs.append(f"cell name {w.get('name')} twice")
        cells[w.get("name")] = w
    for name in configs:
        if not any(w.get("config") == name for w in m["workloads"]):
            errs.append(f"config {name} has no cell")
    four = sum(1 for w in m["workloads"] if w.get("chips") == 4)
    if four > max(1, len(m["workloads"]) // 4):
        errs.append(f"{four} four-chip cells: at most {max(1, len(m['workloads']) // 4)}")

    metric_names = set()
    e2e: Dict[str, Dict[str, Any]] = {}
    if not 1 <= len(m["end_to_end"]) <= 16:
        errs.append("end_to_end: want 1-16")
    for e in m["end_to_end"]:
        what = f"end_to_end {e.get('name')}"
        _keys(e, {"name", "unit", "better", "bound", "source"}, {"workloads"}, what, errs)
        _name(e.get("name"), what, errs)
        if not isinstance(e.get("unit"), str) or not UNIT_RE.match(e.get("unit", "")):
            errs.append(f"{what}: bad unit {e.get('unit')!r}")
        if e.get("better") not in ("lower", "higher"):
            errs.append(f"{what}: better must be lower or higher")
        if e.get("source") not in ("host_clock", "device_trace"):
            errs.append(f"{what}: source must be host_clock or device_trace")
        b = e.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.1:
            errs.append(f"{what}: bound {b!r} outside [0.01, 0.1]")
        for cell in e.get("workloads", []):
            if cell not in cells:
                errs.append(f"{what}: unknown cell {cell!r}")
        if e.get("name") in metric_names:
            errs.append(f"metric name {e.get('name')} twice")
        metric_names.add(e.get("name"))
        e2e[e.get("name")] = e
    if "setup_s" not in e2e:
        errs.append("end_to_end lacks setup_s")

    def reports(metric: Dict[str, Any]) -> set:
        return set(metric.get("workloads", cells))

    layers: Dict[str, str] = {}
    if not 1 <= len(m["per_layer"]) <= 128:
        errs.append("per_layer: want 1-128")
    for p in m["per_layer"]:
        what = f"per_layer {p.get('name')}"
        _keys(p, {"name", "unit", "better", "source", "layer", "moves"}, {"workloads"}, what, errs)
        _name(p.get("name"), what, errs)
        if not isinstance(p.get("unit"), str) or not UNIT_RE.match(p.get("unit", "")):
            errs.append(f"{what}: bad unit {p.get('unit')!r}")
        if p.get("better") not in ("lower", "higher"):
            errs.append(f"{what}: better must be lower or higher")
        if p.get("source") not in SOURCES:
            errs.append(f"{what}: unknown source {p.get('source')!r}")
        _line(p.get("layer"), f"{what} layer", errs)
        if p.get("name", "").endswith("_roofline") and p.get("unit") != "%":
            errs.append(f"{what}: a roofline share has the unit %")
        moved = e2e.get(p.get("moves"))
        if moved is None:
            errs.append(f"{what}: moves unknown end-to-end metric {p.get('moves')!r}")
        else:
            for cell in reports(p):
                if cell not in cells:
                    errs.append(f"{what}: unknown cell {cell!r}")
                elif cell not in reports(moved):
                    errs.append(f"{what}: cell {cell} does not report {p.get('moves')}")
        if p.get("name") in metric_names:
            errs.append(f"metric name {p.get('name')} twice")
        metric_names.add(p.get("name"))
        layers.setdefault(p.get("layer", "").lower(), p.get("layer", ""))
        if layers[p.get("layer", "").lower()] != p.get("layer"):
            errs.append(f"{what}: layer spelt two ways")
    for cell in cells:
        others = [e for e in m["end_to_end"] if e.get("name") != "setup_s" and cell in reports(e)]
        if not others:
            errs.append(f"cell {cell}: reports no end-to-end metric besides setup_s")
        if not any(cell in reports(p) for p in m["per_layer"]):
            errs.append(f"cell {cell}: reports no per-layer metric")
    return errs


class Cell:
    """One cell with the files it names, resolved against the checkout."""

    def __init__(self, root: str, manifest_path: str, workload: str) -> None:
        self.root = root
        self.manifest = load(manifest_path)
        errs = validate(self.manifest)
        if errs:
            raise ManifestError("; ".join(errs))
        by_name = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in by_name:
            raise ManifestError(f"no cell {workload!r}; cells: {sorted(by_name)}")
        self.cell = by_name[workload]
        self.name = workload
        self.chips = int(self.cell["chips"])
        cfg_entry = next(c for c in self.manifest["configs"] if c["name"] == self.cell["config"])
        self.config_entry = cfg_entry
        self.config = load(os.path.join(root, cfg_entry["file"]))
        self.data_dir = data_dir_of(os.path.join(root, cfg_entry["file"]))
        self.mix = load(os.path.join(self.data_dir, "mixes", self.cell["traffic"] + ".json"))

    def _reported(self, metrics: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        return [m for m in metrics if self.name in m.get("workloads", [self.name])]

    @property
    def end_to_end(self) -> List[Dict[str, Any]]:
        return self._reported(self.manifest["end_to_end"])

    @property
    def per_layer(self) -> List[Dict[str, Any]]:
        return self._reported(self.manifest["per_layer"])

    def reader_path(self, metric: str) -> str:
        """The metric's reader. A quantity split by the end-to-end metric it
        moves (`dispatch_ms.fed`, `dispatch_ms.resident`) is read by the one
        reader of its first part."""
        return beside(self.data_dir, "metrics", metric.split(".", 1)[0])
