"""One traced run cut from inside: the step's device time by the stage scopes
of the step program, and the chip's idle time by the program's own spans.

The harness hands a reader `ctx` and no path, and `ctx["trace"]` holds sums,
not operations. So this module finds the run's directory from the tracer's
first event (`telemetry/open`, whose `args` hold the telemetry directory; the
run's `profile/`, `step_hlo.txt` and `stage_scope.txt` lie beside it: the
last holds the prefix of the program's stage scopes, which the harness
writes there from the configuration's reference module), loads the
`.xplane.pb` once per run (kept in `ctx`) and the `op_name` of every
instruction of the compiled step, and from there is arithmetic on (name, start_ns, duration_ns,
stats) tuples as `xtrace` is, so a hand-built trace tests it:

- each device nanosecond goes to the innermost operation running then (a
  `while` and the fusions inside it never both count), and that operation to
  the LAST stage scope (`<prefix>[a-z_]+`) in its `op_name`: backward where the path holds
  `transpose(`, `unscoped` where it holds no scope. Stage sums + unscoped =
  the chip's busy time;
- each idle gap of the first chip goes to the innermost program span that
  overlaps it, by the xplane's clock alone; the rest is unattributed.

A program without the scopes, the mirrored spans or `telemetry/open` (any
commit before they came) gives nothing to read: `of(ctx)` is then None and
every reader built on it returns None.

    python3 perf/stagecut.py <run directory>    # the stage and per-op table
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perf import xtrace

Event = xtrace.Event
Interval = xtrace.Interval

OPEN_EVENT = "telemetry/open"
SCOPE_FILE = "stage_scope.txt"
UNSCOPED = "unscoped"
# the result type may be a tuple with spaces; layouts hold none
INSTRUCTION_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
TABLE_SHARE = 0.9  # of the busy time, listed operation by operation by `table`
CPU_EXECUTOR = "tf_XLAPjRtCpuClient"  # a rehearsal's stand-in for a device, as in xtrace


# ------------------------------------------------------------- the files


def run_dir(spans: Sequence[Dict[str, Any]]) -> Optional[str]:
    """The directory the traced run wrote into, from the tracer's first
    event; None where the program's tracer does not say."""
    first = spans[0] if spans else {}
    where = (first.get("args") or {}).get("dir")
    if first.get("name") != OPEN_EVENT or not where:
        return None
    return os.path.dirname(where)


def span_names(spans: Iterable[Dict[str, Any]]) -> set:
    return {e["name"] for e in spans if e.get("ph") == "X"}


def load_origin(hlo_text: str) -> Dict[str, str]:
    """instruction name -> `op_name` metadata, from the compiled module's
    text."""
    origin: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION_RE.match(line)
        if not m:
            continue
        where = re.search(r'op_name="([^"]*)"', line)
        if where:
            origin[m.group(1)] = where.group(1)
    return origin


def load_trace(path: str, names: set) -> Dict[str, Any]:
    """{"devices": {plane: [Event]}, "host": [Event]}: the operations of each
    device plane's op line, and every host event, on whatever thread, whose
    name is one of `names`. Where the trace holds no device plane (a CPU
    rehearsal) XLA:CPU's executor threads stand in for one, so that the
    rehearsal walks the same code."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    executor: List[Event] = []
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(xtrace.DEVICE_PREFIX):
            devices[plane.name] = [
                (ev.name, float(ev.start_ns), float(ev.duration_ns), {})
                for line in plane.lines if line.name == xtrace.OP_LINE
                for ev in line.events
            ]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                is_executor = line.name.startswith(CPU_EXECUTOR)
                for ev in line.events:
                    if ev.name in names:
                        host.append((ev.name, float(ev.start_ns), float(ev.duration_ns), {}))
                    elif is_executor and ev.duration_ns > 0:
                        executor.append((ev.name, float(ev.start_ns), float(ev.duration_ns), {}))
    if not any(devices.values()) and executor:
        devices = {"rehearsal:cpu": executor}
    return {"devices": devices, "host": host}


# ------------------------------------------------------- the device side


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's self time: the nanoseconds during which it is the
    innermost (latest started) of the events running. The sum is the union
    of the intervals, however the events nest or overlap."""
    order = sorted(
        (i for i in range(len(events)) if events[i][2] > 0),
        key=lambda i: (events[i][1], -events[i][2]),
    )
    own = [0.0] * len(events)
    stack: List[Tuple[float, int]] = []  # (end, index), innermost last
    at = 0.0

    def run_until(t: float) -> None:
        # the time from `at` to `t` goes to whatever is innermost then;
        # what ends on the way is closed
        nonlocal at
        while stack and at < t:
            end, i = stack[-1]
            upto = min(end, t)
            if upto > at:
                own[i] += upto - at
                at = upto
            if end > t:
                break
            stack.pop()
        at = max(at, t)

    for i in order:
        run_until(events[i][1])
        stack.append((events[i][1] + events[i][2], i))
    if stack:
        run_until(max(end for end, _ in stack))
    return own


def scope_re(prefix: str) -> "re.Pattern[str]":
    return re.compile(re.escape(prefix) + r"[a-z_]+")


def stage_of(path: str, scopes: "re.Pattern[str]") -> Tuple[str, bool]:
    """(the last stage scope of an `op_name` path, or "unscoped"; whether
    the path lies in the backward pass)."""
    found = scopes.findall(path)
    return (found[-1] if found else UNSCOPED), "transpose(" in path


def cut_device(planes: Dict[str, List[Event]], origin: Dict[str, str], scopes: "re.Pattern[str]") -> Dict[str, Any]:
    """Self time of every operation, summed over the chips, by stage and
    direction, and by operation."""
    stage_ns: Dict[str, Dict[str, float]] = {}
    ops: Dict[str, Dict[str, Any]] = {}
    busy = 0.0
    for events in planes.values():
        for (name, _, _, _), own in zip(events, self_times(events)):
            if own <= 0:
                continue
            busy += own
            path = origin.get(xtrace.op_name(name), "")
            stage, backward = stage_of(path, scopes)
            side = "backward" if backward else "forward"
            by_side = stage_ns.setdefault(stage, {"forward": 0.0, "backward": 0.0})
            by_side[side] += own
            row = ops.setdefault(
                xtrace.short_name(name), {"stage": stage, "side": side, "op_name": path, "ns": 0.0}
            )
            row["ns"] += own
    return {"busy_ns": busy, "stage_ns": stage_ns, "ops": ops}


# --------------------------------------------------------- the host side


def attribute_idle(idle: Sequence[Interval], spans: Sequence[Event]) -> Dict[str, float]:
    """Idle nanoseconds by the innermost span overlapping them: of the spans
    that cover a moment, on whatever thread, the one that started last.
    What no span covers goes to "unattributed"."""
    spans = sorted(((s, s + d, name) for name, s, d, _ in spans if d > 0), key=lambda x: x[0])
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    out: Dict[str, float] = {}
    for g0, g1 in idle:
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_left(starts, g1)
        over = [sp for sp in spans[lo:hi] if sp[1] > g0]
        cuts = sorted({g0, g1, *(t for s, e, _ in over for t in (s, e) if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            covering = [sp for sp in over if sp[0] <= a and sp[1] >= b]
            name = max(covering, key=lambda sp: (sp[0], -sp[1]))[2] if covering else "unattributed"
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def cut_idle(planes: Dict[str, List[Event]], host: Sequence[Event]) -> Dict[str, Any]:
    """The first chip's idle gaps inside the traced window (first to last
    device operation on any chip, as `xtrace.reduce` has it), by span."""
    lo = min(s for ops in planes.values() for _, s, _, _ in ops)
    hi = max(s + d for ops in planes.values() for _, s, d, _ in ops)
    first = planes[sorted(planes)[0]]
    idle = xtrace.gaps(xtrace.union(xtrace.intervals_of(first)), (lo, hi))
    by_span = attribute_idle(idle, host)
    return {"idle_ns": xtrace.total(idle), "by_span": by_span}


# ------------------------------------------------------------ one run


def cut_run(where: str, names: set) -> Optional[Dict[str, Any]]:
    """Everything the readers start from, for the run that wrote `where`;
    None where the run left no trace or no compiled module there."""
    try:
        xplane = xtrace.find_xplane(os.path.join(where, "profile"))
        with open(os.path.join(where, "step_hlo.txt")) as f:
            origin = load_origin(f.read())
        with open(os.path.join(where, SCOPE_FILE)) as f:
            scopes = scope_re(f.read().strip())
    except FileNotFoundError:
        return None
    trace = load_trace(xplane, names)
    planes = {k: v for k, v in trace["devices"].items() if v}
    if not planes:
        return None
    out = cut_device(planes, origin, scopes)
    out.update(cut_idle(planes, trace["host"]))
    out["chips"] = len(planes)
    out["dispatches"] = sum(1 for e in trace["host"] if e[0] == "step/dispatch")
    return out


def of(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The cut of the run `ctx` describes, made once and kept in `ctx`."""
    if "stagecut" not in ctx:
        where = run_dir(ctx["spans"])
        ctx["stagecut"] = cut_run(where, span_names(ctx["spans"])) if where else None
    return ctx["stagecut"]


# ----------------------------------------------------- what readers ask


def stage_ms(ctx: Dict[str, Any], scopes: Sequence[str]) -> Optional[float]:
    """Device milliseconds per completed traced step and chip inside the
    given scopes, forward and backward."""
    steps = ctx["window"].get("traced_steps", 0)
    cut = of(ctx) if steps > 0 else None
    if cut is None:
        return None
    ns = sum(sum(cut["stage_ns"].get(s, {}).values()) for s in scopes)
    return ns / 1e6 / (steps * cut["chips"])


def backward_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """Share of the device time inside any scope that lies under `transpose(`."""
    cut = of(ctx)
    if cut is None:
        return None
    staged = [v for k, v in cut["stage_ns"].items() if k != UNSCOPED]
    whole = sum(sum(v.values()) for v in staged)
    return 100.0 * sum(v["backward"] for v in staged) / whole if whole > 0 else None


def unscoped_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """Share of the chips' busy time in no stage scope."""
    cut = of(ctx)
    if cut is None or cut["busy_ns"] <= 0:
        return None
    return 100.0 * sum(cut["stage_ns"].get(UNSCOPED, {}).values()) / cut["busy_ns"]


def idle_unattributed_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """Share of the first chip's idle time under no program span."""
    cut = of(ctx)
    if cut is None or cut["idle_ns"] <= 0:
        return None
    return 100.0 * cut["by_span"].get("unattributed", 0.0) / cut["idle_ns"]


# -------------------------------------------------------------- by hand


def table(cut: Dict[str, Any], steps: int) -> Dict[str, Any]:
    """The cut as a table, milliseconds per step and chip: every stage, the
    operations, longest first, that make up `TABLE_SHARE` of the busy time,
    and the longest of those no scope reaches."""
    per = 1e6 * max(steps, 1) * cut["chips"]
    ranked = sorted(cut["ops"].items(), key=lambda kv: -kv[1]["ns"])
    loose = [(name, row) for name, row in ranked if row["stage"] == UNSCOPED]
    rows, covered = [], 0.0
    for name, row in ranked:
        if covered >= TABLE_SHARE * cut["busy_ns"]:
            break
        covered += row["ns"]
        rows.append([name, row["stage"], row["side"], row["ns"] / per, row["op_name"][-160:]])
    return {
        "steps": steps,
        "chips": cut["chips"],
        "step_busy_ms": cut["busy_ns"] / per,
        "stages_ms": {k: {s: ns / per for s, ns in v.items()} for k, v in sorted(cut["stage_ns"].items())},
        "ops_ms": rows,
        "ops_listed": len(rows),
        "ops_in_all": len(ranked),
        "unscoped_ms": [[name, row["ns"] / per, row["op_name"][-100:]] for name, row in loose[:15]],
        "unscoped_without_op_name_ms": sum(row["ns"] for _, row in loose if not row["op_name"]) / per,
        "idle_ms_in_slice": cut["idle_ns"] / 1e6,
        "idle_by_span_ms": {k: v / 1e6 for k, v in sorted(cut["by_span"].items(), key=lambda kv: -kv[1])},
    }


def main(argv: Sequence[str]) -> int:
    where = os.path.abspath(argv[1])
    with open(os.path.join(where, "telemetry", "trace.json")) as f:
        spans = json.load(f)["traceEvents"]
    cut = cut_run(where, span_names(spans))
    if cut is None:
        print(f"nothing to read under {where}", file=sys.stderr)
        return 1
    print(json.dumps(table(cut, cut["dispatches"]), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
