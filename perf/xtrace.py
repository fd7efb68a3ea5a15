"""From a profiler trace to numbers: which intervals a device was busy,
which operations took the time, what the host was doing in the gaps.

`load_xplane` turns the profiler's `.xplane.pb` into plain lists (read with
`jax.profiler.ProfileData`, nothing else); everything after it is
arithmetic on (name, start_ns, duration_ns) tuples, so a hand-built trace
tests it. A v5e trace names an operation by its whole HLO line and attaches
no category: convolutions are told by opcode, or by the list of fusions that
the step's compiled module says hold one.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # start_ns, end_ns
Event = Tuple[str, float, float, Dict[str, Any]]  # name, start_ns, duration_ns, stats

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PREFIX = "bench/"  # the harness's own TraceAnnotations


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, rehearsal: bool = False) -> Dict[str, Any]:
    """{"devices": {plane name: [Event]}, "host": [Event]}: the operations of
    each device plane's op line, and the harness's annotations on the host.
    `rehearsal` reads XLA:CPU's executor threads as one "device", so that the
    CPU rehearsal walks the same code; it is never a device number."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if rehearsal and plane.name.startswith("/host:CPU"):
            ops = devices.setdefault("rehearsal:cpu", [])
            for line in plane.lines:
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    for ev in line.events:
                        if ev.duration_ns > 0:
                            ops.append((ev.name, float(ev.start_ns), float(ev.duration_ns), dict(ev.stats)))
        if plane.name.startswith(DEVICE_PREFIX):
            ops: List[Event] = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.name, float(ev.start_ns), float(ev.duration_ns), dict(ev.stats)))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, float(ev.start_ns), float(ev.duration_ns), {}))
    return {"devices": devices, "host": host}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    merged: List[Interval] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(end - start for start, end in intervals))


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def intervals_of(events: Sequence[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d, _ in events if d > 0]


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The idle intervals of `window` that `busy` (merged) leaves."""
    out: List[Interval] = []
    at = window[0]
    for start, end in busy:
        if start > at:
            out.append((at, min(start, window[1])))
        at = max(at, end)
    if at < window[1]:
        out.append((at, window[1]))
    return [g for g in out if g[1] > g[0]]


def exposed(target: Sequence[Interval], others: Sequence[Interval]) -> float:
    """ns of `target` during which nothing of `others` runs."""
    t = union(target)
    covered = 0.0
    o = union(others)
    j = 0
    for start, end in t:
        while j < len(o) and o[j][1] <= start:
            j += 1
        k = j
        while k < len(o) and o[k][0] < end:
            covered += min(end, o[k][1]) - max(start, o[k][0])
            k += 1
    return total(t) - covered


def op_name(name: str) -> str:
    """The instruction's name: a v5e trace prints each operation as its whole
    HLO line, `%fusion.65 = bf16[32,38,38,256]{...} fusion(...), kind=...`."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def short_name(name: str) -> str:
    """`fusion.65 bf16[32,38,38,256] fusion`: name, result type, opcode."""
    if " = " not in name:
        return name[:120]
    head, rest = name.split(" = ", 1)
    rest = re.sub(r"\{[^{}]*\}", "", rest)  # layouts
    m = re.match(r"^(.*?)\s([\w\-]+)\(", rest)
    if not m:
        return head.lstrip("%")
    return f"{head.lstrip('%')} {m.group(1)[:90]} {m.group(2)}"


def category(name: str, conv_ops: Iterable[str] = ()) -> str:
    """"conv", "allreduce" or "other". The trace prints an all-reduce and an
    unfused convolution by their opcode; which `%fusion.N` hold a
    convolution comes from the step's compiled module (`conv_ops`)."""
    low = name.lower()
    if "all-reduce" in low or "all_reduce" in low or "allreduce" in low:
        return "allreduce"
    if op_name(name) in conv_ops or " convolution(" in low:
        return "conv"
    return "other"


def top_ops(events: Sequence[Event], n: int = 10, origin: Optional[Dict[str, str]] = None) -> List[List[Any]]:
    """The `n` operation names with the most summed device time, seconds;
    each with the tail of its `op_name` where the compiled module gives one."""
    sums: Dict[str, float] = {}
    origin = origin or {}
    for name, _, dur, _ in events:
        where = origin.get(op_name(name), "")
        name = short_name(name)
        if where:
            name = f"{name} @ {where.split('/', 1)[-1][-110:]}"
        sums[name] = sums.get(name, 0.0) + dur
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def attribute_gaps(idle: Sequence[Interval], host: Sequence[Event], n: int = 10) -> List[List[Any]]:
    """Idle time by what the host was doing: each gap's nanoseconds go to the
    harness annotations that overlap it (the innermost by start), the rest
    to "unannotated". Longest first, seconds."""
    spans = sorted(((s, s + d, name) for name, s, d, _ in host), key=lambda x: x[0])
    sums: Dict[str, float] = {}
    for g0, g1 in idle:
        left = g1 - g0
        for s, e, name in spans:
            if e <= g0 or s >= g1:
                continue
            part = min(e, g1) - max(s, g0)
            sums[name] = sums.get(name, 0.0) + part
            left -= part
        if left > 0:
            sums["unannotated"] = sums.get("unannotated", 0.0) + left
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce(
    trace: Dict[str, Any], window_s: float, conv_ops: Iterable[str] = (),
    origin: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    """The numbers every reader starts from. The traced window is from the
    first to the last device operation seen on any chip, which the harness
    brackets with a drained queue on both sides; `window_s` (host clock,
    profiler start to stop) is kept beside it."""
    planes = {k: v for k, v in trace["devices"].items() if v}
    if not planes:
        raise ValueError("the trace holds no device operation")
    lo = min(s for ops in planes.values() for _, s, _, _ in ops)
    hi = max(s + d for ops in planes.values() for _, s, d, _ in ops)
    window = (lo, hi)
    per_chip = {}
    for name, ops in planes.items():
        busy = union(intervals_of(ops))
        cats = [category(e[0], conv_ops) for e in ops]
        conv = [e for e, c in zip(ops, cats) if c == "conv"]
        coll = [e for e, c in zip(ops, cats) if c == "allreduce"]
        rest = [e for e, c in zip(ops, cats) if c != "allreduce"]
        per_chip[name] = {
            "busy_ns": total(busy),
            "conv_ns": float(sum(d for _, _, d, _ in conv)),
            "allreduce_ns": total(union(intervals_of(coll))),
            "allreduce_exposed_ns": exposed(intervals_of(coll), intervals_of(rest)),
        }
    first = sorted(planes)[0]
    idle = gaps(union(intervals_of(planes[first])), window)
    n = len(per_chip)
    return {
        "chips": n,
        "trace_window_s": (hi - lo) / 1e9,
        "host_window_s": window_s,
        "busy_s": sum(c["busy_ns"] for c in per_chip.values()) / n / 1e9,
        "conv_s": sum(c["conv_ns"] for c in per_chip.values()) / n / 1e9,
        "allreduce_exposed_s": per_chip[first]["allreduce_exposed_ns"] / 1e9,
        "device_ops": top_ops(planes[first], origin=origin),
        "idle_gaps": attribute_gaps(idle, trace["host"]),
    }
