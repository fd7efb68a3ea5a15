"""Minimal XSpace/XPlane trace reader — op-level time attribution from
``jax.profiler.trace`` output with zero external tooling.

SURVEY.md §5 "tracing/profiling": this module turns a captured trace
(``<dir>/plugins/profile/*/\\*.xplane.pb``) into a per-op table, so a
``train --profile`` run can be attributed at the XLA-op level from the
command line (``cli trace-summary``, `telemetry/report.py`). The
image's tensorboard profile plugin cannot do this (its generated protos
predate the installed protobuf and fail to import), so the stable xplane wire format is decoded directly: a
~60-line protobuf wire reader plus a walker for the four message types
the table needs. Schema (field numbers are stable across TF/TSL/JAX):

    XSpace   { repeated XPlane planes = 1; }
    XPlane   { int64 id=1; string name=2; repeated XLine lines=3;
               map<int64,XEventMetadata> event_metadata=4; }
    XLine    { string name=2; repeated XEvent events=4; }
    XEvent   { int64 metadata_id=1; int64 duration_ps=3; }
    XEventMetadata { int64 id=1; string name=2; string display_name=4; }

The reference has no profiling of any kind (SURVEY.md §5); torch users
reach for the TensorBoard plugin this replaces.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterator, List, Optional, Tuple

# ----------------------------------------------------------------- wire

_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        if i >= len(buf):
            raise ValueError(f"truncated varint at byte {i}")
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """Yield (field_number, wire_type, value) over one message's bytes.

    LEN fields yield their raw bytes (caller decides: submessage vs
    string); unknown wire types raise — better loud than silently
    misaligned."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _read_varint(buf, i)
        field, wt = tag >> 3, tag & 7
        if wt == _VARINT:
            v, i = _read_varint(buf, i)
        elif wt == _I64:
            if i + 8 > n:
                raise ValueError(f"truncated fixed64 at byte {i}")
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wt == _LEN:
            ln, i = _read_varint(buf, i)
            if i + ln > n:
                raise ValueError(f"truncated length-delimited at byte {i}")
            v, i = buf[i:i + ln], i + ln
        elif wt == _I32:
            if i + 4 > n:
                raise ValueError(f"truncated fixed32 at byte {i}")
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported wire type {wt} at byte {i}")
        yield field, wt, v


# --------------------------------------------------------------- schema


def _parse_event(buf: bytes) -> Tuple[int, int]:
    """(metadata_id, duration_ps)"""
    mid = dur = 0
    for f, _, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 3:
            dur = v
    return mid, dur


def _parse_line(buf: bytes) -> Tuple[str, List[Tuple[int, int]]]:
    name, events = "", []
    for f, wt, v in _fields(buf):
        if f == 2 and wt == _LEN:
            name = v.decode("utf-8", "replace")
        elif f == 4 and wt == _LEN:
            events.append(_parse_event(v))
    return name, events


def _parse_metadata_entry(buf: bytes) -> Tuple[int, str]:
    """map<int64, XEventMetadata> entry -> (id, best name)."""
    key, name, display = 0, "", ""
    for f, wt, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2 and wt == _LEN:
            for mf, mwt, mv in _fields(v):
                if mf == 2 and mwt == _LEN:
                    name = mv.decode("utf-8", "replace")
                elif mf == 4 and mwt == _LEN:
                    display = mv.decode("utf-8", "replace")
    return key, display or name


class Plane:
    def __init__(self, name: str):
        self.name = name
        self.lines: List[Tuple[str, List[Tuple[int, int]]]] = []
        self.event_names: Dict[int, str] = {}


def parse_xspace(path: str) -> List[Plane]:
    with open(path, "rb") as f:
        space = f.read()
    planes: List[Plane] = []
    for f_no, wt, v in _fields(space):
        if f_no != 1 or wt != _LEN:
            continue
        plane = Plane("")
        for pf, pwt, pv in _fields(v):
            if pf == 2 and pwt == _LEN:
                plane.name = pv.decode("utf-8", "replace")
            elif pf == 3 and pwt == _LEN:
                plane.lines.append(_parse_line(pv))
            elif pf == 4 and pwt == _LEN:
                k, name = _parse_metadata_entry(pv)
                plane.event_names[k] = name
        planes.append(plane)
    return planes


# ---------------------------------------------------------------- table


def find_xplane_files(trace_dir: str) -> List[str]:
    """All *.xplane.pb under a ``jax.profiler.trace`` output dir."""
    return sorted(
        glob.glob(
            os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
        )
    )


def has_device_trace(trace_dir: str) -> bool:
    """True when ``trace_dir`` holds a device profiler capture. Used by
    `telemetry.report` to point a run summary at ``trace-summary`` when a
    --profile capture sits next to the host-side span trace."""
    return bool(find_xplane_files(trace_dir))


def op_table(
    trace_dir: str,
    plane_filter: Optional[str] = None,
    top: int = 25,
) -> List[Dict[str, object]]:
    """Aggregate event durations by op name across matching planes.

    ``plane_filter`` substring-matches the plane name (e.g. "TPU" to
    exclude host threads; default: device planes preferred — any plane
    whose name contains 'TPU' or 'GPU' or starts with '/device', else
    all planes). Returns rows sorted by total time, each
    {op, total_ms, count, pct} with pct of the table's total.
    """
    totals: Dict[str, Tuple[float, int]] = {}
    for path in find_xplane_files(trace_dir):
        for plane in parse_xspace(path):
            if plane_filter is not None:
                if plane_filter.lower() not in plane.name.lower():
                    continue
            elif not _is_device_plane(plane.name):
                continue
            # device planes carry several overlapping timelines ("XLA
            # Modules" spans whole programs, "Steps" spans steps); the
            # "XLA Ops" line is the non-overlapping leaf-op timeline —
            # restrict to it when present so totals don't double-count
            lines = [
                (n, ev) for n, ev in plane.lines if n == "XLA Ops"
            ] or plane.lines
            for _, events in lines:
                for mid, dur_ps in events:
                    name = plane.event_names.get(mid, f"op#{mid}")
                    ms, cnt = totals.get(name, (0.0, 0))
                    totals[name] = (ms + dur_ps / 1e9, cnt + 1)
    if not totals and plane_filter is None:
        # host-only trace (CPU backend): fall back to every plane
        return op_table(trace_dir, plane_filter="", top=top)
    grand = sum(ms for ms, _ in totals.values()) or 1.0
    rows = [
        {
            "op": op,
            "total_ms": round(ms, 3),
            "count": cnt,
            "pct": round(100.0 * ms / grand, 2),
        }
        for op, (ms, cnt) in totals.items()
    ]
    rows.sort(key=lambda r: -r["total_ms"])
    return rows[:top]


def _is_device_plane(name: str) -> bool:
    low = name.lower()
    return "tpu" in low or "gpu" in low or name.startswith("/device")


def format_table(rows: List[Dict[str, object]]) -> str:
    if not rows:
        return "(no events)"
    w = max(len(str(r["op"])) for r in rows)
    out = [f"{'op':<{w}}  total_ms   count    pct"]
    for r in rows:
        out.append(
            f"{r['op']:<{w}}  {r['total_ms']:>8.3f}  {r['count']:>6}  "
            f"{r['pct']:>5.2f}%"
        )
    return "\n".join(out)
