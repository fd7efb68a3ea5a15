"""Profiling & timing — SURVEY.md §5 "tracing/profiling" (the reference has
none; its only signal is a per-step loss print at `train.py:124`).

:func:`trace` is a context manager around `jax.profiler` producing a
TensorBoard/Perfetto trace directory for device timeline inspection; the
program's telemetry spans (`telemetry/spans.py`) and the step's stage
scopes (`telemetry/stages.py`) appear in it. :func:`sync` waits for a tree.
"""

from __future__ import annotations

import contextlib
from typing import Any, Iterator, Optional

import jax


def sync(tree: Any) -> None:
    """Wait for every array in ``tree`` to be computed."""
    jax.block_until_ready(tree)


@contextlib.contextmanager
def trace(logdir: Optional[str]) -> Iterator[None]:
    """Capture a device trace viewable in TensorBoard / Perfetto.

    ``logdir=None`` is a no-op, so callers with an optional --profile flag
    can unconditionally write ``with trace(flag):``."""
    if logdir is None:
        yield
        return
    from replication_faster_rcnn_tpu.telemetry import spans as tspans

    # mirrored as a telemetry span so the host-side trace.json shows when
    # (and for how long) the device profiler was recording
    with tspans.current_tracer().span("profiler/trace", cat="profile",
                                      logdir=logdir):
        jax.profiler.start_trace(logdir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
