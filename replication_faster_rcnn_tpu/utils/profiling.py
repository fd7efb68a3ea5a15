"""Profiling & timing — SURVEY.md §5 "tracing/profiling" (the reference has
none; its only signal is a per-step loss print at `train.py:124`).

Two tools:
  * :func:`trace` — context manager around `jax.profiler` producing a
    TensorBoard/Perfetto trace directory for device timeline inspection.
  * :class:`StepTimer` / :func:`measure_throughput` — wall-clock throughput
    with device synchronization (``jax.block_until_ready`` on the tree).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, Iterator, Optional

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


class StepTimer:
    """Running images/sec over a training loop (per-window, synced)."""

    def __init__(self, window: int = 50):
        self.window = window
        self._count = 0
        self._images = 0
        self._t0: Optional[float] = None
        self.images_per_sec = 0.0

    def update(self, batch_size: int, sync_tree: Any = None) -> Optional[float]:
        """Call once per step; returns images/sec at window boundaries."""
        if self._t0 is None:
            self._t0 = time.time()
        self._count += 1
        self._images += batch_size
        if self._count % self.window == 0:
            if sync_tree is not None:
                sync(sync_tree)
            dt = time.time() - self._t0
            self.images_per_sec = self._images / dt if dt > 0 else 0.0
            self._t0 = time.time()
            self._images = 0
            return self.images_per_sec
        return None


def measure_throughput(
    fn: Callable[..., Any],
    args: tuple,
    batch_size: int,
    n_steps: int = 10,
    warmup: int = 3,
    carry_state: bool = True,
) -> Dict[str, float]:
    """Benchmark a (state, batch) -> (state, aux) step function.

    With ``carry_state`` the state threads through iterations (real training
    dependency chain); sync waits on the final aux.
    """
    state, batch = args
    aux = None
    for _ in range(warmup):
        out = fn(state, batch)
        state = out[0] if carry_state else state
        aux = out[1] if isinstance(out, tuple) and len(out) > 1 else out
    sync(aux)
    t0 = time.time()
    for _ in range(n_steps):
        out = fn(state, batch)
        state = out[0] if carry_state else state
        aux = out[1] if isinstance(out, tuple) and len(out) > 1 else out
    sync(aux)
    dt = time.time() - t0
    return {
        "sec_per_step": dt / n_steps,
        "images_per_sec": n_steps * batch_size / dt,
    }
