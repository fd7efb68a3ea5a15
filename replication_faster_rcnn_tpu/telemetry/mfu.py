"""Model FLOPs utilisation (MFU).

MFU = achieved FLOP/s / peak FLOP/s, the canonical "is the chip or the
feed the bottleneck" number. Achieved FLOP/s comes from the caller's
per-step analytical FLOPs times steps/sec; peak comes from one of two
bases:

- ``tpu_datasheet`` — published per-chip bf16 peaks times device count,
  keyed off the runtime's own ``device_kind`` string. A TPU whose kind
  is not in the table is an error, not a default.
- ``cpu_measured_matmul`` — off-TPU there is no meaningful datasheet
  number, so the peak is *measured*: best throughput of a jitted f32
  matmul, cached per process. The ``mfu_basis`` field keeps the two
  regimes from being confused (a CPU-basis MFU says how well the host
  was used, not anything about TPU efficiency).
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

# Published per-chip bf16 peak FLOP/s. Matching is substring-based over the
# runtime device_kind string ("TPU v5 lite", "TPU v4", ...), most specific
# first — "v5p" must not fall through to the bare "v5" bucket and vice versa.
TPU_PEAK_BF16_FLOPS = (
    (("v5 lite", "v5e", "v5lite"), 197e12),
    (("v5p", "v5"), 459e12),
    (("v6 lite", "v6e"), 918e12),
    (("v4",), 275e12),
)

_cpu_peak_cache: Optional[float] = None


def device_record() -> dict:
    """The device as JAX reports it — printed beside every number a run
    reports, so a CPU figure can never pass for a chip figure."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_accelerator(who: str) -> dict:
    """The :func:`device_record`, or exit non-zero when JAX found no
    accelerator: a measurement entry point never carries on on the CPU."""
    device = device_record()
    if device["platform"] == "cpu":
        raise SystemExit(
            f"{who}: no accelerator — jax.devices()[0].platform is 'cpu' "
            f"({device['count']} device(s)); nothing was measured"
        )
    return device


def tpu_peak_flops_per_sec(device_kind: str, n_dev: int) -> float:
    """Aggregate datasheet bf16 peak for ``n_dev`` chips of ``device_kind``.
    An unrecognized generation raises: a silently-wrong or silently-null
    peak would distort MFU more than a failed run."""
    kind = device_kind.lower()
    for names, peak in TPU_PEAK_BF16_FLOPS:
        if any(n in kind for n in names):
            return peak * n_dev
    raise KeyError(
        f"TPU device_kind {device_kind!r} is not in TPU_PEAK_BF16_FLOPS "
        "(telemetry/mfu.py); add its datasheet peak with the source"
    )


def measured_cpu_peak_flops_per_sec(n: int = 512, iters: int = 4) -> Optional[float]:
    """Best observed FLOP/s of a jitted f32 ``n×n`` matmul, cached per
    process (~0.5 s once). FRCNN_CPU_PEAK_FLOPS overrides the measurement
    entirely — useful for deterministic tests and for hosts where a quick
    matmul under-represents sustained throughput."""
    global _cpu_peak_cache
    override = os.environ.get("FRCNN_CPU_PEAK_FLOPS")
    if override:
        try:
            return float(override)
        except ValueError:
            pass
    if _cpu_peak_cache is not None:
        return _cpu_peak_cache
    try:
        import time

        import jax
        import jax.numpy as jnp

        @jax.jit
        def mm(a, b):
            return a @ b

        a = jnp.ones((n, n), jnp.float32)
        b = jnp.ones((n, n), jnp.float32)
        mm(a, b).block_until_ready()  # compile outside the timed reps
        flops = 2.0 * n * n * n
        best = 0.0
        for _ in range(iters):
            t0 = time.perf_counter()
            mm(a, b).block_until_ready()
            dt = time.perf_counter() - t0
            if dt > 0:
                best = max(best, flops / dt)
        _cpu_peak_cache = best or None
    except Exception:
        _cpu_peak_cache = None
    if _cpu_peak_cache is None:
        # jitted path unavailable — a numpy matmul is a coarser but still
        # *measured* basis
        try:
            import time

            import numpy as np

            a = np.ones((n, n), np.float32)
            b = np.ones((n, n), np.float32)
            a @ b  # first call may pay thread-pool spin-up
            flops = 2.0 * n * n * n
            best = 0.0
            for _ in range(iters):
                t0 = time.perf_counter()
                a @ b
                dt = time.perf_counter() - t0
                if dt > 0:
                    best = max(best, flops / dt)
            _cpu_peak_cache = best or None
        except Exception:
            _cpu_peak_cache = None
    return _cpu_peak_cache


def peak_flops_per_sec(n_dev: Optional[int] = None) -> Tuple[Optional[float], Optional[str]]:
    """(aggregate peak FLOP/s, basis label) for the current backend.

    Basis is ``"tpu_datasheet"`` on TPU, ``"cpu_measured_matmul"`` on CPU,
    and ``(None, None)`` anywhere else (GPU has no table here yet).
    """
    import jax

    dev = jax.devices()[0]
    if n_dev is None:
        n_dev = jax.device_count()
    if dev.platform == "tpu":
        return (tpu_peak_flops_per_sec(dev.device_kind, n_dev), "tpu_datasheet")
    if dev.platform == "cpu":
        peak = measured_cpu_peak_flops_per_sec()
        return (peak, "cpu_measured_matmul" if peak else None)
    return (None, None)


def compute_mfu(
    flops_per_step: float,
    steps_per_sec: float,
    peak_flops_per_second: Optional[float],
) -> Optional[float]:
    """Achieved / peak. Pure arithmetic, no backend queries — testable
    against a hand-computed value."""
    if not flops_per_step or not steps_per_sec or not peak_flops_per_second:
        return None
    return (flops_per_step * steps_per_sec) / peak_flops_per_second
