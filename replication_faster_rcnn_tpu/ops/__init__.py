"""Detection ops + the `ops.backend` dispatch seam.

Two implementation families live side by side:

* **xla** (default): the pure-XLA tilings (`nms_tiled.py`, `roi_ops.py`,
  `boxes.py`). The committed fingerprint banks (`frcnn audit`) pin these
  programs byte-for-byte, so the default backend must never change HLO.
* **pallas**: the Pallas kernels in `ops/pallas/` — interpret-mode off-TPU
  (pure JAX, parity-tested on CPU in tier-1), Mosaic-compiled on a TPU.

Resolution order, highest first:

1. :func:`backend_scope` — lexical override (tests, warmup twin programs)
2. ``FRCNN_OPS_BACKEND`` env var — read ONCE per process then cached, so a
   mid-run env flip can't split a program between backends (the trace-time
   ``FRCNN_NMS`` reads were a recurring source of that confusion)
3. the ``config.ops.backend`` value the caller passes down
4. ``"xla"``

`want_pallas(op)` is the single question dispatch sites ask. Choosing the
pallas backend and not getting it is an error, never a quiet XLA program
under a pallas name: a kernel package that fails to import raises, and on
a TPU backend a kernel compiles or the compiler's error propagates — it
never interprets there (`interpret_mode`).

Outside the seam: `pool_ops.py`, the ResNet stem's norm + ReLU + max-pool,
is two Pallas kernels under every backend (one writing, no XLA twin beside
it: PERF.md section 6, PR 30); it asks `interpret_mode` alone.
"""

import os
import threading
import warnings

from replication_faster_rcnn_tpu.ops import (  # noqa: F401
    anchors,
    boxes,
    nms,
    nms_tiled,
    roi_ops,
)

BACKENDS = ("xla", "pallas")

_ENV_VAR = "FRCNN_OPS_BACKEND"
_env_backend = None  # resolved-once cache: None = not read yet, "" = unset
_env_lock = threading.Lock()
_scope = threading.local()
_warned: set = set()


def _warn_once(key: str, msg: str) -> None:
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(msg, stacklevel=3)


def _env_override() -> str:
    """The FRCNN_OPS_BACKEND value, read once per process ("" = unset)."""
    global _env_backend
    if _env_backend is None:
        with _env_lock:
            if _env_backend is None:
                raw = os.environ.get(_ENV_VAR, "").strip().lower()
                if raw and raw not in BACKENDS:
                    _warn_once(
                        "env:invalid",
                        f"{_ENV_VAR}={raw!r} is not one of {BACKENDS}; "
                        "ignoring (using the config/default backend)",
                    )
                    raw = ""
                _env_backend = raw
    return _env_backend


class backend_scope:
    """Lexically pin the ops backend for the current thread.

    with ops.backend_scope("pallas"):
        ...   # every dispatch site in this block resolves to pallas

    Wins over the env var and config — this is how the warmup registry
    traces the ``__pallas`` twin programs and how tier-1 exercises both
    families in one process.
    """

    def __init__(self, backend: str):
        if backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {backend!r}"
            )
        self.backend = backend

    def __enter__(self):
        stack = getattr(_scope, "stack", None)
        if stack is None:
            stack = _scope.stack = []
        stack.append(self.backend)
        return self

    def __exit__(self, *exc):
        _scope.stack.pop()
        return False


def resolve_backend(config=None) -> str:
    """The effective ops backend: scope > env (read once) > config > xla."""
    stack = getattr(_scope, "stack", None)
    if stack:
        return stack[-1]
    env = _env_override()
    if env:
        return env
    if config is not None:
        ops_cfg = getattr(config, "ops", config)
        backend = getattr(ops_cfg, "backend", None)
        if backend is not None:
            if backend not in BACKENDS:
                raise ValueError(
                    f"config ops.backend must be one of {BACKENDS}, "
                    f"got {backend!r}"
                )
            return backend
    return "xla"


def require_pallas(op: str):
    """The kernel package, for dispatch site ``op`` — or an error saying
    why the pallas backend that was chosen cannot be had."""
    try:
        from replication_faster_rcnn_tpu.ops import pallas
    except Exception as e:
        raise RuntimeError(
            f"ops.backend=pallas was selected for {op!r} but the kernel "
            f"package failed to import ({type(e).__name__}: {e})"
        ) from e
    return pallas


def want_pallas(op: str, config=None) -> bool:
    """True iff dispatch site ``op`` should take the pallas path (raises
    when pallas is chosen and unavailable — see :func:`require_pallas`)."""
    if resolve_backend(config) != "pallas":
        return False
    require_pallas(op)
    return True


def interpret_mode() -> bool:
    """Pallas interpret mode: everywhere except a real TPU backend, where
    a kernel compiles or raises and never interprets."""
    import jax

    return jax.default_backend() != "tpu"
