"""Compile warm start: persistent XLA compilation cache + AOT warmup.

Every process start re-pays full XLA compilation of the train step
(minutes for the big presets on TPU) before the first batch dispatches.
Three pieces take that off the startup critical path:

* :func:`place_compile_cache` — the one place that decides where JAX's
  persistent compilation cache lives (``JAX_COMPILATION_CACHE_DIR`` if
  set, else ``compile.cache_dir`` / ``--compile-cache``, else a fixed
  path inside the checkout). Compiled executables are keyed by HLO +
  compile options and written under the directory; a later process
  compiling the *same* program (same config, same mesh, same jaxlib)
  deserializes instead of re-running XLA.
* :func:`keep_compiled` — the executable store beside that cache
  (``<cache>/executables/``). The persistent cache is keyed by the lowered
  module, so every start still traces and lowers each program before it
  can ask; the store keys a program's executable by everything its
  lowering reads, and a start that matches loads it without tracing.
* :func:`warmup_compile` — AOT-lower and compile the training-step
  program(s) (and optionally the eval inference program) for a config
  WITHOUT building datasets, allocating parameters or running a step:
  inputs are `jax.ShapeDtypeStruct` fixtures with the trainer's own
  shardings attached, so the lowered HLO matches what the real run jits.
  Run via ``cli warmup`` (typically with the cache enabled) to populate
  the cache ahead of a fleet launch; each compile is timed under a
  ``compile/*`` telemetry span.

Both consumers go through one PROGRAM REGISTRY
(:func:`build_program_specs`): every (feed × K) train program the Trainer
can jit — host loader, ``--cache-device`` selection feed, explicit
shard_map SPMD — plus the eval inference program, each with the exact jit
wrapping (donation, out_shardings) and abstract inputs (trainer
shardings attached) the real run uses. ``warmup_compile`` compiles the
subset its config selects; ``analysis/hlolint.py`` AOT-lowers the full
matrix and audits the artifacts (aliasing, collectives, memory) against
committed fingerprints.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import hashlib
import os
import pickle
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import serialize_executable
from jax.sharding import NamedSharding

from replication_faster_rcnn_tpu.config import FasterRCNNConfig
from replication_faster_rcnn_tpu.telemetry import spans as tspans


# where the persistent compilation cache lives when the environment does
# not place it: one fixed path inside the checkout (gitignored). The path
# is part of the cache key's world — a directory that moves never hits.
CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(CHECKOUT_ROOT, ".compile_cache")


def place_compile_cache(cache_dir: str = "") -> Optional[str]:
    """The one owner of the persistent compilation cache's location;
    returns the directory in use (None: no cache).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache stays there: JAX
    read the variable itself at import, and no directory is set in code —
    ``cache_dir`` (``compile.cache_dir`` / ``--compile-cache``) does not
    move it. Where it is not set the cache goes to ``cache_dir`` if given
    (~ expanded), else to :data:`DEFAULT_COMPILE_CACHE_DIR` — on an
    accelerator. On the CPU backend nothing is kept unless the
    environment asks: XLA:CPU's loader logs a page of machine-feature
    warnings on every hit, and debug runs should not fill the checkout.

    The min-compile-time / min-entry-size gates are dropped to zero so
    even cheap programs persist — this cache exists to make *restarts*
    free, and a restart replays every program, not just the slow ones.

    The key holds the program's metadata (its name scopes), which JAX
    leaves out by default: a cached executable carries the ``op_name`` of
    whoever compiled it, so without this a run loads another commit's
    executable and its profiler trace shows that commit's names (found on
    the chip: the step read 100 % outside the stage scopes of
    `telemetry/stages.py`, from a step an earlier commit had cached). A hit
    is now a program with this source's own names.

    The metadata holds no source locations (a traceback limit of 0 frames):
    a nested jit's jaxpr is traced once a process and keeps the call stack
    of whichever program traced it first, so with locations a program's key
    would depend on what the process compiled before it, and a start that
    loads the train programs from the executable store (`keep_compiled`)
    would miss every entry a start that compiled them had written."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.default_backend() == "cpu":
            jax.config.update("jax_enable_compilation_cache", False)
            return None
        path = (
            os.path.abspath(os.path.expanduser(cache_dir))
            if cache_dir
            else DEFAULT_COMPILE_CACHE_DIR
        )
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    return path


# ------------------------------------------------------ executable store

EXECUTABLES_DIR = "executables"  # under the compile cache's directory
PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what changes a lowering besides the source, the configuration and the
# arguments: jax settings, and the environment the compiler and the
# package's own dispatch sites (`ops/`: FRCNN_OPS_BACKEND, FRCNN_NMS, ...) read
LOWERING_SETTINGS = (
    "jax_enable_x64",
    "jax_default_matmul_precision",
    "jax_default_prng_impl",
    "jax_threefry_partitionable",
    "jax_numpy_dtype_promotion",
    "jax_use_shardy_partitioner",
    "jax_traceback_in_locations_limit",
    "jax_include_full_tracebacks_in_locations",
)
LOWERING_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
LOWERING_ENV_PREFIX = "FRCNN_"


@functools.lru_cache(maxsize=None)
def source_digest(root: str) -> str:
    """sha256 over every ``*.py`` under ``root``, each by its path relative
    to it: the Pallas kernels and every function a program traces."""
    h = hashlib.sha256()
    for path in sorted(glob.glob(os.path.join(root, "**", "*.py"), recursive=True)):
        h.update(os.path.relpath(path, root).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
        h.update(b"\0")
    return h.hexdigest()


@functools.lru_cache(maxsize=None)
def _versions() -> str:
    import flax
    import jaxlib
    import optax

    return (
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"flax {flax.__version__} optax {optax.__version__}"
    )


def program_context(
    config: FasterRCNNConfig, steps_per_epoch: int, n_shards: int
) -> Tuple[str, ...]:
    """What a train program's builder is handed, as the store's key holds
    it: the resolved configuration, ``steps_per_epoch`` and ``n_shards``
    (the schedule's constants) and the resolved ops backend.

    ``train.seed`` is left out unless ``data.augment_device`` is on: that is
    the one place a program reads it (`train/train_step.py::_device_input`);
    elsewhere it seeds host-side draws and the init program's key, which is
    an argument. A restart with another seed then finds its entry
    (`tests/test_executable_store.py` lowers the step for two seeds)."""
    from replication_faster_rcnn_tpu import ops as ops_pkg

    keyed = config
    if not config.data.augment_device:
        keyed = config.replace(train=dataclasses.replace(config.train, seed=0))
    return (
        repr(keyed),
        f"steps_per_epoch {steps_per_epoch}",
        f"n_shards {n_shards}",
        f"ops_backend {ops_pkg.resolve_backend(config)}",
    )


def _devices_of(leaves) -> List[Any]:
    """The devices a program runs on, in its mesh's order: those of its
    first argument placed by a sharding, else the default device."""
    for leaf in leaves:
        s = getattr(leaf, "sharding", None)
        if isinstance(s, NamedSharding):
            return list(s.mesh.devices.flat)
        if s is not None:
            return sorted(s.device_set, key=lambda d: d.id)
    return [jax.devices()[0]]


def _signature(leaves, treedef) -> Optional[str]:
    """The arguments as a lowering sees them (treedef; each leaf's shape,
    dtype, weak type, sharding with its devices), or None where any is
    abstract: a transformation is tracing through the program."""
    parts = [str(treedef)]
    for x in leaves:
        if isinstance(x, jax.core.Tracer):
            return None
        s = getattr(x, "sharding", None)
        if s is None:
            parts.append(repr(jax.typeof(x)))
            continue
        ids = (
            [d.id for d in s.mesh.devices.flat]
            if isinstance(s, NamedSharding)
            else sorted(d.id for d in s.device_set)
        )
        parts.append(
            f"{jax.typeof(x)!r} {s!r} devices={ids} "
            f"committed={getattr(x, 'committed', True)}"
        )
    return "\n".join(parts)


def _runtime(devices) -> List[str]:
    d = devices[0]
    env = sorted(
        (k, v)
        for k, v in os.environ.items()
        if k in LOWERING_ENV or k.startswith(LOWERING_ENV_PREFIX)
    )
    return [
        _versions(),
        f"backend {d.platform} {d.client.platform_version}",
        f"device {d.device_kind}",
        f"process {jax.process_index()} of {jax.process_count()}",
        *(f"{name} {getattr(jax.config, name)}" for name in LOWERING_SETTINGS),
        *(f"env {k}={v}" for k, v in env),
    ]


class StoredProgram:
    """A jitted program whose compiled executables outlive the process.

    The first call with a signature looks its entry up under the key of
    everything the lowering reads (:func:`source_digest` of the package,
    the caller's ``context``, the label, the signature, versions, devices,
    settings); a hit loads the executable onto the program's devices and
    traces, lowers and compiles nothing. A miss lowers and compiles as the
    jit would (so the persistent cache is asked and written as before),
    then writes the entry atomically. An entry that cannot be read or
    loaded is deleted and written anew; that is never fatal.

    After the first call, dispatch goes straight to the held executable,
    whose own argument check runs in C++; only where it refuses the
    arguments is a signature computed (one executable each). ``.lower``
    gives the held executable where there is one for its arguments, else
    the jit's lowering; every other attribute passes through to the jit.
    ``_cache_size`` counts the executables held, for the strict harness's
    recompile gate. Each
    lookup writes a ``jit/load`` span (``fun``, ``outcome`` hit | miss |
    bad, ``bytes``), each write a ``jit/store`` span.
    """

    def __init__(self, jitted, directory: str, label: str, context: Sequence[str]):
        self._jitted = jitted
        self._dir = directory
        self.label = label
        self._context = tuple(context)
        self._held: Dict[str, Any] = {}
        self._last = None

    def __call__(self, *args):
        last = self._last
        if last is not None:
            try:
                return last(*args)
            except (TypeError, ValueError):
                # its check refused the arguments: another signature (an
                # error of the call itself is raised again below)
                pass
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = _signature(leaves, treedef)
        if sig is None:
            return self._jitted(*args)
        compiled = self._held.get(sig)
        if compiled is None:
            compiled = self._held[sig] = self._obtain(sig, leaves, args)
        self._last = compiled
        return compiled(*args)

    def lower(self, *args, **kwargs):
        """Where an executable is held for these arguments, a stand-in whose
        ``compile()`` is that executable: the program that ran, which a
        loaded program has never traced (lowering it again here would cost
        a start's seconds, and a persistent-cache key of its own, since the
        key holds the callers' lines); else the jit's own lowering."""
        if not kwargs:
            leaves, treedef = jax.tree_util.tree_flatten(args)
            held = self._held.get(_signature(leaves, treedef))
            if held is not None:
                return _HeldLowering(held)
        return self._jitted.lower(*args, **kwargs)

    def _cache_size(self) -> int:
        return len(self._held)

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def _obtain(self, sig: str, leaves, args):
        tracer = tspans.current_tracer()
        devices = _devices_of(leaves)
        key = hashlib.sha256(
            "\n".join(
                (source_digest(PACKAGE_ROOT), self.label, *self._context, *_runtime(devices), sig)
            ).encode()
        ).hexdigest()
        path = os.path.join(self._dir, f"{re.sub(r'[^\w.-]', '_', self.label)}-{key}.exe")
        t0 = tracer.now_us()
        compiled, outcome, size, error = None, "miss", 0, {}
        if os.path.exists(path):
            try:
                with open(path, "rb") as f:
                    blob = f.read()
                stored, payload, in_tree, out_tree = pickle.loads(blob)
                if stored != key:
                    raise ValueError("an entry of another key")
                compiled = serialize_executable.deserialize_and_load(
                    payload, in_tree, out_tree,
                    backend=devices[0].client, execution_devices=devices,
                )
                outcome, size = "hit", len(blob)
            except Exception as e:  # never fatal: compiled and written anew below
                outcome, error = "bad", {"error": f"{type(e).__name__}: {e}"[:200]}
                _remove(path)
            else:
                try:
                    os.utime(path)  # last used: what the bound evicts by
                except OSError:
                    pass
        tracer.complete(
            "jit/load", t0, tracer.now_us() - t0, cat="compile",
            fun=self.label, outcome=outcome, bytes=size, **error,
        )
        if compiled is None:
            compiled = self._jitted.lower(*args).compile()
            self._store(compiled, key, path, tracer)
        return compiled

    def _store(self, compiled, key: str, path: str, tracer) -> None:
        t0 = tracer.now_us()
        tmp, size, error = None, 0, {}
        try:
            payload, in_tree, out_tree = serialize_executable.serialize(compiled)
            blob = pickle.dumps((key, payload, in_tree, out_tree), protocol=pickle.HIGHEST_PROTOCOL)
            os.makedirs(self._dir, exist_ok=True)
            tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
            tmp, size = None, len(blob)
            _evict(self._dir)
        except Exception as e:  # unserializable or unwritable: it runs as compiled
            error = {"error": f"{type(e).__name__}: {e}"[:200]}
            if tmp is not None:
                _remove(tmp)
        tracer.complete(
            "jit/store", t0, tracer.now_us() - t0, cat="compile",
            fun=self.label, bytes=size, **error,
        )


class _HeldLowering:
    """`StoredProgram.lower`'s answer where the executable is held."""

    def __init__(self, compiled):
        self._compiled = compiled

    def compile(self, *args, **kwargs):
        return self._compiled


def _remove(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


# entries used since this module was imported were loaded or written by
# this process (or one beside it) and are never evicted by it: the programs
# of one start must not turn each other out
_IMPORTED_AT = time.time()


def _evict(directory: str) -> None:
    """Hold the store to the size the persistent cache is held to
    (``jax_compilation_cache_max_size``; unbounded where it is not set):
    the least recently used entries go first, never one in use."""
    limit = jax.config.jax_compilation_cache_max_size
    if limit is None or limit < 0:
        return
    entries = []
    for path in glob.glob(os.path.join(directory, "*.exe")):
        try:
            st = os.stat(path)
        except OSError:
            continue
        entries.append((st.st_mtime, st.st_size, path))
    total = sum(size for _, size, _ in entries)
    for mtime, size, path in sorted(entries):
        if total <= limit:
            break
        if mtime < _IMPORTED_AT:
            _remove(path)
            total -= size


def keep_compiled(
    jitted, cache_dir: Optional[str], label: str, context: Sequence[str]
):
    """``jitted`` with its executables kept under ``<cache_dir>/executables/``
    (:class:`StoredProgram`), or ``jitted`` itself, unchanged, where no
    compile cache is placed (``cache_dir`` None: the CPU backend without
    ``JAX_COMPILATION_CACHE_DIR``). Empty the store by deleting that
    directory."""
    if cache_dir is None:
        return jitted
    return StoredProgram(jitted, os.path.join(cache_dir, EXECUTABLES_DIR), label, context)


def _mesh_for(config: FasterRCNNConfig):
    """The mesh the Trainer would build for this config (fit the data
    axis to the batch the same way Trainer.__init__ does)."""
    from replication_faster_rcnn_tpu.parallel import (
        fit_data_parallelism,
        make_mesh,
    )

    mesh_cfg = config.mesh
    if mesh_cfg.num_data <= 0:
        n_dev = len(jax.devices()) // max(1, mesh_cfg.num_model)
        mesh_cfg = dataclasses.replace(
            mesh_cfg,
            num_data=fit_data_parallelism(config.train.batch_size, n_dev),
        )
    return make_mesh(mesh_cfg), mesh_cfg


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One AOT-compilable program: the trainer-exact jitted callable plus
    the abstract inputs (trainer shardings attached) it lowers against.

    ``arg_roles`` names each positional abstract argument ("state",
    "batch", "cache", "sel", ...) so downstream consumers (the HLO
    auditor's donation rule) can map XLA parameter indices back to the
    Python-level argument they came from. ``build`` is lazy: constructing
    specs costs nothing until a consumer lowers a program.
    """

    name: str
    feed: str  # "loader" | "cached" | "spmd" | "zero" | "zero_lamb" | "eval"
    k: int  # fused steps per dispatch (1 = single step; 0 for eval)
    arg_roles: Tuple[str, ...]
    build: Callable[[], Tuple[Any, Tuple[Any, ...]]]
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


# "zero" is the shard_map backend with ZeRO-1 weight-update sharding
# forced on (train.shard_opt_state): same step math as "spmd" but the
# optimizer state is sharded over the data axis and the update is
# reduce-scatter / sharded-Adam / all-gather (parallel/spmd.py).
# "zero_lamb" is the same feed with train.optimizer='lamb' — the chain
# gains the sharded trust ratio (psum'd per-layer norms, see
# train/train_step.py::scale_by_sharded_trust_ratio), a distinct program
# with its own fingerprint.
# "mp" is the jit auto-partitioning backend on a 2D (dp, mp) mesh with
# model-parallel weight sharding (mesh.param_sharding / --mesh-shape):
# params arrive 1/mp per chip and GSPMD inserts the weight all-gathers.
# "mp_zero" additionally shards the optimizer state (ZeRO-1 over dp,
# composed off the mp dim — parallel/zero.py::compose_spec).
TRAIN_FEEDS: Tuple[str, ...] = (
    "loader", "cached", "spmd", "zero", "zero_lamb", "mp", "mp_zero"
)

# the (dp, mp) topology the audited mp programs lower against when the
# config itself is not model-parallel: mp = 4, dp = devices/4 (the audit
# tier runs 8 fake CPU devices -> a (2, 4) mesh)
MP_AUDIT_NUM_MODEL = 4


def mp_audit_config(config: FasterRCNNConfig) -> FasterRCNNConfig:
    """The config the "mp"/"mp_zero" feeds lower: the given config if it
    is already model-parallel, else the audit (dp, mp) topology forced
    onto it (num_model=4, dp = devices/4, param_sharding on)."""
    if config.mesh.param_sharding and config.mesh.num_model > 1:
        return config
    n, m = len(jax.devices()), MP_AUDIT_NUM_MODEL
    if n % m:
        raise ValueError(
            f"the mp audit feeds need a device count divisible by {m}, "
            f"got {n} (run under XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 on CPU)"
        )
    return config.replace(
        mesh=dataclasses.replace(
            config.mesh,
            num_data=n // m,
            num_model=m,
            param_sharding=True,
            spatial=False,
        )
    )


def program_name(feed: str, k: int) -> str:
    return "eval_infer" if feed == "eval" else f"train_{feed}_k{k}"


def bucket_train_program_name(feed: str, k: int, h: int, w: int) -> str:
    """Canonical name of one multi-scale train-bucket program
    (data.train_resolutions): the base (feed x K) name with the bucket's
    static resolution appended, mirroring serve_program_name."""
    return f"{program_name(feed, k)}_{h}x{w}"


def bucket_train_program_names(
    config: FasterRCNNConfig,
    feeds: Sequence[str] = ("loader", "cached"),
    ks: Sequence[int] = (1,),
) -> Tuple[str, ...]:
    """Every per-bucket train program the config's trainer would compile
    (empty when data.train_resolutions is unset). EVERY train feed
    buckets: the shard_map/mp feeds compile one program per resolution
    with the resample traced into the body, the in/out specs unchanged
    (they shard batch dims only, which is resolution-independent)."""
    return tuple(
        bucket_train_program_name(feed, k, h, w)
        for feed in feeds
        for k in ks
        for h, w in config.data.train_resolutions
    )


PALLAS_TWIN_SUFFIX = "__pallas"


def pallas_program_name(base: str) -> str:
    """Registry name of the ops.backend=pallas twin of a base program."""
    return base + PALLAS_TWIN_SUFFIX


class _ScopedLower:
    """Proxy a jitted callable so tracing happens under a pinned
    `ops.backend_scope`.

    jit is lazy: the ops-dispatch decisions (`ops.want_pallas`) run at
    TRACE time, which for a ProgramSpec is inside ``.lower()`` and for the
    Trainer is the first real dispatch. Wrapping the callable — instead of
    asking every call site to remember the scope — guarantees a program
    never half-resolves across backends, and that a pallas-backend program
    is only ever built through this registry (the 431e219 lesson: no lazy
    in-train-step pallas compiles). Everything else (`_clear_cache`, cache
    probes) passes through to the wrapped callable.
    """

    def __init__(self, jitted, backend: str):
        self._jitted = jitted
        self._backend = backend

    def lower(self, *args, **kwargs):
        from replication_faster_rcnn_tpu import ops as ops_pkg

        with ops_pkg.backend_scope(self._backend):
            return self._jitted.lower(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        from replication_faster_rcnn_tpu import ops as ops_pkg

        with ops_pkg.backend_scope(self._backend):
            return self._jitted(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def scope_jitted(jitted, config=None, backend: Optional[str] = None):
    """Wrap ``jitted`` so it traces under the config's resolved ops
    backend. Returns the callable unchanged for backend=xla — the default
    path must stay the exact jit object (and HLO) it always was."""
    if backend is None:
        from replication_faster_rcnn_tpu import ops as ops_pkg

        backend = ops_pkg.resolve_backend(config)
    if backend == "xla":
        return jitted
    return _ScopedLower(jitted, backend)


def serve_program_name(h: int, w: int, batch: int) -> str:
    """Canonical name of one serving bucket program."""
    return f"serve_{h}x{w}_b{batch}"


def serving_program_names(config: FasterRCNNConfig) -> Tuple[str, ...]:
    """Every serving bucket program the config's engine would compile."""
    return tuple(
        serve_program_name(h, w, n)
        for h, w in config.serving.bucket_resolutions(config.data.image_size)
        for n in sorted(set(config.serving.batch_sizes))
    )


def build_serving_specs(
    config: FasterRCNNConfig, model=None
) -> Dict[str, ProgramSpec]:
    """{program_name: ProgramSpec} for the serving engine's bucket matrix
    (``serving.resolutions × serving.batch_sizes``).

    Each bucket program is the SAME inference function the eval sweep
    jits (`eval/evaluator.py::make_infer_fn`, re-closed over the bucket
    resolution) against abstract inputs with every float variable leaf in
    ``serving.params_dtype`` — the dtype the engine holds its resident
    params in. Routing serving through this registry is what lets the
    persistent compile cache pre-warm `frcnn serve` and `frcnn audit`
    enforce HX001-HX006 on the serving programs.

    Under ``mesh.param_sharding`` with ``num_model > 1`` (``--mesh-shape
    DP,MP``) the abstract params carry `zero.param_shardings` layouts on
    a (1, num_model) serving mesh instead of the implicit single-device
    replication: serving holds ONE model replica, so a model too large
    for one chip's weights stays servable, and the engine's resident
    upload (`serving/engine.py::_build_resident`) places each leaf on
    the sharding banked here. Non-param collections (batch_stats) stay
    replicated. The audited 'ci' matrix runs num_model=1, so the banked
    serve fingerprints are untouched by this path.
    """
    from replication_faster_rcnn_tpu.eval.evaluator import make_infer_fn
    from replication_faster_rcnn_tpu.models.faster_rcnn import FasterRCNN

    if model is None:
        model = FasterRCNN(config)
    dtype = np.dtype(jax.numpy.dtype(config.serving.params_dtype))
    h0, w0 = config.data.image_size
    variables_abs = jax.eval_shape(
        lambda rng, img: model.init({"params": rng}, img, train=False),
        jax.ShapeDtypeStruct((2,), np.uint32),
        jax.ShapeDtypeStruct((1, h0, w0, 3), np.float32),
    )
    variables_abs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype if np.issubdtype(x.dtype, np.floating) else x.dtype
        ),
        variables_abs,
    )
    mesh_meta: Optional[Dict[str, int]] = None
    if config.mesh.param_sharding and max(1, config.mesh.num_model) > 1:
        variables_abs, mesh_meta = _mp_serving_variables(config, variables_abs)

    specs: Dict[str, ProgramSpec] = {}
    for h, w in config.serving.bucket_resolutions(config.data.image_size):
        for n in sorted(set(config.serving.batch_sizes)):
            name = serve_program_name(h, w, n)

            def _build(hh=h, ww=w, nn=n, name_=name):
                from replication_faster_rcnn_tpu.parallel.plan import (
                    Plan,
                    compile_step_with_plan,
                )

                # a bare plan: serving buckets jit plain (single-device
                # inference, params resident, nothing donated)
                jitted = compile_step_with_plan(
                    make_infer_fn(model, config, (hh, ww)),
                    Plan(label=name_),
                )
                images_abs = jax.ShapeDtypeStruct((nn, hh, ww, 3), np.float32)
                return jitted, (variables_abs, images_abs)

            specs[name] = ProgramSpec(
                name=name,
                feed="serve",
                k=0,
                arg_roles=("variables", "images"),
                build=_build,
                meta={
                    "bucket": [h, w],
                    "batch": n,
                    "params_dtype": config.serving.params_dtype,
                    **(
                        {"mesh_shape": mesh_meta, "param_sharding": True}
                        if mesh_meta
                        else {}
                    ),
                },
            )
    return specs


def _mp_serving_variables(config: FasterRCNNConfig, variables_abs):
    """Attach the model-parallel serving layout to the abstract variables:
    params get `zero.param_shardings` over a (1, num_model) mesh, every
    other collection a replicated NamedSharding on the same mesh. Returns
    ``(sharded_variables_abs, mesh_shape_meta)``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from replication_faster_rcnn_tpu.parallel import zero

    n_model = config.mesh.num_model
    devices = jax.devices()
    if len(devices) < n_model:
        raise ValueError(
            f"mesh.param_sharding serving needs num_model={n_model} "
            f"devices; only {len(devices)} visible"
        )
    grid = np.asarray(devices[:n_model]).reshape(1, n_model)
    mesh = Mesh(grid, (config.mesh.data_axis, config.mesh.model_axis))
    replicated = NamedSharding(mesh, PartitionSpec())
    params_sh = zero.param_shardings(
        variables_abs["params"], mesh, config.mesh
    )
    colls = {
        coll: (
            jax.tree_util.tree_map(
                lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
                variables_abs[coll],
                params_sh,
            )
            if coll == "params"
            else jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=replicated
                ),
                variables_abs[coll],
            )
        )
        for coll in variables_abs
    }
    if not isinstance(variables_abs, dict):
        colls = type(variables_abs)(colls)
    mesh_meta = {config.mesh.data_axis: 1, config.mesh.model_axis: n_model}
    return colls, mesh_meta


INT8_TWIN_SUFFIX = "__int8"


def int8_program_name(base: str) -> str:
    """Registry name of the serving.params_dtype=int8 twin of a serve
    bucket program."""
    return base + INT8_TWIN_SUFFIX


def int8_serving_program_names(config: FasterRCNNConfig) -> Tuple[str, ...]:
    """Every int8 serving bucket program the config's engine would
    compile under ``serving.params_dtype="int8"``."""
    return tuple(
        int8_program_name(base) for base in serving_program_names(config)
    )


def int8_program_names(config: FasterRCNNConfig) -> Tuple[str, ...]:
    """The full int8 registry name set `build_int8_program_specs` emits:
    every serving bucket program's int8 twin plus the one
    ops.backend=pallas int8 twin (largest bucket, smallest batch) —
    pure names, no lowering (the audit's expected-set arithmetic)."""
    buckets = config.serving.bucket_resolutions(config.data.image_size)
    batches = sorted(set(config.serving.batch_sizes))
    names = list(int8_serving_program_names(config))
    names.append(
        pallas_program_name(
            int8_program_name(serve_program_name(*buckets[-1], min(batches)))
        )
    )
    return tuple(names)


def make_int8_infer_fn(model, config: FasterRCNNConfig, image_size=None):
    """The int8 serving program body: in-program reconstruction of the
    quantized resident tree (`quant/apply.py::build_infer_variables` —
    per-channel dequantize through the `ops/quant_ops.py` backend seam,
    QuantDense kernels passed through as int8), then the SAME inference
    function every other serve bucket jits."""
    from replication_faster_rcnn_tpu.eval.evaluator import make_infer_fn
    from replication_faster_rcnn_tpu.quant.apply import build_infer_variables

    base = make_infer_fn(model, config, image_size)

    def infer(qvars, images):
        return base(build_infer_variables(qvars, config), images)

    return infer


def build_int8_program_specs(
    config: FasterRCNNConfig, model=None, artifact=None
) -> Dict[str, ProgramSpec]:
    """{name: ProgramSpec} for the ``serve_*__int8`` twin programs — one
    per serving bucket/batch — plus one ops.backend=pallas int8 twin
    (largest bucket, smallest batch, ``serve_*__int8__pallas``) whose
    dequantize routes through `ops/pallas/quant_kernel.py`.

    ``artifact`` defaults to the structure-only synthetic artifact
    (all-int8 plan, `quant/apply.py::synthetic_artifact`): lowering only
    needs the qvars STRUCTURE, and pinning the canonical plan keeps the
    audited program matrix independent of any local calibration run. The
    engine builds the same specs against its real sidecar.
    """
    from replication_faster_rcnn_tpu import ops as ops_pkg
    from replication_faster_rcnn_tpu.models.faster_rcnn import FasterRCNN
    from replication_faster_rcnn_tpu.quant.apply import (
        abstract_quantize_variables,
        synthetic_artifact,
    )

    if model is None:
        model = FasterRCNN(config)
    h0, w0 = config.data.image_size
    variables_abs = jax.eval_shape(
        lambda rng, img: model.init({"params": rng}, img, train=False),
        jax.ShapeDtypeStruct((2,), np.uint32),
        jax.ShapeDtypeStruct((1, h0, w0, 3), np.float32),
    )
    if artifact is None:
        artifact = synthetic_artifact(variables_abs)
    qvars_abs = abstract_quantize_variables(variables_abs, artifact)
    plan = dict(artifact["plan"])
    dense_int8 = "quant" in qvars_abs

    def _spec(base_name: str, h: int, w: int, n: int, backend: str):
        name = int8_program_name(base_name)
        if backend == "pallas":
            name = pallas_program_name(name)

        def _build(hh=h, ww=w, nn=n, name_=name, backend_=backend):
            from replication_faster_rcnn_tpu.parallel.plan import (
                Plan,
                compile_step_with_plan,
            )

            jitted = compile_step_with_plan(
                make_int8_infer_fn(model, config, (hh, ww)),
                Plan(label=name_),
            )
            if backend_ == "pallas":
                jitted = _ScopedLower(jitted, "pallas")
            images_abs = jax.ShapeDtypeStruct((nn, hh, ww, 3), np.float32)
            return jitted, (qvars_abs, images_abs)

        meta = {
            "bucket": [h, w],
            "batch": n,
            "params_dtype": "int8",
            "quant_plan": plan,
            "int8_dense": dense_int8,
            "twin": base_name,
        }
        if backend == "pallas":
            meta.update(
                ops_backend="pallas",
                pallas_interpret=ops_pkg.interpret_mode(),
                twin=int8_program_name(base_name),
            )
        return name, ProgramSpec(
            name=name,
            feed="serve",
            k=0,
            arg_roles=("qvariables", "images"),
            build=_build,
            meta=meta,
        )

    specs: Dict[str, ProgramSpec] = {}
    buckets = config.serving.bucket_resolutions(config.data.image_size)
    batches = sorted(set(config.serving.batch_sizes))
    for h, w in buckets:
        for n in batches:
            name, spec = _spec(serve_program_name(h, w, n), h, w, n, "xla")
            specs[name] = spec
    # one pallas int8 twin, mirroring pallas_twin_base_names' serving
    # choice: largest-area bucket, smallest batch
    ph, pw = buckets[-1]
    pn = min(batches)
    name, spec = _spec(serve_program_name(ph, pw, pn), ph, pw, pn, "pallas")
    specs[name] = spec
    return specs


def abstract_step_inputs(cfg, tx):
    """(model, state_abs, batch_abs): abstract fixtures of one train step
    — shapes/dtypes only, no arrays allocated, no param-init programs run
    (a pure trace). Shared by the program registry below and the static
    cost scripts (`benchmarks/step_profile.py`, `layer_cost_table.py`) so
    they can never analyze different shapes."""
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.data.loader import collate
    from replication_faster_rcnn_tpu.models.faster_rcnn import FasterRCNN
    from replication_faster_rcnn_tpu.train import create_train_state

    model = FasterRCNN(cfg)
    state_abs = jax.eval_shape(
        lambda rng: create_train_state(cfg, rng, tx)[1], jax.random.PRNGKey(0)
    )
    sample = collate([SyntheticDataset(cfg.data, length=1)[0]])
    b = cfg.train.batch_size
    batch_abs = {
        k: jax.ShapeDtypeStruct((b,) + v.shape[1:], v.dtype)
        for k, v in sample.items()
    }
    if cfg.data.augment_device and (
        cfg.data.augment_hflip
        or cfg.data.augment_scale
        or cfg.data.augment_translate
    ):
        # device-mode augmentation ships an int32 (idx, epoch) row per
        # sample (data/augment.py::AugmentTagView) — the fixture must
        # carry it so warmup/audit lower the runtime trace, not a twin
        batch_abs["aug"] = jax.ShapeDtypeStruct((b, 2), np.int32)
    return model, state_abs, batch_abs


def build_program_specs(
    config: FasterRCNNConfig,
    feeds: Sequence[str] = ("loader",),
    ks: Sequence[int] = (1,),
    include_eval: bool = True,
    cache_n: Optional[int] = None,
) -> Dict[str, ProgramSpec]:
    """The registry: {program_name: ProgramSpec} for every requested
    (feed × K) train program plus (``include_eval``) the eval inference
    program, all against ONE config.

    Each spec reproduces the Trainer's jit site exactly — loader/cached
    feeds jit with ``donate_argnums=(0,)`` and
    ``out_shardings=(state_shardings, None)``; the spmd feed comes
    pre-jitted from `make_shard_map_train_step` (replicated state,
    donated); eval is `Evaluator._jit_infer` under its own eval-mesh
    placement — so what a consumer lowers is what the real run compiles,
    not a similar program. ``cache_n`` sizes the abstract device cache
    for cached-feed programs (default: two batches — the cache length is
    a free shape parameter, and fingerprints pin it).
    """
    from replication_faster_rcnn_tpu.parallel import (
        batch_sharding,
        image_sharding,
        replicated,
        stacked_batch_sharding,
    )
    from replication_faster_rcnn_tpu.parallel.plan import (
        Plan,
        compile_step_with_plan,
    )
    from replication_faster_rcnn_tpu.parallel.zero import train_state_shardings
    from replication_faster_rcnn_tpu.train.train_step import (
        build_multi_step,
        make_cached_multi_step,
        make_cached_train_step,
        make_optimizer,
        make_train_step,
    )

    unknown = set(feeds) - set(TRAIN_FEEDS)
    if unknown:
        raise ValueError(f"unknown feeds {sorted(unknown)}; pick from {TRAIN_FEEDS}")
    if any(k < 1 for k in ks):
        raise ValueError(f"ks must be >= 1, got {tuple(ks)}")

    mesh, mesh_cfg = _mesh_for(config)
    tx, _ = make_optimizer(config, steps_per_epoch=100)
    model, state_raw, batch_raw = abstract_step_inputs(config, tx)
    state_shardings = train_state_shardings(
        state_raw, mesh, mesh_cfg, config.train.shard_opt_state
    )

    def _attach(tree, shardings):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree,
            shardings,
        )

    state_abs = _attach(state_raw, state_shardings)
    rep = replicated(mesh)
    state_rep = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), state_raw
    )
    img_s, other_s = image_sharding(mesh, mesh_cfg), batch_sharding(mesh, mesh_cfg)
    batch_abs = {
        k: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=img_s if k == "image" else other_s
        )
        for k, v in batch_raw.items()
    }
    stacked_s = stacked_batch_sharding(mesh, mesh_cfg)

    def _chunk_abs(k: int) -> Dict[str, jax.ShapeDtypeStruct]:
        return {
            key: jax.ShapeDtypeStruct((k,) + v.shape, v.dtype, sharding=stacked_s)
            for key, v in batch_abs.items()
        }

    batch = config.train.batch_size
    n_cache = cache_n if cache_n is not None else 2 * batch
    # the cache holds the collated sample arrays minus per-step jitter
    # geometry (data/device_cache.py: jitter attaches via sel, never the
    # cache), replicated over the mesh like DeviceCache places them
    cache_abs = {
        k: jax.ShapeDtypeStruct((n_cache,) + v.shape[1:], v.dtype, sharding=rep)
        for k, v in batch_raw.items()
        if k != "jitter"
    }

    def _sel_abs(lead: Tuple[int, ...]) -> Dict[str, jax.ShapeDtypeStruct]:
        sel = {"idx": jax.ShapeDtypeStruct(lead + (batch,), np.int32, sharding=rep)}
        if config.data.augment_hflip:
            sel["flip"] = jax.ShapeDtypeStruct(lead + (batch,), np.bool_, sharding=rep)
        if config.data.augment_scale is not None:
            sel["jitter"] = jax.ShapeDtypeStruct(
                lead + (batch, 4), np.int32, sharding=rep
            )
        return sel

    meta = {
        "n_float_grad_leaves": sum(
            1
            for leaf in jax.tree_util.tree_leaves(state_raw.params)
            if np.issubdtype(leaf.dtype, np.floating)
        ),
        "mesh_shape": dict(mesh.shape),
    }

    # the pjit plan every jit auto-partitioning feed compiles through:
    # donated state, out_shardings pinning the state layout across steps
    def _pjit_plan(shardings, mesh_=None):
        return Plan(
            mesh=mesh_ if mesh_ is not None else mesh,
            donate_argnums=(0,),
            out_shardings=(shardings, None),
        )

    def _loader(k: int, res: Optional[Tuple[int, int]] = None):
        step_fn = make_train_step(model, config, tx, train_resolution=res)
        if k == 1:
            fn, args = step_fn, (state_abs, batch_abs)
        else:
            fn, args = build_multi_step(step_fn, k), (state_abs, _chunk_abs(k))
        return compile_step_with_plan(fn, _pjit_plan(state_shardings)), args

    def _cached(k: int, res: Optional[Tuple[int, int]] = None):
        if k == 1:
            fn = make_cached_train_step(model, config, tx, train_resolution=res)
            args = (state_abs, cache_abs, _sel_abs(()))
        else:
            fn = make_cached_multi_step(
                model, config, tx, k, train_resolution=res
            )
            args = (state_abs, cache_abs, _sel_abs((k,)))
        # donate the state ONLY — the cache must survive the dispatch
        # (train/train_step.py::make_cached_train_step)
        return compile_step_with_plan(fn, _pjit_plan(state_shardings)), args

    def _mp(
        k: int,
        shard_opt: bool = False,
        res: Optional[Tuple[int, int]] = None,
    ):
        # model-parallel feed: the mp (dp, mp) mesh, params sharded 1/mp
        # over the model axis in BOTH the abstract inputs and the
        # out_shardings; the step function itself is the plain auto-
        # partitioning one — GSPMD does the rest. ``shard_opt`` composes
        # ZeRO-1 over dp (the "mp_zero" feed).
        mcfg = mp_audit_config(config)
        if shard_opt != mcfg.train.shard_opt_state:
            mcfg = mcfg.replace(
                train=dataclasses.replace(
                    mcfg.train, shard_opt_state=shard_opt
                )
            )
        mesh_mp, mesh_mp_cfg = _mesh_for(mcfg)
        mp_shardings = train_state_shardings(
            state_raw, mesh_mp, mesh_mp_cfg, shard_opt
        )
        state_mp = _attach(state_raw, mp_shardings)
        img_mp = image_sharding(mesh_mp, mesh_mp_cfg)
        other_mp = batch_sharding(mesh_mp, mesh_mp_cfg)
        batch_mp = {
            key: jax.ShapeDtypeStruct(
                v.shape, v.dtype,
                sharding=img_mp if key == "image" else other_mp,
            )
            for key, v in batch_raw.items()
        }
        step_fn = make_train_step(model, mcfg, tx, train_resolution=res)
        if k == 1:
            fn, args = step_fn, (state_mp, batch_mp)
        else:
            stacked_mp = stacked_batch_sharding(mesh_mp, mesh_mp_cfg)
            chunk_mp = {
                key: jax.ShapeDtypeStruct(
                    (k,) + v.shape, v.dtype, sharding=stacked_mp
                )
                for key, v in batch_mp.items()
            }
            fn, args = build_multi_step(step_fn, k), (state_mp, chunk_mp)
        return (
            compile_step_with_plan(fn, _pjit_plan(mp_shardings, mesh_mp)),
            args,
        )

    def _spmd(k: int, res: Optional[Tuple[int, int]] = None):
        from replication_faster_rcnn_tpu.parallel.spmd import (
            make_shard_map_train_step,
        )

        scfg = config.replace(
            train=dataclasses.replace(config.train, shard_opt_state=False)
        )
        jitted, _ = make_shard_map_train_step(
            scfg, tx, mesh, steps_per_dispatch=k, train_resolution=res
        )
        if k == 1:
            return jitted, (state_rep, batch_abs)
        return jitted, (state_rep, _chunk_abs(k))

    def _zero(k: int, res: Optional[Tuple[int, int]] = None):
        from replication_faster_rcnn_tpu.parallel.spmd import (
            make_shard_map_train_step,
        )

        zcfg = config.replace(
            train=dataclasses.replace(config.train, shard_opt_state=True)
        )
        # ZeRO state placement: params/BN replicated, opt state sharded
        # over the data axis — exactly what the Trainer device_puts
        zero_shardings = train_state_shardings(state_raw, mesh, mesh_cfg, True)
        state_zero = _attach(state_raw, zero_shardings)
        jitted, _ = make_shard_map_train_step(
            zcfg, tx, mesh, steps_per_dispatch=k, state_template=state_raw,
            train_resolution=res,
        )
        if k == 1:
            return jitted, (state_zero, batch_abs)
        return jitted, (state_zero, _chunk_abs(k))

    def _zero_lamb(k: int, res: Optional[Tuple[int, int]] = None):
        from replication_faster_rcnn_tpu.parallel.spmd import (
            make_shard_map_train_step,
        )

        lcfg = config.replace(
            train=dataclasses.replace(
                config.train,
                backend="spmd",
                shard_opt_state=True,
                optimizer="lamb",
            )
        )
        # The module-level tx is the config's own chain (adam for the
        # audit config); this feed needs the LAMB chain whose trust
        # ratio psums its norms over the data axis, and a matching
        # state template (the chain's opt_state structure differs).
        ltx, _ = make_optimizer(
            lcfg,
            steps_per_epoch=100,
            n_shards=mesh.shape[mesh_cfg.data_axis],
        )
        _, lstate_raw, _ = abstract_step_inputs(lcfg, ltx)
        lamb_shardings = train_state_shardings(lstate_raw, mesh, mesh_cfg, True)
        state_lamb = _attach(lstate_raw, lamb_shardings)
        jitted, _ = make_shard_map_train_step(
            lcfg, ltx, mesh, steps_per_dispatch=k, state_template=lstate_raw,
            train_resolution=res,
        )
        if k == 1:
            return jitted, (state_lamb, batch_abs)
        return jitted, (state_lamb, _chunk_abs(k))

    def _eval():
        from replication_faster_rcnn_tpu.eval import Evaluator

        ev = Evaluator(config, model)
        # mirror Evaluator.evaluate's own placement: its eval mesh (or no
        # sharding on a single device), so the lowered program is the one
        # the real eval sweep jits
        e_img_s, rep_s = ev._eval_sharding(config.train.batch_size)

        def _abs(x, s):
            if s is None:
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)

        variables_abs = {
            "params": jax.tree_util.tree_map(
                lambda x: _abs(x, rep_s), state_raw.params
            ),
            "batch_stats": jax.tree_util.tree_map(
                lambda x: _abs(x, rep_s), state_raw.batch_stats
            ),
        }
        images_abs = _abs(batch_raw["image"], e_img_s)
        return ev._jit_infer, (variables_abs, images_abs)

    builders = {
        "loader": _loader, "cached": _cached, "spmd": _spmd, "zero": _zero,
        "zero_lamb": _zero_lamb,
        "mp": _mp,
        "mp_zero": (lambda k: _mp(k, shard_opt=True)),
    }
    roles = {
        "loader": ("state", "batch"),
        "cached": ("state", "cache", "sel"),
        "spmd": ("state", "batch"),
        "zero": ("state", "batch"),
        "zero_lamb": ("state", "batch"),
        "mp": ("state", "batch"),
        "mp_zero": ("state", "batch"),
    }
    mp_meta = dict(meta)
    if any(f in ("mp", "mp_zero") for f in feeds):
        # mp programs lower on their own (dp, mp) mesh — stamp ITS shape
        # so the collective-contract rules know the model-axis width
        mp_mesh, _ = _mesh_for(mp_audit_config(config))
        mp_meta["mesh_shape"] = dict(mp_mesh.shape)
    specs: Dict[str, ProgramSpec] = {}
    for feed in feeds:
        for k in ks:
            name = program_name(feed, k)
            specs[name] = ProgramSpec(
                name=name,
                feed=feed,
                k=k,
                arg_roles=roles[feed],
                build=(lambda f=feed, kk=k: builders[f](kk)),
                meta=dict(mp_meta if feed in ("mp", "mp_zero") else meta),
            )
    if config.data.train_resolutions:
        # multi-scale train buckets: one program per (feed x K x bucket)
        # for the bucketable feeds, each baking the bucket's static
        # on-device resample into the trace (the Trainer's own per-bucket
        # jit sites) — registered here so warmup pre-compiles them and
        # the HLO audit banks them exactly like serving buckets.
        bucket_builders = {
            "loader": _loader,
            "cached": _cached,
            "spmd": _spmd,
            "zero": _zero,
            "zero_lamb": _zero_lamb,
            "mp": _mp,
            "mp_zero": (lambda k, res=None: _mp(k, shard_opt=True, res=res)),
        }
        for feed in feeds:
            if feed not in bucket_builders:
                continue
            for k in ks:
                for bh, bw in config.data.train_resolutions:
                    name = bucket_train_program_name(feed, k, bh, bw)
                    specs[name] = ProgramSpec(
                        name=name,
                        feed=feed,
                        k=k,
                        arg_roles=roles[feed],
                        build=(
                            lambda f=feed, kk=k, hh=bh, ww=bw: bucket_builders[
                                f
                            ](kk, res=(hh, ww))
                        ),
                        # mp bucket programs lower on the (dp, mp) mesh —
                        # they need ITS shape for the model-axis
                        # collective classification, same as their
                        # non-bucket rows
                        meta={
                            **(
                                mp_meta
                                if feed in ("mp", "mp_zero")
                                else meta
                            ),
                            "bucket": [bh, bw],
                        },
                    )
    if include_eval:
        specs["eval_infer"] = ProgramSpec(
            name="eval_infer",
            feed="eval",
            k=0,
            arg_roles=("variables", "images"),
            build=_eval,
            meta=dict(meta),
        )
    return specs


def pallas_twin_base_names(config: FasterRCNNConfig) -> Tuple[str, ...]:
    """The base programs that get an ops.backend=pallas twin in the audit
    registry: the canonical k=1 loader train step, the eval inference
    program, and one serving bucket (full-size resolution, batch 1) —
    one program per dispatch seam (targets matching + proposal NMS in the
    train step; NMS + ROIAlign in the inference programs) without
    doubling the whole (feed × K × bucket) matrix.
    """
    buckets = config.serving.bucket_resolutions(config.data.image_size)
    h, w = buckets[-1]  # largest-area bucket = the full-size program
    b = min(config.serving.batch_sizes)
    return (
        program_name("loader", 1),
        "eval_infer",
        serve_program_name(h, w, b),
    )


def build_pallas_program_specs(
    config: FasterRCNNConfig,
) -> Dict[str, ProgramSpec]:
    """{twin_name: ProgramSpec} for the ops.backend=pallas twin programs.

    Each twin is the SAME ProgramSpec as its base — same jit wrapping,
    same abstract inputs — built and lowered under
    ``ops.backend_scope("pallas")`` via :class:`_ScopedLower`, so the ops
    dispatch sites resolve to the `ops/pallas/` kernels at trace time.
    Twin meta records ``ops_backend``/``pallas_interpret``/``twin`` for
    the fingerprint bank and the HX007 hlolint rule; off-TPU the kernels
    lower in interpret mode (plain StableHLO loops, no custom-call), on a
    real TPU they lower through Mosaic custom-calls.
    """
    from replication_faster_rcnn_tpu import ops as ops_pkg

    base_specs = {
        **build_program_specs(
            config, feeds=("loader",), ks=(1,), include_eval=True
        ),
        **build_serving_specs(config),
    }
    interpret = ops_pkg.interpret_mode()
    specs: Dict[str, ProgramSpec] = {}
    for base_name in pallas_twin_base_names(config):
        base = base_specs[base_name]
        name = pallas_program_name(base_name)

        def _build(b=base):
            from replication_faster_rcnn_tpu import ops as ops_pkg

            with ops_pkg.backend_scope("pallas"):
                jitted, args = b.build()
            return _ScopedLower(jitted, "pallas"), args

        meta = dict(base.meta)
        meta.update(
            ops_backend="pallas", pallas_interpret=interpret, twin=base_name
        )
        specs[name] = ProgramSpec(
            name=name,
            feed=base.feed,
            k=base.k,
            arg_roles=base.arg_roles,
            build=_build,
            meta=meta,
        )
    return specs


LM_PROGRAM = "train_lm_k1"


def build_lm_program_specs() -> Dict[str, ProgramSpec]:
    """{LM_PROGRAM: ProgramSpec}: the sequence model's train step
    (`models/lm.py`) at the tiny preset's sizes on one device, jitted as the
    Trainer jits it (donated state, `out_shardings` pinning its layout). It
    hangs on no audited detector config: the kind of model is the preset's.
    Off the TPU its two kernels lower in interpret mode (`pallas_interpret`
    in the meta), as the ops.backend=pallas twins do."""
    import dataclasses as _dc

    from replication_faster_rcnn_tpu import ops as ops_pkg
    from replication_faster_rcnn_tpu.config import get_config
    from replication_faster_rcnn_tpu.parallel import batch_sharding
    from replication_faster_rcnn_tpu.parallel.plan import Plan, compile_step_with_plan
    from replication_faster_rcnn_tpu.parallel.zero import train_state_shardings
    from replication_faster_rcnn_tpu.train.train_step import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )

    config = get_config("trinity_tiny")
    config = config.replace(mesh=_dc.replace(config.mesh, num_data=1))

    def _build():
        mesh, mesh_cfg = _mesh_for(config)
        tx, _ = make_optimizer(config, steps_per_epoch=100)
        state_raw = jax.eval_shape(
            lambda rng: create_train_state(config, rng, tx)[1], jax.random.PRNGKey(0)
        )
        shardings = train_state_shardings(state_raw, mesh, mesh_cfg, False)
        state_abs = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), state_raw, shardings
        )
        batch_abs = {
            "tokens": jax.ShapeDtypeStruct(
                (config.train.batch_size, config.data.seq_len), np.int32,
                sharding=batch_sharding(mesh, mesh_cfg),
            )
        }
        plan = Plan(mesh=mesh, donate_argnums=(0,), out_shardings=(shardings, None))
        return compile_step_with_plan(make_train_step(None, config, tx), plan), (state_abs, batch_abs)

    meta = {
        "preset": "trinity_tiny", "mesh_shape": {config.mesh.data_axis: 1, config.mesh.model_axis: 1},
        "pallas_interpret": ops_pkg.interpret_mode(),
    }
    return {
        LM_PROGRAM: ProgramSpec(
            name=LM_PROGRAM, feed="loader", k=1, arg_roles=("state", "batch"), build=_build, meta=meta
        )
    }


def warmup_compile(
    config: FasterRCNNConfig,
    include_eval: bool = True,
    cache_n: Optional[int] = None,
    include_serving: bool = False,
) -> Dict[str, float]:
    """AOT-compile the programs a training run of ``config`` would jit.

    Covers the per-step train program of the config's own feed (spmd
    backend, ``--cache-device`` selection feed when ``cache_n`` supplies
    the dataset length, host loader otherwise), the fused multi-step
    program when ``train.steps_per_dispatch > 1``, and (``include_eval``)
    the eval inference program. Returns {program_name: compile_seconds};
    with the persistent cache enabled, a warmed second run shows
    near-zero times here and — the point — at real-run startup.

    Everything comes from :func:`build_program_specs`, so the compiled
    executables are cache hits for the real run, not merely similar
    programs. Cached-feed programs need the cache length ``cache_n``
    (= len(dataset)) to pin shapes; without it the loader program is
    warmed instead (same step math, different feed plumbing)."""
    tracer = tspans.current_tracer()
    if config.mesh.param_sharding and config.mesh.num_model > 1:
        # model-parallel run (--mesh-shape with MP > 1; the decision
        # table already pinned backend='auto' for this combination)
        feed = "mp_zero" if config.train.shard_opt_state else "mp"
    elif config.train.backend == "spmd":
        if config.train.shard_opt_state:
            feed = (
                "zero_lamb" if config.train.optimizer == "lamb" else "zero"
            )
        else:
            feed = "spmd"
    elif config.data.cache_device and cache_n is not None:
        feed = "cached"
    else:
        feed = "loader"
    k = max(1, config.train.steps_per_dispatch)
    ks = (1,) if k == 1 else (1, k)
    specs = build_program_specs(
        config, feeds=(feed,), ks=ks, include_eval=include_eval, cache_n=cache_n
    )
    if include_serving:
        # pre-warm the serving engine's bucket matrix too, so a `frcnn
        # serve` start against the same persistent cache deserializes
        # instead of compiling
        specs = {**specs, **build_serving_specs(config)}

    # report under the registry's canonical feed-qualified names
    # (train_<feed>_k<K> / eval_infer / serve_<HxW>_b<N>) — the same keys
    # `frcnn audit` banks, so the two reports line up program-for-program
    from replication_faster_rcnn_tpu import ops as ops_pkg

    # the config's resolved ops backend pins every program here: for
    # backend=pallas this AOT pass (plus the persistent cache) is the ONLY
    # sanctioned route to an on-chip pallas compile — the trainer and the
    # serving engine trace under the same scope and hit the cache
    backend = ops_pkg.resolve_backend(config)
    times: Dict[str, float] = {}
    for spec in specs.values():
        with tracer.span(f"compile/{spec.name}", cat="compile"):
            t0 = time.perf_counter()
            with ops_pkg.backend_scope(backend):
                jitted, args = spec.build()
                jitted.lower(*args).compile()
            times[spec.name] = round(time.perf_counter() - t0, 3)
    return times
