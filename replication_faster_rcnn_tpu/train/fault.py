"""Fault-tolerance layer: guarded updates, preemption-safe shutdown, and
verified checkpoint restore with latest-good fallback.

PR 1 built the *eyes* (health metrics count nonfinite grad entries per
step, the watchdog records stall incidents); this module closes the
observe→react loop for the three failure modes that dominate long
schedules on preemptible capacity:

* **Poisoned gradients** — :func:`guarded_update` gates the optimizer
  update on ``nonfinite_count == 0`` inside the jitted step, so one NaN
  batch skips the update (params, Adam moments AND BatchNorm stats carry
  through unchanged) instead of silently poisoning Adam's moments for
  the rest of the run. The host-side :class:`SkipMonitor` turns the
  per-step ``skipped`` flags into consecutive-skip escalation: a
  transient blow-up costs one step, a persistently-NaN run halts with a
  descriptive error instead of burning an epoch of wasted compute.
* **Preemption** — :class:`GracefulShutdown` converts SIGTERM/SIGINT
  into a flag the trainer checks at each dispatch boundary; the loop
  saves an emergency checkpoint (tagged in the manifest) and exits via
  :class:`Preempted` with a distinct exit code so a supervisor can tell
  "preempted, resume me" from "crashed".
* **Torn checkpoints** — every save writes a sidecar manifest (step,
  config hash, leaf count, per-leaf CRC32); :func:`verified_restore`
  checks the restored tree against it and, on corruption or load
  failure, walks back to the newest step that verifies, logging what
  was discarded — a truncated latest directory costs one checkpoint
  interval, not the run.

Everything device-side is a scalar predicate + per-leaf selects, so the
guarded step is bit-identical to the unguarded one on clean gradients
and identical across all three feeds (host loader, ``--cache-device``,
shard_map) and across fused ``steps_per_dispatch`` chunks — the gate
lives in the two step bodies everything else composes from.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import signal
import threading
import zlib
from datetime import datetime, timezone
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from replication_faster_rcnn_tpu.faultlib import failpoints
from replication_faster_rcnn_tpu.telemetry import spans as tspans
from replication_faster_rcnn_tpu.telemetry import stages
from replication_faster_rcnn_tpu.telemetry.health import health_metrics

# Distinct exit code for "preempted with a verified emergency checkpoint;
# restart me with --resume" — EX_TEMPFAIL in sysexits.h, i.e. transient,
# retry. Crashes keep their tracebacks and nonzero codes; a supervisor
# branching on 75 can requeue instead of paging.
EXIT_PREEMPTED = 75

# Exit code for "a peer rank was lost; re-form the fleet at the surviving
# world size and resume me" — the elastic supervisor (parallel/elastic.py)
# branches on it (or on the durable shrink-intent file, for the watchdog
# path that must os._exit before the JAX coordination service's ~10s
# SIGABRT) to respawn the child at the next generation.
EXIT_FLEET_SHRINK = 76

NONFINITE_POLICIES = ("apply", "skip", "halt")

MANIFEST_DIRNAME = "manifests"
MANIFEST_SCHEMA = "ckpt_manifest/v1"


class Preempted(RuntimeError):
    """Raised by the trainer after a graceful-shutdown request has been
    honored: the emergency checkpoint is on disk and verified."""

    def __init__(self, step: int, reason: str = "signal"):
        super().__init__(
            f"training preempted ({reason}) at step {step}; emergency "
            "checkpoint saved — restart with --resume"
        )
        self.step = int(step)
        self.reason = reason


class NonFiniteEscalation(FloatingPointError):
    """Raised when nonfinite-gradient skips exceed the configured budget
    (or immediately under ``nonfinite_policy='halt'``)."""


class FleetShrink(RuntimeError):
    """Raised at a dispatch boundary when a peer rank's heartbeat lease
    has expired: this rank must exit (EXIT_FLEET_SHRINK) so the elastic
    supervisor can re-form the fleet at the surviving world size. No
    emergency checkpoint is attempted — every save is a cross-process
    collective that would hang on the dead peer — so resume falls back to
    the last CRC-verified step (bound the window with
    ``train.checkpoint_every_steps``)."""

    def __init__(self, step: int, lost, survivors):
        self.step = int(step)
        self.lost = sorted(int(r) for r in lost)
        self.survivors = sorted(int(r) for r in survivors)
        super().__init__(
            f"fleet shrink at step {self.step}: rank(s) {self.lost} lost "
            f"heartbeat lease; survivors {self.survivors} re-form at world "
            f"size {len(self.survivors)}"
        )


# --------------------------------------------------------------- jitted gate


def guarded_update(
    tx: optax.GradientTransformation,
    state,
    grads: Any,
    new_stats: Any,
    policy: str = "skip",
) -> Tuple[Any, Dict[str, jnp.ndarray]]:
    """Optimizer update gated on gradient finiteness, inside the jitted step.

    Returns ``(new_state, health)`` where ``health`` is the standard
    health-metric dict plus a ``skipped`` flag (1.0 when the update was
    withheld). Under ``policy='apply'`` the update is unconditional (the
    pre-guard behavior). Under ``'skip'``/``'halt'`` a gradient tree with
    any NaN/Inf entry leaves params, optimizer state AND BatchNorm stats
    bit-identical to their pre-step values — the gate is a scalar
    predicate feeding per-leaf selects, so a clean step is bit-identical
    to the unguarded one, and the same code composes unchanged under
    `lax.scan` (fused multi-step) and `shard_map` (call it on post-psum
    grads so every shard takes the same branch). ``step`` advances either
    way: it counts dispatched batches, and the fold_in(rng, step) keying
    must keep moving so the next batch draws fresh sampling randomness.

    ``'halt'`` gates exactly like ``'skip'`` — params must be clean when
    the host-side :class:`SkipMonitor` raises on the flag.

    The whole of it runs under the ``frcnn.update`` scope
    (`telemetry/stages.py`), in every step program that calls it.
    """
    if policy not in NONFINITE_POLICIES:
        raise ValueError(
            f"nonfinite_policy must be one of {NONFINITE_POLICIES}, got {policy!r}"
        )
    with jax.named_scope(stages.UPDATE):
        return _guarded_update(tx, state, grads, new_stats, policy)


def _guarded_update(tx, state, grads, new_stats, policy):
    updates, new_opt = tx.update(grads, state.opt_state, state.params)
    new_params = optax.apply_updates(state.params, updates)
    health = health_metrics(grads, state.params, updates)
    if policy == "apply":
        health["skipped"] = jnp.zeros((), jnp.float32)
        return (
            state.replace(
                step=state.step + 1,
                params=new_params,
                batch_stats=new_stats,
                opt_state=new_opt,
            ),
            health,
        )
    ok = health["nonfinite_count"] == 0

    def keep(new, old):
        # select, not arithmetic masking: NaNs on the untaken side must
        # not propagate, and the taken side must pass through bitwise
        return jnp.where(ok, new, old)

    new_state = state.replace(
        step=state.step + 1,
        params=jax.tree_util.tree_map(keep, new_params, state.params),
        batch_stats=jax.tree_util.tree_map(keep, new_stats, state.batch_stats),
        opt_state=jax.tree_util.tree_map(keep, new_opt, state.opt_state),
    )
    health["skipped"] = 1.0 - ok.astype(jnp.float32)
    return new_state, health


def check_step_metrics(metrics: Dict[str, Any], step: int) -> Dict[str, float]:
    """Log-boundary metric validation, guard-aware: a row whose update was
    withheld (``skipped > 0``) is allowed to carry non-finite diagnostics
    (the NaN loss/grad_norm of the poisoned batch ARE the evidence); any
    other row fails fast exactly like :func:`utils.debug.finite_or_raise`.
    """
    from replication_faster_rcnn_tpu.utils.debug import finite_or_raise

    vals = {k: float(v) for k, v in metrics.items()}
    if vals.get("skipped", 0.0) > 0.0:
        return vals
    return finite_or_raise(vals, step)


# ------------------------------------------------------- host-side monitor


class SkipMonitor:
    """Consecutive-skip escalation from the per-step ``skipped`` flags.

    The trainer feeds every dispatch's flag in via :meth:`observe` (a
    scalar, or a stacked ``[K]`` array from a fused chunk) WITHOUT
    forcing a device sync — flags are retained as device arrays and only
    fetched in :meth:`drain`, which the trainer calls where it already
    syncs (log boundaries, epoch ends). Under ``policy='halt'`` observe
    drains immediately: promptness over pipelining is the point of that
    policy.

    Escalation (``consecutive >= max_consecutive``, or any skip under
    ``halt``) calls ``on_escalate(kind, **fields)`` — the trainer routes
    it to the watchdog incident log — then raises
    :class:`NonFiniteEscalation` with a descriptive message.
    """

    # auto-drain threshold: pending flags this old are long computed, so
    # fetching them cannot stall the pipeline; bounds memory for callers
    # that never hit a log boundary (direct train_one_batch loops)
    _AUTO_DRAIN = 512

    def __init__(
        self,
        policy: str = "skip",
        max_consecutive: int = 10,
        on_escalate: Optional[Callable[..., None]] = None,
    ):
        if policy not in NONFINITE_POLICIES:
            raise ValueError(
                f"nonfinite_policy must be one of {NONFINITE_POLICIES}, "
                f"got {policy!r}"
            )
        self.policy = policy
        self.max_consecutive = int(max_consecutive)
        self.on_escalate = on_escalate
        self.consecutive = 0
        self.total_skipped = 0
        self.last_skipped_step: Optional[int] = None
        self._pending: List[Tuple[int, Any]] = []

    def observe(self, first_step: int, metrics: Dict[str, Any]) -> None:
        """Record one dispatch's ``skipped`` flag(s); ``first_step`` is the
        1-indexed global step of the dispatch's first fused step."""
        if self.policy == "apply" or "skipped" not in metrics:
            return
        self._pending.append((int(first_step), metrics["skipped"]))
        if self.policy == "halt" or len(self._pending) >= self._AUTO_DRAIN:
            self.drain()

    def drain(self) -> None:
        """Fetch pending flags and update the consecutive counter; raises
        :class:`NonFiniteEscalation` past the budget."""
        pending, self._pending = self._pending, []
        for first, flags in pending:
            with tspans.current_tracer().span("fault/skip_drain", cat="sync"):
                flags = jax.device_get(flags)
            arr = np.atleast_1d(np.asarray(flags, np.float64))
            for off, flag in enumerate(arr):
                if flag > 0:
                    self.consecutive += 1
                    self.total_skipped += 1
                    self.last_skipped_step = first + off
                    if self.policy == "halt":
                        self._escalate(
                            "nonfinite_gradient halted training "
                            f"(nonfinite_policy='halt') at step {first + off}: "
                            "the update was withheld and params are clean; "
                            "inspect the batch, or train with "
                            "nonfinite_policy='skip' to ride through "
                            "transients"
                        )
                    if self.consecutive >= self.max_consecutive:
                        self._escalate(
                            f"{self.consecutive} consecutive nonfinite-"
                            "gradient skips (>= train.max_consecutive_skips="
                            f"{self.max_consecutive}, last at step "
                            f"{first + off}, {self.total_skipped} skipped "
                            "total): gradients are persistently non-finite, "
                            "not a transient — lower the lr, check the data, "
                            "or enable --debug-nans to pinpoint the op"
                        )
                else:
                    self.consecutive = 0

    def _escalate(self, message: str) -> None:
        if self.on_escalate is not None:
            try:
                self.on_escalate(
                    "nonfinite_escalation",
                    policy=self.policy,
                    consecutive=self.consecutive,
                    total_skipped=self.total_skipped,
                    last_skipped_step=self.last_skipped_step,
                )
            except Exception:  # incident recording must not mask the error
                pass
        raise NonFiniteEscalation(message)


# ----------------------------------------------------------- shutdown flag


class GracefulShutdown:
    """Convert SIGTERM/SIGINT into a flag checked at dispatch boundaries.

    Context manager: on enter, installs handlers that set
    :attr:`requested` (first signal) — the training loop then saves an
    emergency checkpoint and raises :class:`Preempted` at the next
    boundary. A second delivery of the same signal restores the previous
    handler and re-raises it, so a stuck save can still be killed. On
    exit, previous handlers are restored.

    Installed best-effort: off the main thread (where ``signal.signal``
    raises) the flag remains programmatically settable via
    :meth:`request` but no handlers are bound.
    """

    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self.signals = tuple(signals)
        self._prev: Dict[int, Any] = {}
        self._requested = threading.Event()
        self.reason: Optional[str] = None

    @property
    def requested(self) -> bool:
        return self._requested.is_set()

    def request(self, reason: str = "manual") -> None:
        if not self._requested.is_set():
            self.reason = reason
            self._requested.set()

    def _handle(self, signum, frame) -> None:
        if self._requested.is_set():
            # second signal: give up gracefulness, fall back to the
            # previous disposition and re-deliver
            prev = self._prev.get(signum, signal.SIG_DFL)
            signal.signal(signum, prev)
            os.kill(os.getpid(), signum)
            return
        try:
            name = signal.Signals(signum).name
        except ValueError:  # pragma: no cover - unknown signal number
            name = f"signal {signum}"
        self.request(name)

    def __enter__(self) -> "GracefulShutdown":
        for sig in self.signals:
            try:
                self._prev[sig] = signal.signal(sig, self._handle)
            except (ValueError, OSError):  # not the main thread
                pass
        return self

    def __exit__(self, *exc: Any) -> bool:
        for sig, prev in self._prev.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):  # pragma: no cover - defensive
                pass
        self._prev.clear()
        return False


# ------------------------------------------------------ checkpoint manifest


def config_hash(config) -> str:
    """Stable short hash of a (dataclass) config — manifest provenance."""
    payload = json.dumps(
        dataclasses.asdict(config), sort_keys=True, default=str
    )
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def _leaf_records(tree: Any) -> Dict[str, Dict[str, Any]]:
    leaves: Dict[str, Dict[str, Any]] = {}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    with tspans.current_tracer().span("checkpoint/manifest", cat="checkpoint"):
        host_leaves = [jax.device_get(leaf) for _path, leaf in flat]
    for (path, _leaf), fetched in zip(flat, host_leaves):
        arr = np.asarray(fetched)
        leaves[jax.tree_util.keystr(path)] = {
            "crc32": zlib.crc32(np.ascontiguousarray(arr).tobytes()),
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
        }
    return leaves


def manifest_path(workdir: str, step: int) -> str:
    return os.path.join(
        os.path.abspath(workdir), MANIFEST_DIRNAME, f"{int(step)}.json"
    )


def run_topology(config=None, mesh=None) -> Dict[str, Any]:
    """The runtime topology a checkpoint was saved under: process count,
    global device count, mesh shape, and whether the optimizer state was
    ZeRO-sharded at save time. Provenance, not a restore constraint —
    checkpoints are saved fully replicated (host-gathered), so
    `verified_restore` re-places them onto whatever mesh the restoring
    run built (a preempted 2-proc×4-dev run resumes on 1-proc×8-dev and
    vice versa); the CRCs are computed on the gathered host tree and are
    therefore topology-invariant."""
    topo: Dict[str, Any] = {
        "process_count": jax.process_count(),
        "device_count": jax.device_count(),
        # fleet generation (elastic training): 0 for a static fleet; the
        # elastic supervisor bumps it per re-formation via the child env
        "generation": int(os.environ.get("FRCNN_FLEET_GENERATION", "0") or 0),
    }
    if mesh is not None:
        topo["mesh_shape"] = {
            str(name): int(size) for name, size in mesh.shape.items()
        }
    if config is not None:
        topo["shard_opt_state"] = bool(config.train.shard_opt_state)
    return topo


def write_manifest(
    workdir: str,
    step: int,
    state: Any,
    config=None,
    kind: str = "scheduled",
    writer: str = "sync",
    topology: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Sidecar manifest for the checkpoint at ``step``: leaf count +
    per-leaf CRC32/shape/dtype of the saved tree, the config hash, the
    save ``kind`` (scheduled | emergency | crash | final), and the saving
    run's topology (:func:`run_topology` unless passed explicitly).
    Written atomically next to — not inside — the orbax step directory,
    so orbax never sees a foreign file and a manifest for a
    garbage-collected step is merely stale, not corrupting.

    ``writer`` records whether the save ran on the trainer thread
    ("sync") or the background checkpoint writer ("async",
    train/async_checkpoint.py) — provenance for post-mortems; restore
    verification treats both identically."""
    leaves = _leaf_records(state)
    manifest = {
        "schema": MANIFEST_SCHEMA,
        "step": int(step),
        "kind": kind,
        "writer": writer,
        "saved_utc": datetime.now(timezone.utc).isoformat(),
        "config_hash": config_hash(config) if config is not None else None,
        "topology": topology if topology is not None else run_topology(config),
        "leaf_count": len(leaves),
        "leaves": leaves,
    }
    # failpoint: ioerror raises before any bytes land; torn_write /
    # crc_corrupt hit the tmp file so the published manifest is damaged
    # (load_manifest treats unreadable JSON as missing → step discarded)
    inj = failpoints.fire("checkpoint.manifest", step=int(step), kind=kind)
    path = manifest_path(workdir, step)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
    if inj is not None and inj.kind in ("torn_write", "crc_corrupt"):
        failpoints.apply_file_fault(inj, tmp)
    os.replace(tmp, path)
    return manifest


def load_manifest(workdir: str, step: int) -> Optional[Dict[str, Any]]:
    path = manifest_path(workdir, step)
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if manifest.get("schema") != MANIFEST_SCHEMA:
        return None
    return manifest


FEED_BASENAME = "feed.jsonl"


def feed_path(workdir: str) -> str:
    return os.path.join(
        os.path.abspath(workdir), MANIFEST_DIRNAME, FEED_BASENAME
    )


def publish_manifest_event(
    workdir: str, step: int, kind: str = "scheduled", writer: str = "sync"
) -> None:
    """Append one line to ``manifests/feed.jsonl`` — the rollout feed.

    The manifest files themselves are the versions; this append-only log
    records *publication order* so the serving-side watcher
    (serving/rollout/) can tail it instead of re-scanning and
    re-validating every manifest per poll, and so a step that is later
    pruned still leaves a publication record. Best-effort: a failed
    append never fails the save that produced the checkpoint (the
    watcher falls back to directory scans)."""
    event = {
        "step": int(step),
        "kind": kind,
        "writer": writer,
        "published_utc": datetime.now(timezone.utc).isoformat(),
    }
    try:
        path = feed_path(workdir)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(event, sort_keys=True) + "\n")
    except OSError:  # pragma: no cover - best-effort publication
        pass


def prune_manifests(workdir: str, live_steps) -> None:
    """Drop manifests whose checkpoints orbax has garbage-collected."""
    d = os.path.join(os.path.abspath(workdir), MANIFEST_DIRNAME)
    if not os.path.isdir(d):
        return
    keep = {f"{int(s)}.json" for s in live_steps}
    for name in os.listdir(d):
        if name.endswith(".json") and name not in keep:
            try:
                os.remove(os.path.join(d, name))
            except OSError:  # pragma: no cover - best-effort housekeeping
                pass


def verify_state(
    manifest: Dict[str, Any], state: Any, expected_config_hash: Optional[str] = None
) -> List[str]:
    """Integrity problems (empty list = verified). Config-hash drift is
    reported but integrity is judged on the tree alone — warm-starting
    under an edited config is legitimate; restoring torn bytes is not."""
    problems: List[str] = []
    got = _leaf_records(state)
    want = manifest.get("leaves", {})
    if len(got) != manifest.get("leaf_count"):
        problems.append(
            f"leaf count {len(got)} != manifest {manifest.get('leaf_count')}"
        )
    for key, rec in want.items():
        if key not in got:
            problems.append(f"missing leaf {key}")
        elif got[key]["crc32"] != rec["crc32"]:
            problems.append(
                f"checksum mismatch at {key} "
                f"(crc32 {got[key]['crc32']} != {rec['crc32']})"
            )
    for key in got:
        if key not in want:
            problems.append(f"unexpected leaf {key}")
    if (
        expected_config_hash is not None
        and manifest.get("config_hash") not in (None, expected_config_hash)
    ):
        # provenance note, not an integrity failure
        problems = problems  # no-op: documented decision point
    return problems


@dataclasses.dataclass
class RestoreResult:
    step: Optional[int]
    state: Any
    manifest: Optional[Dict[str, Any]]
    discarded: List[Tuple[int, str]]


def verified_restore(
    mgr,
    template: Any,
    workdir: str,
    step: Optional[int] = None,
    log: Callable[[str], None] = print,
) -> RestoreResult:
    """Restore the newest checkpoint that loads AND matches its manifest.

    ``mgr`` is an orbax CheckpointManager, ``template`` the host-side
    tree to restore into. With an explicit ``step`` there is no walking:
    a corrupt requested step raises (silently handing back older weights
    than asked for would be worse than failing). With ``step=None`` the
    steps are tried newest→oldest; every discard (load failure or
    checksum mismatch) is logged and returned so the caller can delete
    the torn directories. A checkpoint with no manifest (pre-manifest
    legacy) restores unverified, with a log line saying so.
    """
    import orbax.checkpoint as ocp

    steps = sorted(int(s) for s in mgr.all_steps())
    if step is not None:
        steps = [s for s in steps if s == int(step)]
        if not steps:
            raise ValueError(
                f"checkpoint step {step} not found in {workdir} "
                f"(available: {sorted(mgr.all_steps())})"
            )
    discarded: List[Tuple[int, str]] = []
    for s in reversed(steps):
        try:
            restored = mgr.restore(s, args=ocp.args.StandardRestore(template))
        except Exception as e:  # torn/truncated step dir, orbax metadata, ...
            why = f"restore failed: {type(e).__name__}: {str(e)[:200]}"
            if step is not None:
                raise RuntimeError(
                    f"checkpoint step {s} in {workdir} is unrecoverable "
                    f"({why}); drop --checkpoint-step to fall back to the "
                    "newest verifiable step"
                ) from e
            discarded.append((s, why))
            log(f"fault: discarding checkpoint step {s} — {why}")
            continue
        manifest = load_manifest(workdir, s)
        if manifest is None:
            log(
                f"fault: checkpoint step {s} has no manifest "
                "(pre-manifest save) — restoring unverified"
            )
            return RestoreResult(s, restored, None, discarded)
        problems = verify_state(manifest, restored)
        if problems:
            why = "; ".join(problems[:3]) + (
                f" (+{len(problems) - 3} more)" if len(problems) > 3 else ""
            )
            if step is not None:
                raise RuntimeError(
                    f"checkpoint step {s} in {workdir} failed manifest "
                    f"verification: {why}"
                )
            discarded.append((s, why))
            log(f"fault: discarding checkpoint step {s} — {why}")
            continue
        if discarded:
            log(
                f"fault: fell back to verified step {s} after discarding "
                f"{[d[0] for d in discarded]}"
            )
        saved_topo = manifest.get("topology") or {}
        current = run_topology()
        drift = {
            k: (saved_topo[k], current[k])
            for k in ("process_count", "device_count")
            if k in saved_topo and saved_topo[k] != current[k]
        }
        if drift:
            # informational: state is saved fully replicated, so the
            # caller re-places it onto the current mesh bit-identically
            log(
                f"fault: checkpoint step {s} was saved on a different "
                f"topology ({', '.join(f'{k} {a}->{b}' for k, (a, b) in drift.items())}); "
                "re-placing the replicated state onto the current mesh"
            )
        return RestoreResult(s, restored, manifest, discarded)
    return RestoreResult(None, None, None, discarded)
