"""Training orchestration — capability parity with reference ``trainer``
(`train.py:13-151`), rebuilt around one jitted SPMD step:

  * mesh setup + batch sharding (reference: none — single device)
  * Adam + per-epoch cosine schedule (reference `train.py:139-140,148`)
  * per-step scalar metrics incl. images/sec (reference prints raw losses
    every step, `train.py:124`)
  * orbax checkpointing of params + BN stats + optimizer state + step with
    resume (the reference saves params-only every 10 epochs and restarts
    the schedule on load, `train.py:132-133,149-150` — SURVEY.md §5 flags
    this; here resume is exact)
  * optional pretrained-backbone graft (reference `resnet_torch.py:392-409`)
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import os
import sys
import time
from typing import Dict, Optional

import jax
import numpy as np

from replication_faster_rcnn_tpu.config import FasterRCNNConfig
from replication_faster_rcnn_tpu.data import DataLoader, make_dataset
from replication_faster_rcnn_tpu.faultlib import failpoints
from replication_faster_rcnn_tpu.data.prefetch_device import (
    STAGED,
    DevicePrefetcher,
)
from replication_faster_rcnn_tpu.parallel import (
    Plan,
    compile_step_with_plan,
    fit_data_parallelism,
    is_coordinator,
    make_mesh,
    gather_replicated,
    replicate_tree,
    stage_to_devices,
    validate_parallel,
)
from replication_faster_rcnn_tpu.parallel import elastic as elastic_fleet
from replication_faster_rcnn_tpu.train import fault
from replication_faster_rcnn_tpu.train.async_checkpoint import (
    AsyncCheckpointWriter,
)
from replication_faster_rcnn_tpu.train.warmup import (
    keep_compiled,
    place_compile_cache,
    program_context,
)
from replication_faster_rcnn_tpu.train.train_step import (
    TrainState,
    build_multi_step,
    create_train_state,
    host_schedule,
    make_cached_multi_step,
    make_optimizer,
    make_train_step,
    model_kind,
)
from replication_faster_rcnn_tpu.telemetry import compiles
from replication_faster_rcnn_tpu.telemetry import spans as tspans
from replication_faster_rcnn_tpu.telemetry.watchdog import StallWatchdog
from replication_faster_rcnn_tpu.utils.logging import MetricLogger

COUNTER_EVERY = 10  # steps between the counters a sequence model's trainer traces


def _rows(batch) -> int:
    """Samples in a host batch (or a --cache-device selection)."""
    return next(iter(batch.values())).shape[0]


def load_eval_variables(
    config: FasterRCNNConfig,
    workdir: str,
    step: Optional[int] = None,
):
    """(model, variables) for inference: fresh init, then the latest (or
    given) checkpoint restored if one exists. Avoids constructing a Trainer
    — eval must not require the train split or an optimizer."""
    import orbax.checkpoint as ocp

    from replication_faster_rcnn_tpu.models.faster_rcnn import FasterRCNN  # noqa: F401

    config.require_detector("eval, predict and serve")
    tx, _ = make_optimizer(config, steps_per_epoch=1)
    model, state = create_train_state(
        config, jax.random.PRNGKey(config.train.seed), tx
    )
    if os.path.isdir(workdir):
        mgr = ocp.CheckpointManager(os.path.abspath(workdir))
        try:
            if mgr.all_steps():
                # manifest-verified restore with latest-good fallback: a
                # torn newest step must not make eval unrecoverable either
                with tspans.current_tracer().span(
                    "checkpoint/restore", cat="checkpoint"
                ):
                    template = jax.device_get(state)
                result = fault.verified_restore(
                    mgr, template, os.path.abspath(workdir), step=step,
                )
                if result.state is not None:
                    state = result.state
        finally:
            mgr.close()
    return model, {"params": state.params, "batch_stats": state.batch_stats}


class Trainer:
    def __init__(
        self,
        config: FasterRCNNConfig,
        workdir: str = "checkpoints",
        dataset=None,
        devices=None,
        telemetry_dir: Optional[str] = None,
        stall_timeout_s: float = 300.0,
    ) -> None:
        self.config = config
        self.workdir = workdir
        # multi-process identity: the coordinator (process 0) owns the
        # checkpoint store, manifests and the canonical telemetry files;
        # every other rank writes rank-suffixed telemetry files so
        # `frcnn telemetry` can merge and group per-rank traces
        self._rank = jax.process_index()
        self._process_count = jax.process_count()

        # --- telemetry: span tracer + JSONL metrics + stall watchdog.
        # With no telemetry_dir everything collapses to no-ops (NULL
        # tracer spans, stream-only logger, no watchdog thread). The
        # tracer comes first, so set-up is seen from inside: `setup/trainer`
        # over the constructor, its slow phases as children, and every
        # compile's `jit/*` spans from the process's compile listener.
        self.telemetry_dir = telemetry_dir
        self.watchdog: Optional[StallWatchdog] = None
        if telemetry_dir:
            os.makedirs(telemetry_dir, exist_ok=True)
            rank = self._rank if self._process_count > 1 else None

            def _rank_file(name: str) -> str:
                # trace.json -> trace.rank1.json on non-coordinator ranks
                if not rank:
                    return os.path.join(telemetry_dir, name)
                stem, ext = os.path.splitext(name)
                return os.path.join(telemetry_dir, f"{stem}.rank{rank}{ext}")

            self.tracer = tspans.SpanTracer(
                _rank_file("trace.json"),
                rank=rank,
                max_events=config.telemetry.trace_max_events,
            )
            # install process-wide so the loader/evaluator/device-cache
            # span call sites (which take no tracer parameter) attach here
            tspans.set_tracer(self.tracer)
            self.logger = MetricLogger(
                jsonl_path=_rank_file("metrics.jsonl"), rank=rank
            )
            self.watchdog = StallWatchdog(
                timeout_s=stall_timeout_s,
                snapshot_path=_rank_file("watchdog.jsonl"),
                progress_path=_rank_file("progress.json"),
                tracer=self.tracer,
                rank=rank,
                on_stall=lambda snap: self.logger.event(
                    "stall",
                    elapsed_s=snap.get("elapsed_since_progress_s"),
                    last_step=snap.get("last_step"),
                    last_phase=snap.get("last_phase"),
                ),
            )
        else:
            self.tracer = tspans.NULL_TRACER
            self.logger = MetricLogger()
        compiles.install()
        setup_t0 = self.tracer.now_us()
        # persistent XLA compilation cache: must be placed before the
        # first jitted call traces — jit is lazy, so doing it here covers
        # every program this trainer compiles. The train programs' and
        # init's executables are kept beside it (`keep` below).
        compile_cache = place_compile_cache(config.compile.cache_dir)
        validate_parallel(
            config, len(devices) if devices is not None else None
        )
        if config.mesh.num_data <= 0:
            # fit the data axis to the batch (a non-dividing batch fails in
            # jit with an opaque sharding error — e.g. the reference's
            # default batch 2 on an 8-chip host), leaving room for any
            # model-parallel axis
            n_dev = len(devices) if devices is not None else len(jax.devices())
            n_dev //= max(1, config.mesh.num_model)
            config = config.replace(
                mesh=dataclasses.replace(
                    config.mesh,
                    num_data=fit_data_parallelism(config.train.batch_size, n_dev),
                )
            )
            self.config = config
        self.mesh = make_mesh(config.mesh, devices)

        # fault-tolerance plumbing (train/fault.py): consecutive-skip
        # escalation for the guarded update's `skipped` flags, and the
        # dispatch-boundary shutdown flag train() installs
        self.skip_monitor = fault.SkipMonitor(
            policy=config.train.nonfinite_policy,
            max_consecutive=config.train.max_consecutive_skips,
            on_escalate=self._fault_incident,
        )
        self._host_step = 0  # host mirror of state.step: no sync to read
        self._shutdown: Optional[fault.GracefulShutdown] = None
        # the step's counters that go to the tracer (a sequence model's
        # routing counts; a detector has none), and the steps' metrics that
        # wait, still on the device, for a moment the host may read them
        self._counters: tuple = ()
        if config.is_sequence_model and telemetry_dir:
            from replication_faster_rcnn_tpu.models.lm import COUNTERS

            self._counters = COUNTERS
        self._counters_pending: list = []
        self._batch_keys = model_kind(config).batch_keys

        # chaos runs: every injected fault lands in the metric stream and
        # the watchdog incident log, so a post-mortem can line up observed
        # failures against the schedule that caused them
        if failpoints.armed():
            failpoints.set_sink(self._chaos_sink)

        # a sequence model's seeded documents draw their ids from the
        # vocabulary rows held here
        ids = {"id_rows": config.lm.vocab_rows} if config.is_sequence_model else {}
        with self.tracer.span("setup/dataset", cat="setup"):
            self.dataset = dataset if dataset is not None else make_dataset(
                config.data, "train", **ids
            )
        self.device_cache = None
        self.sampler = None
        if config.data.cache_device:
            # device-resident feed: dataset lives in HBM, the step gathers
            # and augments on device, the host ships only per-step indices
            # (data/device_cache.py — the route past a transfer-bound
            # loader). The jitter resample necessarily runs on device in
            # this mode, the path already proven at training quality
            # (0.591 vs host 0.592 val mAP, PARITY.md). Feed/backend
            # compatibility (cache×spmd, cache×multiprocess, ...) was
            # already rejected above by the Plan.validate decision table.
            from replication_faster_rcnn_tpu.data.device_cache import (
                CachedSampler,
                DeviceCache,
            )

            with self.tracer.span("setup/dataset", cat="setup", feed="device_cache"):
                self.device_cache = DeviceCache(self.dataset, mesh=self.mesh)
            with self.tracer.span("setup/loader", cat="setup", feed="device_cache"):
                self.sampler = CachedSampler(
                    len(self.dataset),
                    self.device_cache.image_hw,
                    batch_size=config.train.batch_size,
                    seed=config.train.seed,
                    hflip=config.data.augment_hflip,
                    scale_range=config.data.augment_scale,
                    process_index=self._rank,
                    process_count=self._process_count,
                    train_resolutions=config.data.train_resolutions,
                    bucket_chunk=max(1, config.train.steps_per_dispatch),
                )
            self.loader = None
            steps_per_epoch = max(len(self.sampler), 1)
        else:
            # each process loads only its contiguous block of every global
            # batch (loader.py); batch_size stays GLOBAL so schedules and
            # step counts are topology-invariant
            with self.tracer.span("setup/loader", cat="setup", feed="loader"):
                self.loader = DataLoader(
                    self.dataset,
                    batch_size=config.train.batch_size,
                    shuffle=True,
                    seed=config.train.seed,
                    prefetch=config.data.loader_prefetch,
                    num_workers=config.data.loader_workers,
                    worker_mode=config.data.loader_mode,
                    augment_hflip=config.data.augment_hflip,
                    augment_scale=config.data.augment_scale,
                    augment_scale_device=config.data.augment_scale_device,
                    augment_device=config.data.augment_device,
                    augment_translate=config.data.augment_translate,
                    cache_ram=config.data.loader_cache_ram,
                    process_index=self._rank,
                    process_count=self._process_count,
                    train_resolutions=config.data.train_resolutions,
                    bucket_chunk=max(1, config.train.steps_per_dispatch),
                )
            steps_per_epoch = max(len(self.loader), 1)
        # n_shards sizes LAMB's psum'd trust-ratio norms to the data axis
        # the per-shard ZeRO update runs over; inert for adam/lars.
        n_shards = self.mesh.shape[config.mesh.data_axis]
        self.tx, self.schedule = make_optimizer(
            config, steps_per_epoch, n_shards=n_shards
        )
        # host-math twin for log rows: evaluating the jnp schedule on the
        # host would build + sync a device scalar every logged step
        self.host_schedule = host_schedule(config, steps_per_epoch)
        # every program below is kept across processes under a key of all
        # its lowering reads; the same jit object where no cache is placed
        context = program_context(config, steps_per_epoch, n_shards)

        def keep(jitted, label: str, plan: Optional[Plan] = None):
            parts = context if plan is None else context + (repr(plan),)
            return keep_compiled(jitted, compile_cache, label, parts)

        with self.tracer.span("setup/init_state", cat="setup"):
            self.model, state = create_train_state(
                config, jax.random.PRNGKey(config.train.seed), self.tx, keep=keep
            )
        from replication_faster_rcnn_tpu.parallel.zero import (
            place_train_state,
            train_state_shardings,
        )

        # params/BN replicated (params mp-sharded over the model axis
        # under mesh.param_sharding); Adam moments sharded over the data
        # axis when ZeRO-1 weight-update sharding is on (`parallel/zero.py`)
        self._state_shardings = train_state_shardings(
            state, self.mesh, config.mesh, config.train.shard_opt_state
        )
        self._mp = (
            config.mesh.param_sharding
            and self.mesh.shape[config.mesh.model_axis] > 1
        )
        with self.tracer.span("setup/place_state", cat="setup"):
            self.state: TrainState = place_train_state(state, self._state_shardings)

        # --- dispatch: every train program compiles through ONE layer,
        # parallel/plan.py::compile_step_with_plan. The shard_map backend
        # builds its own Plan (in/out specs) inside
        # make_shard_map_train_step; the jit auto-partitioning feeds share
        # this pjit plan — donated state, out_shardings pinning the
        # (possibly mp-sharded) state layout stable across steps.
        self._step_plan = Plan(
            mesh=self.mesh,
            donate_argnums=(0,),
            out_shardings=(self._state_shardings, None),
            param_specs=jax.tree_util.tree_map(
                lambda s: s.spec, self._state_shardings.params
            ),
            label="train_step",
        )
        if config.train.backend == "spmd":
            from replication_faster_rcnn_tpu.parallel import make_shard_map_train_step

            # explicit-collective step (psum allreduce + sync-BN); the
            # parameter tree is identical, so eval/checkpoints are unchanged.
            # state_template carries full leaf shapes so the ZeRO variant
            # (train.shard_opt_state) can derive shard dims outside the body
            self.jitted_step, _ = make_shard_map_train_step(
                config, self.tx, self.mesh, state_template=self.state
            )
        elif config.data.cache_device:
            from replication_faster_rcnn_tpu.train.train_step import (
                make_cached_train_step,
            )

            # (state, cache, sel) step; the cache argument is the same
            # device-resident buffers every call — never donated
            self.jitted_step = compile_step_with_plan(
                make_cached_train_step(self.model, config, self.tx),
                self._step_plan,
            )
        else:
            self.jitted_step = compile_step_with_plan(
                make_train_step(self.model, config, self.tx),
                self._step_plan,
            )
        # fused multi-step dispatch (train.steps_per_dispatch > 1): one
        # jitted call trains K steps via lax.scan (train_chunk). The plain
        # per-step function above stays — jit compiles lazily, so it only
        # costs a compile if an epoch tail (steps_per_epoch % K != 0) or a
        # direct train_one_batch caller actually runs it.
        self.steps_per_dispatch = max(1, config.train.steps_per_dispatch)
        self.jitted_multi_step = None
        if self.steps_per_dispatch > 1:
            k = self.steps_per_dispatch
            multi_plan = dataclasses.replace(
                self._step_plan, label=f"multi_step_k{k}"
            )
            if config.train.backend == "spmd":
                from replication_faster_rcnn_tpu.parallel import (
                    make_shard_map_train_step,
                )

                self.jitted_multi_step, _ = make_shard_map_train_step(
                    config, self.tx, self.mesh, steps_per_dispatch=k,
                    state_template=self.state,
                )
            elif config.data.cache_device:
                self.jitted_multi_step = compile_step_with_plan(
                    make_cached_multi_step(self.model, config, self.tx, k),
                    multi_plan,
                )
            else:
                self.jitted_multi_step = compile_step_with_plan(
                    build_multi_step(
                        make_train_step(self.model, config, self.tx), k
                    ),
                    multi_plan,
                )
        # ops.backend=pallas: pin the backend scope around every trace of
        # the step programs (jit is lazy — without this the first dispatch
        # would trace the default XLA ops; see train/warmup.py). xla
        # configs get the jit objects back unchanged.
        from replication_faster_rcnn_tpu import ops as ops_pkg
        from replication_faster_rcnn_tpu.train.warmup import scope_jitted

        pallas = ops_pkg.resolve_backend(config) == "pallas"
        if pallas:
            self.jitted_step = scope_jitted(self.jitted_step, config)
            if self.jitted_multi_step is not None:
                self.jitted_multi_step = scope_jitted(
                    self.jitted_multi_step, config
                )
        # the shard_map programs build their plans inside
        # make_shard_map_train_step, from what the key already holds
        spmd = config.train.backend == "spmd"
        self.jitted_step = keep(
            self.jitted_step, "train_step", None if spmd else self._step_plan
        )
        if self.jitted_multi_step is not None:
            self.jitted_multi_step = keep(
                self.jitted_multi_step,
                f"multi_step_k{self.steps_per_dispatch}",
                None if spmd else multi_plan,
            )
        # multi-scale resolution buckets (data.train_resolutions): one
        # compiled program per bucket, each baking the bucket's static
        # (h, w) on-device resample into the trace (compute_losses) under
        # its own Plan label — the serving-bucket pattern applied to
        # training, so the strict harness, warmup registry and HLO audit
        # all see per-bucket programs as first-class citizens. The
        # unbucketed programs above stay (jit is lazy; they only compile
        # if dispatched). Buckets compose with every backend — the only
        # genuine constraint (spatial row divisibility per resolution) was
        # already checked by the Plan.validate decision table.
        self._bucket_resolutions = tuple(config.data.train_resolutions)
        self.jitted_bucket_steps = None
        self.jitted_bucket_multi_steps = None
        if self._bucket_resolutions:
            from replication_faster_rcnn_tpu.train.train_step import (
                make_cached_train_step,
            )

            k = self.steps_per_dispatch
            steps, multis = [], []

            def kept(jitted, label: str, plan: Optional[Plan] = None):
                return keep(
                    scope_jitted(jitted, config) if pallas else jitted, label, plan
                )

            for bh, bw in self._bucket_resolutions:
                if config.train.backend == "spmd":
                    # per-bucket shard_map program: the in/out specs shard
                    # batch dims only (resolution-independent), so each
                    # bucket reuses the same Plan shape with the bucket's
                    # resample traced into the per-shard body — bucketed
                    # multi-scale composes with spmd and ZeRO-1 unchanged
                    from replication_faster_rcnn_tpu.parallel import (
                        make_shard_map_train_step,
                    )

                    jitted, _ = make_shard_map_train_step(
                        config, self.tx, self.mesh,
                        state_template=self.state,
                        train_resolution=(bh, bw),
                    )
                    steps.append(kept(jitted, f"train_step_{bh}x{bw}"))
                    if k > 1:
                        mj, _ = make_shard_map_train_step(
                            config, self.tx, self.mesh,
                            steps_per_dispatch=k,
                            state_template=self.state,
                            train_resolution=(bh, bw),
                        )
                        multis.append(kept(mj, f"multi_step_k{k}_{bh}x{bw}"))
                    continue
                plan = dataclasses.replace(
                    self._step_plan, label=f"train_step_{bh}x{bw}"
                )
                if config.data.cache_device:
                    fn = make_cached_train_step(
                        self.model, config, self.tx, train_resolution=(bh, bw)
                    )
                else:
                    fn = make_train_step(
                        self.model, config, self.tx, train_resolution=(bh, bw)
                    )
                steps.append(
                    kept(compile_step_with_plan(fn, plan), plan.label, plan)
                )
                if k > 1:
                    mplan = dataclasses.replace(
                        self._step_plan, label=f"multi_step_k{k}_{bh}x{bw}"
                    )
                    if config.data.cache_device:
                        mfn = make_cached_multi_step(
                            self.model, config, self.tx, k,
                            train_resolution=(bh, bw),
                        )
                    else:
                        mfn = build_multi_step(
                            make_train_step(
                                self.model, config, self.tx,
                                train_resolution=(bh, bw),
                            ),
                            k,
                        )
                    multis.append(
                        kept(compile_step_with_plan(mfn, mplan), mplan.label, mplan)
                    )
            self.jitted_bucket_steps = steps
            if multis:
                self.jitted_bucket_multi_steps = multis
        # runtime hygiene gate (debug.strict / --strict): transfer guard +
        # recompile detector around every dispatch, armed after warmup
        self.strict = None
        if config.debug.strict:
            from replication_faster_rcnn_tpu.analysis.strict import StrictHarness

            self.strict = StrictHarness(
                warmup_dispatches=config.debug.strict_warmup
            )
        self._ckpt_mgr = None
        # topology provenance stamped into every checkpoint manifest:
        # restore on a DIFFERENT topology is supported (checkpoints are
        # saved fully replicated; fault.verified_restore re-places), the
        # stamp just makes a cross-topology resume visible in the logs
        self._topology = fault.run_topology(config, self.mesh)
        # elastic fleet membership (parallel/elastic.py): when a
        # supervisor exported the fleet dir and we have peers to watch,
        # run the heartbeat/lease agent. It is STARTED lazily at the
        # first dispatch boundary (_check_fleet) — leases during the
        # multi-minute compile window would read as dead ranks. The
        # agent's watchdog thread writes the durable shrink intent and
        # hard-exits EXIT_FLEET_SHRINK if the main thread is stuck in a
        # dead fleet's collective; the on_lost hook records the incident
        # before that exit.
        fleet_dir, fleet_gen = elastic_fleet.fleet_env()
        self._fleet_generation = fleet_gen
        self.elastic_agent: Optional[elastic_fleet.ElasticAgent] = None
        if fleet_dir and self._process_count > 1:
            el = config.elastic
            self.elastic_agent = elastic_fleet.ElasticAgent(
                fleet_dir,
                fleet_gen,
                self._rank,
                self._process_count,
                heartbeat_interval_s=el.heartbeat_interval_s,
                lease_timeout_s=el.lease_timeout_s,
                on_lost=lambda lost, survivors: self._fault_incident(
                    "fleet_rank_lost",
                    generation=fleet_gen,
                    lost=lost,
                    survivors=survivors,
                ),
            )
        # background scheduled-checkpoint writer (train.async_checkpoint).
        # Single-process: the writer serializes a host numpy snapshot.
        # Multi-process: EVERY rank runs a writer thread and the snapshot
        # stays on device (fresh replicated buffers via gather_replicated,
        # so donation can't delete them mid-write); the writer threads run
        # the collective orbax save in lockstep, preserving orbax's
        # replica/writer election, and only the coordinator writes the
        # manifest.
        self._async_writer: Optional[AsyncCheckpointWriter] = None
        if config.train.async_checkpoint:
            self._async_writer = AsyncCheckpointWriter()
        self.tracer.complete(
            "setup/trainer", setup_t0, self.tracer.now_us() - setup_t0, cat="setup"
        )

    # ---------------------------------------------------------- checkpoints

    @property
    def checkpoint_manager(self):
        if self._ckpt_mgr is None:
            import orbax.checkpoint as ocp

            self._ckpt_mgr = ocp.CheckpointManager(
                os.path.abspath(self.workdir),
                options=ocp.CheckpointManagerOptions(max_to_keep=3, create=True),
            )
        return self._ckpt_mgr

    def _replicated_state(self) -> TrainState:
        """State with every leaf fully replicated on the mesh. Sharded
        optimizer state (ZeRO-1) is all-gathered via a compiled identity
        (`gather_replicated`) — a plain device_put cannot reshard leaves
        whose shards live on other processes' chips (multi-host)."""
        state = self.state
        if self._mp:
            # model-parallel weights live 1/mp per chip; checkpoints stay
            # fully replicated (topology-portable), so gather them back
            state = state.replace(
                params=gather_replicated(state.params, self.mesh)
            )
        if self.config.train.shard_opt_state:
            # gather ONLY the sharded subtrees: BN stats (and params
            # outside mp mode) are already replicated, and a jitted
            # identity (unlike device_put) always materializes fresh
            # output buffers — gathering the whole state would transiently
            # hold a second copy of the model at every checkpoint event
            state = state.replace(
                opt_state=gather_replicated(state.opt_state, self.mesh)
            )
        return state

    def _host_state(self):
        """Full state on host (numpy)."""
        with self.tracer.span("state/host_fetch", cat="sync"):
            return jax.device_get(self._replicated_state())

    def _chaos_sink(self, event) -> None:
        """Record one injected fault as a ``chaos_injected`` incident (the
        event's own ``kind`` — the fault kind — is renamed so it can't
        collide with the incident kind)."""
        fields = dict(event)
        fields["fault_kind"] = fields.pop("kind", None)
        self._fault_incident("chaos_injected", **fields)

    def _fault_incident(self, kind: str, **fields) -> None:
        """Route a fault event to the JSONL metric stream AND the watchdog
        incident log, so `telemetry report` and post-mortems both see it."""
        self.logger.event(kind, **fields)
        if self.watchdog is not None:
            self.watchdog.incident(kind, **fields)

    def _handle_async_error(self, err) -> None:
        """Containment for a failed BACKGROUND scheduled save, surfaced at
        a drain point: same policy as a failed synchronous scheduled save
        (stderr warning + incident, training continues, next interval
        retries). Never raises — only scheduled saves ride the writer."""
        if err is None:
            return
        err_step, exc = err
        print(
            f"warning: async scheduled checkpoint at step {err_step} failed "
            f"({type(exc).__name__}: {exc}); training continues",
            file=sys.stderr,
        )
        self._fault_incident(
            "checkpoint_save_failed",
            step=err_step,
            ckpt_kind="scheduled",
            writer="async",
            error=f"{type(exc).__name__}: {exc}"[:300],
        )

    def _drain_async_saves(self) -> None:
        """Wait out any in-flight background save (handling its error, if
        any). Called before every synchronous save, before restore, and at
        train() exit, so the checkpoint store is never touched from two
        threads and the newest scheduled save is on disk before anything
        that depends on it runs."""
        if self._async_writer is not None:
            self._handle_async_error(self._async_writer.wait())

    def _save_async(self, step: int) -> bool:
        """Scheduled save via the background writer: the trainer thread
        pays only the snapshot — serialize + manifest + prune run on the
        writer thread (train/async_checkpoint.py). Blocks only while the
        PREVIOUS save is still in flight.

        The snapshot is a host device_get in a single-process run (byte-
        identical to the pre-multi-host path). In a multi-process run the
        snapshot instead stays ON DEVICE as fresh replicated buffers
        (`gather_replicated` — a jitted identity always materializes new
        output buffers, so the training loop's donation cannot delete them
        mid-write): orbax's multi-process replica election needs live
        jax.Arrays, and every rank's writer thread runs the collective
        save in lockstep while only the coordinator writes the manifest."""
        import orbax.checkpoint as ocp

        writer = self._async_writer
        multiproc = self._process_count > 1
        # bound in-flight depth at one; a prior failure surfaces here with
        # scheduled-save containment semantics
        self._handle_async_error(writer.wait())
        try:
            # the writer is drained, so a successful save at `step` is
            # visible via latest_step(); a FAILED one is not, and falls
            # through to a retry here
            if self.checkpoint_manager.latest_step() == step:
                return True
            with self.tracer.span(
                "checkpoint/snapshot", cat="checkpoint", step=step
            ):
                if multiproc:
                    # fresh replicated device buffers, donation-safe
                    snapshot = gather_replicated(self.state, self.mesh)
                else:
                    snapshot = jax.device_get(self._replicated_state())
        except Exception as e:
            print(
                f"warning: scheduled checkpoint at step {step} failed "
                f"({type(e).__name__}: {e}); training continues",
                file=sys.stderr,
            )
            self._fault_incident(
                "checkpoint_save_failed",
                step=step,
                ckpt_kind="scheduled",
                writer="async",
                error=f"{type(e).__name__}: {e}"[:300],
            )
            return False

        mgr = self.checkpoint_manager
        workdir, config = self.workdir, self.config
        topology = self._topology
        tracer = self.tracer

        def _write() -> None:
            # failpoint: ioerror raises on the writer thread and surfaces
            # at the next drain point via _handle_async_error; torn_write/
            # crc_corrupt damage the finished step dir below so restore's
            # manifest verification must walk back past it
            inj = failpoints.fire("checkpoint.write", step=step, writer="async")
            mgr.save(step, args=ocp.args.StandardSave(snapshot))
            mgr.wait_until_finished()
            if not is_coordinator():
                return
            # same manifest writer as the sync path: restore-side
            # verification and the fallback walk stay bit-for-bit
            if multiproc:
                with tracer.span("checkpoint/manifest", cat="checkpoint"):
                    host_state = jax.device_get(snapshot)
            else:
                host_state = snapshot
            fault.write_manifest(
                workdir, step, host_state, config,
                kind="scheduled", writer="async", topology=topology,
            )
            # rollout feed: announce the new version to serving-side
            # watchers (serving/rollout/) AFTER the manifest is durable
            fault.publish_manifest_event(
                workdir, step, kind="scheduled", writer="async"
            )
            fault.prune_manifests(workdir, mgr.all_steps())
            if inj is not None and inj.kind in ("torn_write", "crc_corrupt"):
                failpoints.apply_file_fault(
                    inj,
                    failpoints.find_step_dir(
                        workdir, step, exclude=(fault.MANIFEST_DIRNAME,)
                    ),
                )

        self._handle_async_error(writer.submit(step, _write))
        return True

    def save(
        self,
        step: Optional[int] = None,
        kind: str = "scheduled",
        required: Optional[bool] = None,
    ) -> bool:
        """Checkpoint the full state, plus a sidecar manifest (step, config
        hash, per-leaf checksums, save ``kind``) that restore() verifies.

        A ``scheduled`` (periodic) save that fails is contained: watchdog
        incident + warning, training continues and the next interval
        retries — a full disk mid-run should cost a checkpoint, not the
        run. ``emergency``/``final`` saves (or ``required=True``) raise,
        because they are the last chance to persist anything. Returns
        True when a checkpoint for ``step`` is on disk (for async
        scheduled saves: submitted to the background writer).

        With ``train.async_checkpoint`` on, scheduled saves go through
        :meth:`_save_async`; emergency/final/required saves stay
        synchronous here — they are the last write before the process
        exits and must complete, so they first drain the writer."""
        import orbax.checkpoint as ocp

        if required is None:
            required = kind in ("emergency", "final")
        step = int(self.state.step) if step is None else step
        if (
            self._async_writer is not None
            and kind == "scheduled"
            and not required
        ):
            return self._save_async(step)
        # synchronous save: the store must be quiet first
        self._drain_async_saves()
        try:
            if self.checkpoint_manager.latest_step() == step:
                return True  # already checkpointed (orbax raises on dupes)
            # failpoint: ioerror raises here, riding the scheduled-save
            # containment below (or the required-save raise); torn_write/
            # crc_corrupt damage the finished step dir after the write
            inj = failpoints.fire("checkpoint.write", step=step, writer="sync")
            # Hand orbax the REPLICATED jax arrays, not host numpy: with
            # jax.Array inputs orbax's replica logic makes process 0 the
            # only writer in a multi-process run; a device_get'd numpy tree
            # loses that information and every process tries to write the
            # same files (observed as a deadlock inside save() in the
            # 2-process test).
            rep_state = self._replicated_state()
            self.checkpoint_manager.save(
                step, args=ocp.args.StandardSave(rep_state)
            )
            self.checkpoint_manager.wait_until_finished()
            if is_coordinator():
                with self.tracer.span("checkpoint/manifest", cat="checkpoint"):
                    host_state = jax.device_get(rep_state)
                fault.write_manifest(
                    self.workdir, step, host_state, self.config, kind=kind,
                    topology=self._topology,
                )
                # rollout feed: announce the new version to serving-side
                # watchers once the manifest is durable
                fault.publish_manifest_event(
                    self.workdir, step, kind=kind, writer="sync"
                )
                fault.prune_manifests(
                    self.workdir, self.checkpoint_manager.all_steps()
                )
                if inj is not None and inj.kind in (
                    "torn_write", "crc_corrupt",
                ):
                    failpoints.apply_file_fault(
                        inj,
                        failpoints.find_step_dir(
                            self.workdir, step,
                            exclude=(fault.MANIFEST_DIRNAME,),
                        ),
                    )
        except Exception as e:
            if required:
                raise
            print(
                f"warning: {kind} checkpoint at step {step} failed "
                f"({type(e).__name__}: {e}); training continues",
                file=sys.stderr,
            )
            self._fault_incident(
                "checkpoint_save_failed",
                step=step,
                ckpt_kind=kind,
                error=f"{type(e).__name__}: {e}"[:300],
            )
            return False
        return True

    def restore(
        self, step: Optional[int] = None, directory: Optional[str] = None
    ) -> int:
        """Exact resume: params, BN stats, optimizer state AND step —
        manifest-verified, falling back to the newest verifiable step when
        the latest is torn (fault.verified_restore). Discarded steps are
        logged, recorded as an incident, and deleted from this trainer's
        own store so future saves at those steps don't collide.

        ``directory`` restores from a different checkpoint dir WITHOUT
        changing where this trainer saves (warm-start semantics; treated
        read-only — nothing is deleted there)."""
        import orbax.checkpoint as ocp

        self._drain_async_saves()  # never read a store mid-write
        ephemeral = directory is not None
        dirpath = os.path.abspath(directory if ephemeral else self.workdir)
        if ephemeral:
            mgr = ocp.CheckpointManager(dirpath)
        else:
            mgr = self.checkpoint_manager
        try:
            if not mgr.all_steps():
                return 0
            template = self._host_state()
            result = fault.verified_restore(
                mgr, template, dirpath, step=step
            )
            if result.discarded:
                if not ephemeral:
                    for bad_step, _ in result.discarded:
                        try:
                            mgr.delete(bad_step)
                        except Exception:
                            pass  # a torn step may resist deletion too
                self._fault_incident(
                    "checkpoint_fallback",
                    restored_step=result.step,
                    discarded={s: why for s, why in result.discarded},
                )
        finally:
            if ephemeral:
                mgr.close()
        if result.state is None:
            return 0
        from replication_faster_rcnn_tpu.parallel.zero import place_train_state

        self.state = place_train_state(result.state, self._state_shardings)
        self._host_step = int(self.state.step)
        return self._host_step

    def load_pretrained_backbone(self, pth_path: str) -> None:
        """Graft a torch resnet checkpoint into trunk + head tail."""
        from replication_faster_rcnn_tpu.models import convert

        with self.tracer.span("checkpoint/graft", cat="checkpoint"):
            variables = {
                "params": jax.device_get(self.state.params),
                "batch_stats": jax.device_get(self.state.batch_stats),
            }
        grafted = convert.graft_into_variables(variables, pth_path)
        from replication_faster_rcnn_tpu.parallel.mesh import put_host_tree

        # params go back onto their plan layout (mp-sharded under
        # mesh.param_sharding, replicated otherwise); BN stats replicate
        self.state = self.state.replace(
            params=put_host_tree(
                grafted["params"], self._state_shardings.params
            ),
            batch_stats=replicate_tree(grafted["batch_stats"], self.mesh),
        )

    # ---------------------------------------------------------------- train

    def _stage_batch(
        self,
        batch: Dict[str, np.ndarray],
        wait: bool = False,
        step: Optional[int] = None,
    ) -> Dict[str, jax.Array]:
        """One host batch (or --cache-device selection dict) -> sharded
        device arrays: the ``data/device_put`` half of a step. ``wait``
        blocks until the transfer lands — used by the device stager's
        producer thread so the copy itself is off the critical path.
        ``step`` is the host step the batch will train, where the caller
        knows it: the span carries it, as that step's ``step/dispatch``
        does."""
        feed = "device_cache" if self.device_cache is not None else "loader"
        if self.device_cache is None:
            lacks = [k for k in self._batch_keys if k not in batch]
            if lacks:
                raise ValueError(
                    f"the batch lacks {lacks}: this config's step takes "
                    f"{self._batch_keys} (is the data set of another kind of model?)"
                )
        ids = {} if step is None else {"step": step}
        with self.tracer.span("data/device_put", cat="data", feed=feed, **ids):
            return stage_to_devices(
                batch, self.mesh, self.config.mesh, wait=wait
            )

    def _stage_chunk(
        self, batches, wait: bool = False, step: Optional[int] = None
    ) -> Dict[str, jax.Array]:
        """K host batches -> one stacked [K, B, ...] sharded device chunk
        for the fused dispatch (stack_selections in --cache-device mode,
        np.stack otherwise). ``step``: the chunk's first host step."""
        k = len(batches)
        ids = {} if step is None else {"step": step}
        if self.device_cache is not None:
            from replication_faster_rcnn_tpu.data.device_cache import (
                stack_selections,
            )

            stacked = stack_selections(batches)
            feed = "device_cache"
        else:
            stacked = {
                key: np.stack([b[key] for b in batches]) for key in batches[0]
            }
            feed = "loader"
        with self.tracer.span(
            "data/device_put", cat="data", feed=feed, steps=k, **ids
        ):
            return stage_to_devices(
                stacked, self.mesh, self.config.mesh, stacked=True, wait=wait
            )

    def train_one_batch(
        self,
        batch: Optional[Dict[str, np.ndarray]] = None,
        staged: Optional[Dict[str, jax.Array]] = None,
        bucket: Optional[int] = None,
    ) -> Dict[str, float]:
        """One optimizer step. Callers pass either a host ``batch`` (staged
        here, the synchronous pre-PR-4 path) or an already device-resident
        ``staged`` batch from the DevicePrefetcher. ``bucket`` selects one
        multi-scale resolution bucket's compiled program (the feed's
        ``bucket_of`` assignment); None dispatches the single-scale
        program."""
        tracer = self.tracer
        step = self._host_step + 1  # the identifier this step's spans share
        if staged is None:
            # in --cache-device mode `batch` is a selection dict (idx/flip/
            # jitter — bytes, not megabytes); the images never leave device
            staged = self._stage_batch(batch, step=step)
        step_fn = self.jitted_step
        program = "train_step"
        if bucket is not None and self.jitted_bucket_steps is not None:
            bh, bw = self._bucket_resolutions[bucket]
            step_fn = self.jitted_bucket_steps[bucket]
            program = f"train_step_{bh}x{bw}"
        strict = self._strict_dispatch(program, step_fn)
        if self.device_cache is not None:
            with tracer.span("step/dispatch", cat="step", step=step), strict:
                self.state, metrics = step_fn(
                    self.state, self.device_cache.arrays, staged
                )
        else:
            with tracer.span("step/dispatch", cat="step", step=step), strict:
                self.state, metrics = step_fn(self.state, staged)
        self._host_step += 1
        # hand the monitor this step's `skipped` flag as a DEVICE scalar —
        # it syncs only at drain points, preserving dispatch overlap
        self.skip_monitor.observe(self._host_step, metrics)
        if self._counters and self._host_step % COUNTER_EVERY == 0:
            self._counters_pending.append(
                (self._host_step, {k: metrics[k] for k in self._counters if k in metrics})
            )
            if len(self._counters_pending) > 1:
                # the step before the newest finished COUNTER_EVERY steps ago
                self._write_counters(*self._counters_pending.pop(0))
        return metrics

    def _write_counters(self, step: int, values) -> None:
        """One counter event a name (`lm/<name>`), from a step's metrics the
        host may read without waiting."""
        with self.tracer.span("step/counters", cat="sync", step=step):
            host = jax.device_get(values)
        for name, value in host.items():
            self.tracer.counter(f"lm/{name}", float(value))

    def train_chunk(
        self,
        batches=None,
        staged: Optional[Dict[str, jax.Array]] = None,
        bucket: Optional[int] = None,
    ) -> Dict[str, np.ndarray]:
        """Train ``steps_per_dispatch`` steps in ONE fused jitted dispatch.

        ``batches`` must hold exactly ``steps_per_dispatch`` host batches
        (selection dicts in --cache-device mode) — the fused program was
        compiled for that K. Alternatively ``staged`` is a pre-staged
        stacked device chunk from the DevicePrefetcher (already sharded,
        transfer landed). Returns stacked [K, ...] metrics, still on
        device: callers sync them only at log boundaries so the whole
        chunk's dispatch overlaps device compute.
        """
        k = self.steps_per_dispatch
        first = self._host_step + 1  # the chunk's spans carry its first step
        if staged is None:
            if len(batches) != k:
                raise ValueError(
                    f"train_chunk got {len(batches)} batches; the fused step "
                    f"was compiled for steps_per_dispatch={k}"
                )
            staged = self._stage_chunk(batches, step=first)
        tracer = self.tracer
        step_fn = self.jitted_multi_step
        program = f"multi_step_k{k}"
        if bucket is not None and self.jitted_bucket_multi_steps is not None:
            bh, bw = self._bucket_resolutions[bucket]
            step_fn = self.jitted_bucket_multi_steps[bucket]
            program = f"multi_step_k{k}_{bh}x{bw}"
        strict = self._strict_dispatch(program, step_fn)
        if self.device_cache is not None:
            with tracer.span(
                "step/dispatch", cat="step", steps=k, step=first
            ), strict:
                self.state, metrics = step_fn(
                    self.state, self.device_cache.arrays, staged
                )
        else:
            with tracer.span(
                "step/dispatch", cat="step", steps=k, step=first
            ), strict:
                self.state, metrics = step_fn(
                    self.state, staged
                )
        self._host_step += k
        self.skip_monitor.observe(first, metrics)  # stacked [K] device flags
        return metrics

    def _strict_dispatch(self, program: str, fn):
        """Strict-mode gate for one dispatch of ``program`` (no-op context
        when strict mode is off)."""
        if self.strict is None:
            return contextlib.nullcontext()
        return self.strict.dispatch(program, fn)

    def strict_session(self):
        """Transfer-guard session for the whole loop (no-op when off).
        Callers driving :meth:`train_one_batch` directly (the CLI bounded
        --steps loop) wrap their loop in this."""
        if self.strict is None:
            return contextlib.nullcontext()
        return self.strict.session()

    def flush_telemetry(self) -> None:
        """Write the trace file and stop the watchdog. For callers driving
        :meth:`train_one_batch` directly without :meth:`telemetry_session`."""
        if self.watchdog is not None:
            self.watchdog.stop()
        self._flush_counters()
        self.tracer.flush()

    def _flush_counters(self) -> None:
        while self._counters_pending:
            self._write_counters(*self._counters_pending.pop(0))

    @contextlib.contextmanager
    def telemetry_session(self):
        """Watchdog running inside, tracer flushed + watchdog stopped on ANY
        exit — including KeyboardInterrupt and crashes, which additionally
        record an ``abnormal_exit`` incident so the post-mortem doesn't
        start from a silently-truncated trace."""
        if self.watchdog is not None:
            if self.loader is not None:
                self.watchdog.providers.setdefault(
                    "loader_queue_depth", self.loader.queue_depth
                )
            self.watchdog.start()
        try:
            yield self
        except BaseException as e:
            if self.watchdog is not None:
                self.watchdog.incident(
                    "abnormal_exit", error=f"{type(e).__name__}: {e}"[:500]
                )
            raise
        finally:
            if self.watchdog is not None:
                self.watchdog.stop()
            self._flush_counters()
            self.tracer.flush()

    def _check_preemption(self, step: int) -> None:
        """Dispatch-boundary shutdown check: on a pending SIGTERM/SIGINT,
        save a verified emergency checkpoint, record the incident, and
        leave via :class:`fault.Preempted` (CLI exit code EXIT_PREEMPTED)."""
        sd = self._shutdown
        if sd is None or not sd.requested:
            return
        reason = sd.reason or "signal"
        self._fault_incident("preempted", step=step, reason=reason)
        with self.tracer.span(
            "checkpoint/save", cat="checkpoint", kind="emergency"
        ):
            self.save(kind="emergency")
        raise fault.Preempted(step, reason)

    def _check_fleet(self, step: int) -> None:
        """Dispatch-boundary elastic check: start the heartbeat/watchdog
        agent lazily on the first call (a dispatch retired, so compile is
        over and lease cadence is trustworthy), then surface any
        watchdog-detected rank loss as :class:`fault.FleetShrink`.
        Deliberately NO emergency checkpoint here — saves are
        cross-process collectives and would hang on the dead peer;
        survivors fall back to the last CRC-verified step
        (``train.checkpoint_every_steps`` bounds the rollback). The
        incident and the durable shrink intent were already recorded by
        the agent when it detected the loss."""
        agent = self.elastic_agent
        if agent is None:
            return
        agent.start()
        lost = agent.check()
        if lost:
            raise fault.FleetShrink(step, lost, agent.survivors(lost))

    def _maybe_step_checkpoint(self, step: int) -> None:
        """Scheduled mid-epoch save every ``train.checkpoint_every_steps``
        optimizer steps (0 = epoch-boundary saves only). Boundary-crossing
        logic (not ``step % every``) so fused K-step dispatches cannot
        jump over a save point. Deterministic across ranks — every rank
        sees the same step sequence, so the collective save stays in
        lockstep."""
        every = self.config.train.checkpoint_every_steps
        if not every or step - self._last_step_ckpt < every:
            return
        self._last_step_ckpt = step
        if self.watchdog is not None:
            self.watchdog.beat(phase="checkpoint")
        with self.tracer.span(
            "checkpoint/save", cat="checkpoint", boundary="step"
        ):
            self.save()

    def evaluate(self, max_images: Optional[int] = None) -> Dict[str, float]:
        """mAP on the val split with the CURRENT training parameters
        (reference: impossible — its eval was never written, SURVEY §2.1 #15).

        The val dataset and the Evaluator (whose inference fn is jitted)
        are built once and cached, so per-epoch eval pays no recompile."""
        self.config.require_detector("evaluate")
        if getattr(self, "_evaluator", None) is None:
            from replication_faster_rcnn_tpu.eval import Evaluator

            self._val_dataset = make_dataset(self.config.data, "val")
            self._evaluator = Evaluator(self.config, self.model)
            # under strict mode the epoch-end eval runs inside the train
            # session's transfer guard: the evaluator needs the harness so
            # its first infer dispatch gets a warmup allowance
            self._evaluator.strict = self.strict
        variables = {
            "params": self.state.params,
            "batch_stats": self.state.batch_stats,
        }
        with self.tracer.span("eval/evaluate", cat="eval"):
            return self._evaluator.evaluate(
                variables, self._val_dataset,
                batch_size=self.config.train.batch_size,
                max_images=max_images,
            )

    def _log_step(
        self, step: int, metrics, log_every: int
    ) -> Optional[Dict[str, float]]:
        """Per-step log cadence: when ``step`` is a log boundary, sync the
        metrics (fail fast on NaN/inf unless the guarded update already
        withheld the step — fault.check_step_metrics), log, and drain the
        skip monitor. The sync span is where async dispatch drains, i.e.
        device compute time for the interval. Returns the logged row, or
        None off-boundary."""
        if step % log_every != 0:
            return None
        with self.tracer.span("step/sync", cat="sync"):
            host_metrics = jax.device_get(metrics)
        row = fault.check_step_metrics(host_metrics, step)
        row["lr"] = self.host_schedule(step)
        self.logger.log(step, row)
        self.skip_monitor.drain()
        return row

    def _log_chunk(
        self, first: int, step: int, metrics, log_every: int
    ) -> Optional[Dict[str, float]]:
        """Chunk-aware log cadence: sync the stacked [K] metrics only when
        a log boundary falls inside [``first``, ``step``], and log that
        boundary's own row. Returns the logged row, or None."""
        boundary = (step // log_every) * log_every
        if boundary < first:
            return None
        with self.tracer.span("step/sync", cat="sync"):
            host_metrics = jax.device_get(metrics)
        row = {key: v[boundary - first] for key, v in host_metrics.items()}
        row = fault.check_step_metrics(row, boundary)
        row["lr"] = self.host_schedule(boundary)
        self.logger.log(boundary, row)
        self.skip_monitor.drain()
        return row

    def train(self, log_every: int = 10, resume: bool = False) -> Dict[str, float]:
        """Run cfg.train.n_epoch epochs. The epoch count lives in the config
        (not a parameter) because the cosine schedule was built from it —
        an ad-hoc override would train on a mismatched LR curve.
        """
        cfg = self.config.train
        start_step = self.restore() if resume else 0
        steps_per_epoch = max(
            len(self.sampler if self.device_cache is not None else self.loader), 1
        )
        start_epoch = start_step // steps_per_epoch
        # mid-epoch resume (emergency/step-interval checkpoints land at
        # arbitrary steps): consume the resumed epoch from its global-order
        # OFFSET — set_epoch(epoch, start_batch=replay) re-derives the
        # epoch's deterministic batch order and starts the iterator at the
        # first untrained batch, so the already-consumed prefix never
        # reaches the loader and the loss trajectory still matches an
        # uninterrupted run step-for-step. Under an elastic re-formation
        # the same offset re-partitions the epoch's unconsumed suffix
        # disjointly across the NEW world size (each rank takes its
        # contiguous block of every remaining global batch).
        replay = start_step - start_epoch * steps_per_epoch
        step = start_step  # host-side mirror: no device sync to read it
        self._host_step = start_step
        self._last_step_ckpt = start_step
        if self._fleet_generation > 0:
            # step-free fields: same-seed replays of a shrink produce the
            # identical incident regardless of wall clock or rollback depth
            self._fault_incident(
                "fleet_reformed",
                generation=self._fleet_generation,
                world_size=self._process_count,
                survivors=list(range(self._process_count)),
            )

        last: Dict[str, float] = {}
        eval_result: Dict[str, float] = {}
        feed = self.sampler if self.device_cache is not None else self.loader
        tracer = self.tracer

        def cur_bucket() -> Optional[int]:
            # resolution bucket of the NEXT batch to train: a pure
            # function of (seed, epoch, position-in-epoch) via the feed's
            # bucket_of, so resume/replay and every rank agree. `step` and
            # `epoch` are read at call time (closure over the loop vars);
            # all K batches of one fused dispatch share a bucket by
            # construction (bucket_chunk = steps_per_dispatch).
            if self.jitted_bucket_steps is None:
                return None
            return feed.bucket_of(step - epoch * steps_per_epoch)

        self._shutdown = fault.GracefulShutdown()
        try:
            with self.telemetry_session(), self.strict_session(), self._shutdown:
                k = self.steps_per_dispatch
                prefetch = self.config.data.prefetch_device
                for epoch in range(start_epoch, cfg.n_epoch):
                    feed.set_epoch(epoch, start_batch=replay)
                    replay = 0
                    t_epoch = time.time()
                    n_images = 0
                    if prefetch > 0:
                        # overlap path (data.prefetch_device): a producer
                        # thread collates + stages batch K+1's device
                        # transfer while dispatch K runs, so the consumer
                        # loop below only dequeues resident buffers. A
                        # resumed epoch's trained prefix never reaches the
                        # producer — the feed itself starts at the resume
                        # offset (set_epoch start_batch above).
                        # the producer stages in dispatch order, so the
                        # n-th item it stages trains host steps
                        # step + n*k + 1 .. (its spans' `step` identifier)
                        stage_steps = itertools.count(step + 1, k)
                        stage = (
                            (lambda bs: self._stage_chunk(
                                bs, wait=True, step=next(stage_steps)))
                            if k > 1
                            else (lambda bs: self._stage_batch(
                                bs[0], wait=True, step=next(stage_steps)))
                        )
                        stager = DevicePrefetcher(
                            iter(feed), stage,
                            depth=prefetch, chunk=k,
                        )
                        if self.watchdog is not None:
                            self.watchdog.providers["staged_queue_depth"] = (
                                stager.queue_depth
                            )
                        try:
                            for item in stager:
                                if item[0] == STAGED and k > 1:
                                    metrics = self.train_chunk(
                                        staged=item[1], bucket=cur_bucket()
                                    )
                                    first = step + 1
                                    step += k
                                    n_images += item[3]
                                    if self.watchdog is not None:
                                        self.watchdog.beat(
                                            step=step, phase="train"
                                        )
                                    row = self._log_chunk(
                                        first, step, metrics, log_every
                                    )
                                    if row is not None:
                                        last = row
                                elif item[0] == STAGED:
                                    metrics = self.train_one_batch(
                                        staged=item[1], bucket=cur_bucket()
                                    )
                                    step += 1
                                    n_images += item[3]
                                    if self.watchdog is not None:
                                        self.watchdog.beat(
                                            step=step, phase="train"
                                        )
                                    row = self._log_step(
                                        step, metrics, log_every
                                    )
                                    if row is not None:
                                        last = row
                                else:
                                    # HOST item: epoch tail (< K pending
                                    # batches) through the per-step path
                                    batch = item[1]
                                    metrics = self.train_one_batch(
                                        batch, bucket=cur_bucket()
                                    )
                                    step += 1
                                    n_images += _rows(batch)
                                    if self.watchdog is not None:
                                        self.watchdog.beat(
                                            step=step, phase="train"
                                        )
                                    row = self._log_step(
                                        step, metrics, log_every
                                    )
                                    if row is not None:
                                        last = row
                                self._check_preemption(step)
                                self._check_fleet(step)
                                self._maybe_step_checkpoint(step)
                        finally:
                            # drops staged-but-untrained buffers; resume
                            # replay regenerates them deterministically
                            stager.close()
                    else:
                        it = iter(feed)
                        chunk = []  # pending batches of a partial dispatch
                        while True:
                            # the fetch span covers host-side batch
                            # production (decode/collate or selection draw)
                            # — the feed half of feed-vs-compute
                            with tracer.span("data/fetch", cat="data"):
                                try:
                                    batch = next(it)
                                except StopIteration:
                                    break
                            if k > 1:
                                chunk.append(batch)
                                if len(chunk) < k:
                                    continue
                                metrics = self.train_chunk(
                                    chunk, bucket=cur_bucket()
                                )
                                first = step + 1
                                step += k
                                n_images += sum(_rows(b) for b in chunk)
                                chunk = []
                                if self.watchdog is not None:
                                    self.watchdog.beat(step=step, phase="train")
                                row = self._log_chunk(
                                    first, step, metrics, log_every
                                )
                                if row is not None:
                                    last = row
                                self._check_preemption(step)
                                self._check_fleet(step)
                                self._maybe_step_checkpoint(step)
                                continue
                            metrics = self.train_one_batch(
                                batch, bucket=cur_bucket()
                            )
                            n_images += _rows(batch)
                            step += 1
                            if self.watchdog is not None:
                                self.watchdog.beat(step=step, phase="train")
                            row = self._log_step(step, metrics, log_every)
                            if row is not None:
                                last = row
                            self._check_preemption(step)
                            self._check_fleet(step)
                            self._maybe_step_checkpoint(step)
                        # epoch tail: a feed length not divisible by K
                        # leaves <K batches pending — run them through the
                        # per-step path (its jit compiles lazily, only when
                        # a tail exists)
                        for batch in chunk:
                            metrics = self.train_one_batch(
                                batch, bucket=cur_bucket()
                            )
                            n_images += _rows(batch)
                            step += 1
                            if self.watchdog is not None:
                                self.watchdog.beat(step=step, phase="train")
                            row = self._log_step(step, metrics, log_every)
                            if row is not None:
                                last = row
                            self._check_preemption(step)
                            self._check_fleet(step)
                            self._maybe_step_checkpoint(step)
                    # epoch-boundary sync for an honest throughput number
                    with tracer.span("step/sync", cat="sync", boundary="epoch"):
                        jax.device_get(
                            jax.tree_util.tree_leaves(self.state.params)[0]
                        )
                    self.skip_monitor.drain()
                    dt = time.time() - t_epoch
                    # n_images counted LOCAL rows; report global throughput
                    n_images *= self._process_count
                    self.logger.log_epoch(epoch, n_images / dt if dt > 0 else 0.0)
                    if cfg.eval_every_epochs and (
                        epoch + 1
                    ) % cfg.eval_every_epochs == 0:
                        if self.watchdog is not None:
                            self.watchdog.beat(phase="eval")
                        from replication_faster_rcnn_tpu.eval.evaluator import (
                            summary_scalars,
                        )

                        # flat scalar schema shared by the voc and coco
                        # metrics: aggregates + per-class AP/<name> rows
                        eval_result = summary_scalars(
                            self.evaluate(), self.config.model.num_classes
                        )
                        self.logger.log(step, eval_result)
                    if (epoch + 1) % cfg.checkpoint_every_epochs == 0:
                        if self.watchdog is not None:
                            self.watchdog.beat(phase="checkpoint")
                        with tracer.span("checkpoint/save", cat="checkpoint"):
                            # periodic saves are contained (kind="scheduled"):
                            # a failed one logs an incident and the next
                            # interval retries
                            self.save()
                    self._check_preemption(step)
                    self._check_fleet(step)
        finally:
            self._shutdown = None
            # stop the heartbeat thread on a HEALTHY exit only: after a
            # detected rank loss it stays armed, so its EXIT_FLEET_SHRINK
            # backstop still fires if teardown wedges on the dead peer
            if self.elastic_agent is not None and not self.elastic_agent.check():
                self.elastic_agent.stop()
            # the last scheduled save must be on disk before train()
            # returns (callers immediately save(kind="final") or exit)
            self._drain_async_saves()
        if last:
            last = {k: float(v) for k, v in last.items()}
        # merged last so step-metric logging cannot wipe the eval result
        last.update(eval_result)
        return last
