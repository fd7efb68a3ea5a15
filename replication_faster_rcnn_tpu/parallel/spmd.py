"""Explicit-collective SPMD train step — the hand-written counterpart of
the jit auto-partitioned step in `train/train_step.py`.

The reference has no distributed training at all (SURVEY.md §2.4); the
framework's default path gets data parallelism "for free" from jit
auto-partitioning (annotate shardings, XLA inserts the collectives). This
module is the same training step with every collective PLACED BY HAND via
``jax.shard_map`` — the moral equivalent of writing the DDP/NCCL-allreduce
loop yourself, in XLA collectives:

  * each shard runs forward/backward on its local batch slice;
  * loss normalizers (`#positives`, `#valid labels`) are `lax.psum`'d
    across the ``data`` axis before dividing (train/losses.py
    ``axis_name``), so the objective is the batch-global one;
  * BatchNorm runs in cross-replica (sync) mode — flax's ``axis_name``
    pmean — matching what auto-partitioning computes on a global batch;
  * per-image sampling keys fold in the GLOBAL batch position
    (``lax.axis_index`` offset), so target subsampling draws the same
    randomness as the auto-partitioned step;
  * gradients are `lax.psum`'d, then every shard applies the identical
    optimizer update to its replicated state — or, under
    ``train.shard_opt_state`` (ZeRO-1, arXiv:2004.13336), each shard
    `lax.psum_scatter`s the gradients straight into its 1/N slice, updates
    only that slice of the parameters against its local slice of the Adam
    moments, and `lax.all_gather`s the updated slices back to full
    parameters. Same bytes on the wire as the allreduce it replaces, 1/N
    of the update FLOPs and moment memory per shard; the per-leaf slice
    layout is `parallel/zero.py`'s ``shard_dim`` rule, shared with the jit
    auto-partitioning backend so checkpoints move freely between the two.

Because of the four properties above, this step computes the same update
as the jit auto-partitioned step up to floating-point reduction order —
asserted by `tests/test_parallel.py`. One documented exception: dropout
(VGG16's fc6/fc7). The jit path draws one mask over the global crop batch;
here each shard draws its own mask (rng_do folds in ``lax.axis_index`` so
shards are decorrelated — statistically equivalent, not bitwise). It
exists (a) as an independent check on the auto path, (b) as the place
where collective placement is explicit and profilable, and (c) as the
template for adding shardings XLA cannot infer (e.g. tensor-parallel heads
over the mesh's ``model`` axis).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from replication_faster_rcnn_tpu.config import FasterRCNNConfig
from replication_faster_rcnn_tpu.models.faster_rcnn import FasterRCNN
from replication_faster_rcnn_tpu.parallel import zero
from replication_faster_rcnn_tpu.parallel.plan import Plan, compile_step_with_plan
from replication_faster_rcnn_tpu.telemetry import stages
from replication_faster_rcnn_tpu.train import fault
from replication_faster_rcnn_tpu.train.train_step import TrainState, compute_losses

Array = jnp.ndarray


def make_shard_map_train_step(
    config: FasterRCNNConfig,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    steps_per_dispatch: int = 1,
    state_template: TrainState = None,
    train_resolution=None,
):
    """Build the explicitly-collectivized (state, batch) -> (state, metrics)
    step. State must be replicated on ``mesh``; batch arrays sharded on
    their leading dim over the data axis (`parallel.shard_batch`).

    Under ``config.train.shard_opt_state`` (ZeRO-1) the state is instead
    placed with `parallel.zero.train_state_shardings(shard_opt=True)` —
    optimizer-state leaves arrive as this shard's 1/N slice — and
    ``state_template`` (the TrainState, concrete or abstract: only leaf
    shapes are read, at trace time) is required to derive the per-leaf
    slice layout. The step then reduce-scatters gradients, updates slices,
    and all-gathers the updated parameters; in/out state shardings match
    the jit backend's, so the two ZeRO implementations are checkpoint- and
    placement-compatible.

    ``steps_per_dispatch`` > 1 fuses K steps into the one shard_map call:
    the per-shard body `lax.scan`s over batches stacked on a NEW leading
    [K] axis (shard with `parallel.shard_stacked_batch` — the batch dim is
    then axis 1), psum'ing grads/metrics every fused step; metrics return
    stacked [K, ...]. The carry state never leaves the program between the
    fused steps — one dispatch, K updates.

    ``train_resolution`` (STATIC ``(h, w)`` or None) builds the step for
    ONE multi-scale training bucket: the resample to the bucket's shape
    is traced into the per-shard body (`compute_losses`), so each bucket
    is its own shard_map program. The in/out specs are untouched — they
    shard only batch dims (``P(axis)`` / ``P(None, axis)``), which is
    resolution-independent; only the traced body and the Plan label
    (``train_step_{h}x{w}``) differ between buckets.

    ``config.train.grad_allreduce_dtype`` = "bfloat16" casts the gradient
    tree to bf16 BEFORE the psum — THE all-reduce then moves half the
    bytes — and de-casts for the fp32 optimizer math (arXiv:1711.04325's
    half-precision gradient exchange).

    Returns (step_fn, model): the model is constructed with sync-BN bound
    to the data axis; its parameter tree is identical to the default
    model's, so states are interchangeable between the two backends.
    """
    axis = config.mesh.data_axis
    allreduce_dt = jnp.dtype(config.train.grad_allreduce_dtype)
    # sync-BN binds batch statistics to the data axis; GroupNorm is
    # per-sample and needs no axis (the config layer rejects the combo)
    cfg = config.replace(
        model=dataclasses.replace(
            config.model,
            bn_axis=axis if config.model.norm == "batch" else None,
        )
    )
    model = FasterRCNN(cfg)

    def per_shard(
        state: TrainState, batch: Dict[str, Array]
    ) -> Tuple[TrainState, Dict[str, Array]]:
        step_rng = jax.random.fold_in(state.rng, state.step)
        n_local = batch["image"].shape[0]
        positions = jax.lax.axis_index(axis) * n_local + jnp.arange(
            n_local, dtype=jnp.int32
        )

        def loss_fn(params):
            return compute_losses(
                model, cfg, params, state.batch_stats, batch, step_rng,
                True, axis_name=axis, positions=positions,
                train_resolution=train_resolution,
            )

        (_, (metrics, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)

        # THE allreduce: local grads of (local numerator / global normalizer)
        # sum to the global gradient. grad_allreduce_dtype=bfloat16 halves
        # the bytes this collective moves; the de-cast right after keeps
        # the optimizer math in the params' fp32.
        with jax.named_scope(stages.UPDATE):
            if allreduce_dt != jnp.float32:
                dtypes = jax.tree_util.tree_map(lambda g: g.dtype, grads)
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(allreduce_dt)
                    if jnp.issubdtype(g.dtype, jnp.floating)
                    else g,
                    grads,
                )
                grads = jax.lax.psum(grads, axis)
                grads = jax.tree_util.tree_map(
                    lambda g, dt: g.astype(dt), grads, dtypes
                )
            else:
                grads = jax.lax.psum(grads, axis)
        # loss/count metrics are local-contribution / global-normalizer (or
        # plain local counts), so psum yields the batch-global values.
        metrics = jax.lax.psum(metrics, axis)

        # guarded update AFTER the psum: the nonfinite gate reads the
        # GLOBAL gradient, so every shard takes the same branch and the
        # replicated state stays replicated; health scalars likewise match
        # the auto-partitioned backend's (new_stats are already sync-BN
        # pmean'd, and carry through unchanged on a skipped step)
        new_state, health = fault.guarded_update(
            tx, state, grads, new_stats, config.train.nonfinite_policy
        )
        metrics.update(health)
        return new_state, metrics

    n_shards = mesh.shape[axis]
    shard_opt = bool(config.train.shard_opt_state) and n_shards > 1
    if shard_opt and state_template is None:
        raise ValueError(
            "shard_opt_state on the shard_map backend needs a "
            "state_template (the TrainState, concrete or abstract) to "
            "derive the per-leaf ZeRO-1 slice layout"
        )
    if shard_opt:
        # ZeRO-1 by hand. Per-leaf slice dims come from the FULL shapes of
        # the template (inside the body every sharded leaf is local, so
        # the layout must be closed over, never recomputed from local
        # shapes). -1 marks a leaf the layout rule keeps replicated.
        param_dims = jax.tree_util.tree_map(
            lambda leaf: zero.shard_dim(np.shape(leaf), n_shards),
            state_template.params,
        )
        state_specs = jax.tree_util.tree_map(lambda _: P(), state_template)
        state_specs = state_specs.replace(
            opt_state=jax.tree_util.tree_map(
                lambda leaf: zero.shard_spec(np.shape(leaf), n_shards, axis),
                state_template.opt_state,
            )
        )

        def _reduce_grad(g, d):
            # the restructured allreduce: shardable leaves reduce-scatter
            # straight into this shard's slice (same wire bytes, 1/N the
            # output); unshardable ones keep the plain psum
            if d >= 0:
                return jax.lax.psum_scatter(
                    g, axis, scatter_dimension=d, tiled=True
                )
            return jax.lax.psum(g, axis)

        def _slice(leaf, d):
            if d < 0:
                return leaf
            size = leaf.shape[d] // n_shards
            start = jax.lax.axis_index(axis) * size
            return jax.lax.dynamic_slice_in_dim(leaf, start, size, d)

        def _gather(leaf, d):
            if d < 0:
                return leaf
            return jax.lax.all_gather(leaf, axis, axis=d, tiled=True)

        def _sharded_sumsq(tree, dims, local_fn):
            # sum(local_fn over sliced leaves) psums to the global value;
            # replicated leaves contribute theirs directly on every shard
            xs = jax.tree_util.tree_leaves(tree)
            ds = jax.tree_util.tree_leaves(dims)
            zero_ = jnp.zeros((), jnp.float32)
            local = sum(
                (local_fn(x) for x, d in zip(xs, ds) if d >= 0), zero_
            )
            repl = sum(
                (local_fn(x) for x, d in zip(xs, ds) if d < 0), zero_
            )
            return jax.lax.psum(local, axis) + repl

        def _sumsq(x):
            return jnp.sum(jnp.square(x.astype(jnp.float32)))

        def _nonfin(x):
            if not jnp.issubdtype(x.dtype, jnp.inexact):
                return jnp.zeros((), jnp.float32)
            return jnp.sum(~jnp.isfinite(x)).astype(jnp.float32)

        def per_shard_zero(
            state: TrainState, batch: Dict[str, Array]
        ) -> Tuple[TrainState, Dict[str, Array]]:
            # identical forward/backward to per_shard; params arrive full
            # (replicated), opt-state leaves arrive as this shard's slice
            step_rng = jax.random.fold_in(state.rng, state.step)
            n_local = batch["image"].shape[0]
            positions = jax.lax.axis_index(axis) * n_local + jnp.arange(
                n_local, dtype=jnp.int32
            )

            def loss_fn(params):
                return compute_losses(
                    model, cfg, params, state.batch_stats, batch, step_rng,
                    True, axis_name=axis, positions=positions,
                    train_resolution=train_resolution,
                )

            (_, (metrics, new_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
            metrics = jax.lax.psum(metrics, axis)

            with jax.named_scope(stages.UPDATE):
                if allreduce_dt != jnp.float32:
                    dtypes = jax.tree_util.tree_map(lambda g: g.dtype, grads)
                    grads = jax.tree_util.tree_map(
                        lambda g: g.astype(allreduce_dt)
                        if jnp.issubdtype(g.dtype, jnp.floating)
                        else g,
                        grads,
                    )
                    grads = jax.tree_util.tree_map(_reduce_grad, grads, param_dims)
                    grads = jax.tree_util.tree_map(
                        lambda g, dt: g.astype(dt), grads, dtypes
                    )
                else:
                    grads = jax.tree_util.tree_map(_reduce_grad, grads, param_dims)

                # this shard's parameter slices; the optimizer chain is
                # elementwise (add_decayed_weights / scale_by_adam / lr), so
                # updating slices against the local moment slices computes
                # exactly the slice of the full update
                param_sl = jax.tree_util.tree_map(_slice, state.params, param_dims)
                updates, new_opt = tx.update(grads, state.opt_state, param_sl)
                new_param_sl = optax.apply_updates(param_sl, updates)

                # health on sharded trees: psum'd sums-of-squares reproduce the
                # replicated backend's global norms (same numbers, modulo
                # reduction order) and the nonfinite gate stays GLOBAL — every
                # shard takes the same branch below
                grad_norm = jnp.sqrt(_sharded_sumsq(grads, param_dims, _sumsq))
                update_norm = jnp.sqrt(_sharded_sumsq(updates, param_dims, _sumsq))
                param_norm = optax.global_norm(state.params)
                nonfinite = _sharded_sumsq(grads, param_dims, _nonfin)
                health = {
                    "grad_norm": grad_norm,
                    "param_norm": param_norm,
                    "update_norm": update_norm,
                    "update_ratio": update_norm / (param_norm + 1e-12),
                    "nonfinite_count": nonfinite,
                }
                if config.train.nonfinite_policy == "apply":
                    health["skipped"] = jnp.zeros((), jnp.float32)
                    sel_p, sel_opt, sel_stats = new_param_sl, new_opt, new_stats
                else:
                    ok = nonfinite == 0

                    def keep(new, old):
                        # select BEFORE the gather: on a skipped step every
                        # shard contributes its OLD slice, so the gathered
                        # params are bit-identical to the pre-step tree
                        return jnp.where(ok, new, old)

                    sel_p = jax.tree_util.tree_map(keep, new_param_sl, param_sl)
                    sel_opt = jax.tree_util.tree_map(keep, new_opt, state.opt_state)
                    sel_stats = jax.tree_util.tree_map(
                        keep, new_stats, state.batch_stats
                    )
                    health["skipped"] = 1.0 - ok.astype(jnp.float32)
                new_params = jax.tree_util.tree_map(_gather, sel_p, param_dims)
            metrics.update(health)
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                batch_stats=sel_stats,
                opt_state=sel_opt,
            )
            return new_state, metrics

        step_body, state_spec = per_shard_zero, state_specs
    else:
        step_body, state_spec = per_shard, P()

    if steps_per_dispatch > 1:
        # fused K-step body: scan INSIDE the shard_map so the psums run
        # once per fused step while the carry state stays in-program. The
        # stacked [K, B, ...] batch shards its axis-1 batch dim over the
        # data axis (P(None, axis)); each scan slice is one local batch.
        def per_shard_multi(state, batches):
            from replication_faster_rcnn_tpu.train.train_step import (
                fused_scan_unroll,
            )

            # the carry keeps the step body's state layout (sliced opt
            # leaves under ZeRO), so K-step fusion composes unchanged
            return jax.lax.scan(
                step_body, state, batches, length=steps_per_dispatch,
                unroll=fused_scan_unroll(steps_per_dispatch),
            )

        body, batch_spec = per_shard_multi, P(None, axis)
    else:
        body, batch_spec = step_body, P(axis)

    label = (
        "train_step"
        if steps_per_dispatch <= 1
        else f"multi_step_k{steps_per_dispatch}"
    )
    if train_resolution is not None:
        # per-bucket program: same label convention as the trainer's
        # cached/loader bucket steps, so strict dispatch accounting and
        # the warmup registry agree on names across backends
        label = f"{label}_{int(train_resolution[0])}x{int(train_resolution[1])}"
    plan = Plan(
        mesh=mesh,
        in_specs=(state_spec, batch_spec),
        out_specs=(state_spec, P()),
        donate_argnums=(0,),
        param_specs=state_spec,
        label=label,
    )
    return compile_step_with_plan(body, plan), model
