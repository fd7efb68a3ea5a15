"""Backward-pass cost attribution WITHOUT the profiler.

An op-level account of the dominant backward slice (ROADMAP S3) wants a
decoded ``jax.profiler`` trace; until one exists this script derives an
attribution from wall-times of jitted grad VARIANTS instead:

  trunk_train  forward trunk only, train-mode BN (batch-stats
               reductions computed) — paired with trunk_eval this is
               the BN-density A/B from layer_cost_table /
               STAGE_BREAKDOWN: eval-mode BN is a fusable affine, so
               the delta is the price of train-mode BN on the trunk
  trunk_eval   forward trunk only, eval-mode BN
  fwd        forward + 4 losses (no grad)
  grad_wall  value_and_grad with ``features_wall=True`` — gradients stop
             at the trunk/neck features, so the program runs the full
             forward but only the RPN/targets/head backward
  grad_imgs  grad w.r.t. the INPUT IMAGES with params closed over — the
             full dgrad (activation-gradient) chain through head and
             trunk, but no wgrads (no parameter gradients anywhere)
  grad_full  the real thing: value_and_grad w.r.t. all params, gradient
             norm consumed (identical structure to the train step's)

Attribution (differences of separately compiled programs; each is a
fusion-boundary estimate, same caveat as ``_stage_breakdown``):

  trunk backward  = grad_full - grad_wall   (trunk dgrad + trunk wgrad)
  head+rpn bwd    = grad_wall - fwd
  all wgrads      = grad_full - grad_imgs
  trunk wgrad     ~ (grad_full - grad_wall) - (grad_imgs - fwd_trunk_dgrad)
                    -- not separable without more programs; the three
                    rows above already say where the milliseconds live.

Run ON THE CHIP (six programs, each a fresh compile of a
resnet18-class program — the historically safe compile class; the two
trunk-only programs are small, the four loss/grad variants ~40 s each):

    python benchmarks/grad_breakdown.py [--config voc_resnet18]
                                        [--batch-size 16]

Writes ``benchmarks/grad_breakdown.json``. Refuses to run on a
non-TPU backend unless ``GRAD_BREAKDOWN_CPU=1`` (the CPU path exists
for the unit test, at tiny shapes only).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import optax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `python benchmarks/grad_breakdown.py` from anywhere
    sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "benchmarks", "grad_breakdown.json")


def build(config_name: str, batch_size: int, image_size=None):
    import dataclasses

    from replication_faster_rcnn_tpu.config import get_config
    from replication_faster_rcnn_tpu.data import SyntheticDataset
    from replication_faster_rcnn_tpu.data.loader import collate
    from replication_faster_rcnn_tpu.train import (
        create_train_state,
        make_optimizer,
    )

    cfg = get_config(config_name)
    cfg = cfg.replace(
        data=dataclasses.replace(
            cfg.data,
            dataset="synthetic",
            **({"image_size": tuple(image_size)} if image_size else {}),
        ),
        train=dataclasses.replace(cfg.train, batch_size=batch_size),
    )
    tx, _ = make_optimizer(cfg, steps_per_epoch=100)
    model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
    ds = SyntheticDataset(cfg.data, length=batch_size)
    batch = collate([ds[i] for i in range(batch_size)])
    device_batch = jax.tree_util.tree_map(jnp.asarray, batch)
    return model, cfg, state, device_batch


def timed(fn, *args, n=5):
    for _ in range(2):  # compile + stabilize
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / n * 1e3


def make_programs(model, cfg, state, batch):
    from replication_faster_rcnn_tpu.train.train_step import compute_losses

    rng = jax.random.fold_in(state.rng, state.step)

    def _trunk(train):
        @jax.jit
        def t(params, batch):
            v = {"params": params, "batch_stats": state.batch_stats}
            feat, _ = model.apply(
                v, batch["image"], train, method="extract_features",
                mutable=["batch_stats"],
            )
            feats = feat if isinstance(feat, (list, tuple)) else [feat]
            return sum(f.astype(jnp.float32).sum() for f in feats)

        return t

    @jax.jit
    def fwd(params, batch):
        total, _ = compute_losses(
            model, cfg, params, state.batch_stats, batch, rng, True
        )
        return total

    def _grad_of(wall):
        @jax.jit
        def g(params, batch):
            def loss_fn(p):
                return compute_losses(
                    model, cfg, p, state.batch_stats, batch, rng, True,
                    features_wall=wall,
                )

            (total, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params
            )
            # consume every gradient exactly as the train step does
            return total + optax.global_norm(grads)

        return g

    @jax.jit
    def grad_imgs(params, batch):
        def loss_fn(images):
            return compute_losses(
                model, cfg, params, state.batch_stats,
                dict(batch, image=images), rng, True,
            )

        (total, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            batch["image"].astype(jnp.float32)
        )
        return total + jnp.sqrt((g.astype(jnp.float32) ** 2).sum())

    return fwd, _grad_of(True), _grad_of(False), grad_imgs, _trunk(True), _trunk(False)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="voc_resnet18")
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--image-size", type=int, nargs=2, default=None)
    args = ap.parse_args()

    backend = jax.default_backend()
    if backend not in ("tpu",) and not os.environ.get("GRAD_BREAKDOWN_CPU"):
        raise SystemExit(
            f"backend is {backend!r}; this attribution is meaningful on the "
            "chip only (GRAD_BREAKDOWN_CPU=1 overrides for tiny-shape tests)"
        )

    model, cfg, state, batch = build(
        args.config, args.batch_size, args.image_size
    )
    fwd, grad_wall, grad_full, grad_imgs, trunk_train, trunk_eval = (
        make_programs(model, cfg, state, batch)
    )

    rows = {}
    # cheap-to-expensive, banking each row as it lands. The trunk
    # train/eval A/B tests the BN-density hypothesis from
    # layer_cost_table (STAGE_BREAKDOWN.md): eval-mode BN is a fusable
    # affine; train-mode adds the batch-stats reductions
    for name, fn in (
        ("trunk_train_ms", trunk_train),
        ("trunk_eval_ms", trunk_eval),
        ("fwd_ms", fwd),
        ("grad_wall_ms", grad_wall),
        ("grad_imgs_ms", grad_imgs),
        ("grad_full_ms", grad_full),
    ):
        rows[name] = round(timed(fn, state.params, batch), 2)
        print(f"{name}: {rows[name]}", flush=True)
        _write(args, backend, rows)

    rows["attrib_trunk_backward_ms"] = round(
        rows["grad_full_ms"] - rows["grad_wall_ms"], 2
    )
    rows["attrib_rpn_head_backward_ms"] = round(
        rows["grad_wall_ms"] - rows["fwd_ms"], 2
    )
    rows["attrib_all_wgrads_ms"] = round(
        rows["grad_full_ms"] - rows["grad_imgs_ms"], 2
    )
    _write(args, backend, rows)
    print(json.dumps(rows))


def _write(args, backend, rows) -> None:
    with open(OUT, "w") as f:
        json.dump(
            {
                "config": args.config,
                "batch_size": args.batch_size,
                "image_size": args.image_size,
                "backend": backend,
                "rows": rows,
                "recorded_utc": time.strftime(
                    "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
                ),
                "note": (
                    "differences of separately jitted programs (fusion "
                    "boundaries differ; small negatives are noise floors). "
                    "grad_wall stops gradients at the trunk features "
                    "(compute_losses features_wall); grad_imgs "
                    "differentiates w.r.t. images with params closed over "
                    "(full dgrad chain, zero wgrads); trunk_train/"
                    "trunk_eval are the forward trunk with train-/eval-"
                    "mode BN — their delta prices the train-mode "
                    "batch-stats reductions (the BN-density hypothesis, "
                    "STAGE_BREAKDOWN.md)"
                ),
            },
            f,
            indent=1,
        )


if __name__ == "__main__":
    main()
