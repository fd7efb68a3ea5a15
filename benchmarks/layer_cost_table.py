"""Per-conv cost table + MXU-utilization ceiling model (host-side, exact).

ResNet-config MFU should either reach >=0.25 or be bounded by an
analysis naming the irreducible costs. This is the static half of that
analysis (the measured half is a traced run of the benchmark of record
cut by `perf/stagecut.py`): enumerate every
`conv_general_dilated` in the model's own jaxpr (exact traced shapes —
no hand-maintained table) and bound each pass's achievable MXU
utilization from the systolic array's tiling:

  The v5e MXU multiplies 128x128 tiles. A matmul with contraction size
  K and output-channel size M runs at an efficiency ceiling of
  (K / 128ceil(K)) * (M / 128ceil(M)): padding to the tile is wasted
  lanes. Per pass the (K, M) roles are:
    forward   K = Cin*kh*kw,  M = Cout
    dgrad     K = Cout*kh*kw, M = Cin   (skipped for the stem: dx of
                                         the input image is never used)
    wgrad     K = N*OH*OW,    M = Cout  (x Cin*kh*kw output rows; the
                                         huge spatial contraction makes
                                         K-padding negligible)

  A 64-channel layer therefore cannot exceed 50% MXU utilization on its
  forward/wgrad output lanes no matter what the compiler does — that is
  the "irreducible" part; the rest of the gap between the ceiling floor
  and a measured step is XLA scheduling/fusion/HBM, which the traced
  run's per-op table attributes.

Writes ``benchmarks/layer_cost_table.json``:
  per-conv rows (shapes, per-pass GFLOPs and efficiency ceilings) and
  aggregates: plain compute floor (all FLOPs at peak), ceiling-adjusted
  floor (FLOPs / (peak * eff)), and the implied MFU ceiling for a
  measured step time.

Run (CPU is fine and intended — jaxpr tracing only, nothing executes):
    JAX_PLATFORMS=cpu \
      python benchmarks/layer_cost_table.py [--config voc_resnet18]
      [--batch-size 16] [--measured-step-ms 74.0]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "benchmarks", "layer_cost_table.json")

# the v5e's datasheet bf16 peak, from the one table that holds it
from replication_faster_rcnn_tpu.telemetry.mfu import (  # noqa: E402
    tpu_peak_flops_per_sec,
)

PEAK_BF16 = tpu_peak_flops_per_sec("TPU v5 lite", 1)

TILE = 128


def _eff(k: int, m: int) -> float:
    """Tiling efficiency ceiling of a (K contraction, M output-lane)
    matmul on a TILE x TILE systolic array."""
    kp = TILE * math.ceil(k / TILE)
    mp = TILE * math.ceil(m / TILE)
    return (k / kp) * (m / mp)


def collect_convs(config_name: str, batch_size: int, image_size=None):
    import jax

    jax.config.update("jax_platforms", "cpu")  # pure trace; never touch a chip

    from replication_faster_rcnn_tpu.config import get_config
    from replication_faster_rcnn_tpu.train.train_step import (
        compute_losses,
        make_optimizer,
    )
    from replication_faster_rcnn_tpu.train.warmup import abstract_step_inputs

    import dataclasses

    cfg = get_config(config_name)
    cfg = cfg.replace(
        data=dataclasses.replace(
            cfg.data,
            dataset="synthetic",
            **({"image_size": tuple(image_size)} if image_size else {}),
        ),
        train=dataclasses.replace(cfg.train, batch_size=batch_size),
    )
    tx, _ = make_optimizer(cfg, 100)
    # the warm-up's abstract fixture: shapes only, no arrays, no
    # param-init program — this table traces the shapes the trainer's
    # own programs are compiled for
    model, state_abs, batch_abs = abstract_step_inputs(cfg, tx)

    def loss(params, batch_stats, rng, step, batch):
        total, _ = compute_losses(
            model, cfg, params, batch_stats, batch,
            jax.random.fold_in(rng, step), True,
        )
        return total

    jaxpr = jax.make_jaxpr(loss)(
        state_abs.params, state_abs.batch_stats, state_abs.rng,
        state_abs.step, batch_abs,
    )

    convs = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "conv_general_dilated":
                lhs = tuple(eqn.invars[0].aval.shape)
                rhs = tuple(eqn.invars[1].aval.shape)
                out = tuple(eqn.outvars[0].aval.shape)
                convs.append((lhs, rhs, out))
            for sub in eqn.params.values():
                subs = sub if isinstance(sub, (list, tuple)) else (sub,)
                for s in subs:
                    if hasattr(s, "jaxpr"):
                        walk(s.jaxpr)

    walk(jaxpr.jaxpr)
    return cfg, convs


def analyze(convs):
    rows = []
    tot = {"fwd": 0.0, "dgrad": 0.0, "wgrad": 0.0}
    eff_tot = {"fwd": 0.0, "dgrad": 0.0, "wgrad": 0.0}  # flops / eff
    for i, (lhs, rhs, out) in enumerate(convs):
        # NHWC lhs, HWIO rhs, NHWC out (flax convention)
        kh, kw, cin, cout = rhs
        n = lhs[0]
        spatial = out[1] * out[2] if len(out) == 4 else out[1]
        flops = 2.0 * n * spatial * cout * cin * kh * kw
        # accumulate with the UNROUNDED efficiencies (rounding is for the
        # output rows only; a sub-0.0005 efficiency would otherwise
        # divide by zero and the stem's small values would skew the
        # weighted ceiling)
        e_fwd = _eff(cin * kh * kw, cout)
        e_dgrad = _eff(cout * kh * kw, cin)
        e_wgrad = _eff(n * spatial, cout)
        row = {
            "lhs": lhs,
            "rhs": rhs,
            "out": out,
            "gflops_fwd": round(flops / 1e9, 2),
            "eff_fwd": round(e_fwd, 3),
            "eff_dgrad": round(e_dgrad, 3),
            "eff_wgrad": round(e_wgrad, 3),
        }
        stem = i == 0 and cin <= 4  # image input: dx never needed
        row["dgrad_skipped"] = stem
        rows.append(row)
        tot["fwd"] += flops
        eff_tot["fwd"] += flops / e_fwd
        if not stem:
            tot["dgrad"] += flops
            eff_tot["dgrad"] += flops / e_dgrad
        tot["wgrad"] += flops
        eff_tot["wgrad"] += flops / e_wgrad
    return rows, tot, eff_tot


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="voc_resnet18")
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--image-size", type=int, nargs=2, default=None)
    ap.add_argument(
        "--measured-step-ms", type=float, default=None,
        help="measured on-chip step time; adds implied-MFU-ceiling rows",
    )
    args = ap.parse_args()

    out_path = OUT
    if args.config != "voc_resnet18":  # flagship keeps the unsuffixed name
        out_path = OUT.replace(".json", f"_{args.config}.json")

    cfg, convs = collect_convs(args.config, args.batch_size, args.image_size)
    rows, tot, eff_tot = analyze(convs)

    conv_flops = sum(tot.values())
    floor_ms = conv_flops / PEAK_BF16 * 1e3
    ceil_ms = sum(eff_tot.values()) / PEAK_BF16 * 1e3
    agg = {
        "n_convs": len(rows),
        "conv_gflops": {k: round(v / 1e9, 2) for k, v in tot.items()},
        "conv_gflops_total": round(conv_flops / 1e9, 2),
        "weighted_eff_ceiling": {
            k: round(tot[k] / eff_tot[k], 3) for k in tot if eff_tot[k]
        },
        "compute_floor_ms_at_peak": round(floor_ms, 2),
        "compute_floor_ms_at_tiling_ceiling": round(ceil_ms, 2),
    }
    # even a perfect schedule cannot beat the tiling ceiling: this is
    # the conv-MFU bound the architecture's channel widths impose
    agg["best_achievable_conv_mfu"] = round(floor_ms / ceil_ms, 3)
    if args.measured_step_ms:
        agg["measured_step_ms"] = args.measured_step_ms
        agg["gap_vs_tiling_ceiling"] = round(
            args.measured_step_ms / ceil_ms, 2
        )

    out = {
        "config": args.config,
        "batch_size": args.batch_size,
        "peak_bf16_flops": PEAK_BF16,
        "mxu_tile": TILE,
        "aggregate": agg,
        "convs": rows,
        "recorded_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "note": (
            "conv primitives enumerated from the model's own jaxpr (exact "
            "shapes); efficiency ceilings are the 128x128-tile padding "
            "bound per pass — what no compiler schedule can exceed, not a "
            "prediction of what XLA achieves. dgrad of the image-input "
            "stem is skipped (its dx is unused). Non-conv FLOPs (head "
            "matmuls, NMS, targets) are excluded here. CONVENTION: this "
            "table counts the full kh*kw taps per output position (the "
            "work the MXU actually performs on the padded im2col, and the "
            "fvcore/industry convention behind quoted MFU numbers); "
            "XLA's HloCostAnalysis excludes border padding taps (-30.5% "
            "on the ROI head's 4x4x3x3 SAME convs, (10/12)^2 exactly; "
            "-1.4% on the 300x300 stem), so an MFU priced by it reads "
            "lower than one priced from shapes (perf/flops.py)."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"aggregate": agg}))


if __name__ == "__main__":
    main()
