"""Do the `ops/pallas/` kernels compile on the chip, and do they match XLA?

    python benchmarks/pallas_on_chip.py        # needs a TPU; one process

For each kernel, at the shapes of the `voc_resnet18` train step (600x600:
12,996 anchors, 12000->600 proposals, 632 match candidates, 128 ROIs on a
38x38x256 map, batch 16 under `vmap`) and of the 600x600 serve program
(3000->300 proposals, 6300->100 detections, the VGG head's int8 GEMM), it
compiles the kernel with ``interpret=False`` and compares the result with
its XLA twin ON THE SAME DEVICE: NMS selections, matching outputs and the
int8 kernels bitwise, ROIAlign to the tier-1 tolerance.

A kernel the Mosaic compiler refuses is reported with the compiler's own
message, not skipped; the script exits non-zero if any case failed to
compile or to match, and without a TPU it exits before doing anything.
Whatever Python alone can refuse is caught earlier, on any host, by
tests/test_pallas_tpu_lowering.py. What this prints is a correctness
record — it times nothing.

Writes chiprun_out/pallas_on_chip.json; the last stdout line is
``{"ok": ..., "device": {...}, "cases": N, "failed": [...]}``.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

BATCH = 16


def _boxes(rng, n, extent=600.0, clustered=True):
    """[n, 4] float32 boxes; ``clustered`` packs them around a few centres
    so NMS and matching see real overlap, not 12k disjoint boxes."""
    if clustered:
        centres = rng.uniform(60, extent - 60, (24, 2))
        c = centres[rng.integers(0, 24, n)] + rng.normal(0, 25, (n, 2))
    else:
        c = rng.uniform(0, extent, (n, 2))
    wh = rng.uniform(16, 220, (n, 2))
    tl = np.clip(c - wh / 2, 0, extent - 2)
    br = np.clip(c + wh / 2, tl + 1, extent)
    return np.concatenate([tl, br], axis=1).astype(np.float32)


def _cases(interpret: bool = False):
    """(name, pallas_fn, xla_fn, args, atol) — atol None means bitwise.
    ``interpret`` stays False on the chip; a CPU check of this harness
    flips it."""
    from replication_faster_rcnn_tpu.ops import boxes as box_ops
    from replication_faster_rcnn_tpu.ops import roi_ops
    from replication_faster_rcnn_tpu.ops.nms_tiled import nms_fixed_tiled
    from replication_faster_rcnn_tpu.ops.pallas import (
        dequantize_pallas,
        iou_matrix_pallas,
        match_boxes_pallas,
        nms_fixed_pallas,
        quant_matmul_pallas,
        roi_align_pallas,
    )

    rng = np.random.default_rng(0)
    out = []

    def nms_case(name, n, max_out, sorted_, masked, batch=None):
        lead = () if batch is None else (batch,)
        b = np.stack([_boxes(rng, n) for _ in range(batch or 1)])
        s = rng.uniform(0, 1, (batch or 1, n)).astype(np.float32)
        if sorted_:
            s = -np.sort(-s, axis=1)
        m = rng.uniform(0, 1, (batch or 1, n)) > 0.1
        args = [x.reshape(lead + x.shape[1:]) for x in (b, s, m)]

        def make(impl, **kw):
            def one(b, s, m):
                return impl(
                    b, s, 0.7, max_out, mask=m if masked else None,
                    assume_sorted=sorted_, **kw,
                )

            return jax.vmap(one) if batch else one

        out.append((
            name, make(nms_fixed_pallas, interpret=interpret),
            make(nms_fixed_tiled), args, None,
        ))

    nms_case("nms_12000_600_sorted", 12000, 600, True, True)
    nms_case("nms_12000_600_sorted_vmap16", 12000, 600, True, True, BATCH)
    nms_case("nms_3000_300_sorted", 3000, 300, True, True)
    nms_case("nms_6300_100_unsorted_masked", 6300, 100, False, True)

    def xla_match(a, g, m):
        ious = jnp.where(m[None, :], box_ops.iou(a, g), -1.0)
        return (
            ious, jnp.argmax(ious, 1).astype(jnp.int32),
            jnp.max(jnp.maximum(ious, 0.0), 1),
            jnp.argmax(ious, 0).astype(jnp.int32),
        )

    def match_case(name, n, want_col, batch=None):
        gt = np.stack([_boxes(rng, 32) for _ in range(batch or 1)])
        mask = np.arange(32)[None, :] < rng.integers(1, 9, (batch or 1, 1))
        if want_col:  # anchors are shared across the batch
            a = _boxes(rng, n, clustered=False)
            pal = lambda a, g, m: match_boxes_pallas(a, g, m, interpret=interpret)  # noqa: E731
            xla = xla_match
            axes = (None, 0, 0)
        else:
            a = np.stack([_boxes(rng, n) for _ in range(batch or 1)])
            a = a if batch else a[0]
            pal = lambda a, g, m: iou_matrix_pallas(a, g, m, interpret=interpret)  # noqa: E731
            xla = lambda a, g, m: xla_match(a, g, m)[:3]  # noqa: E731
            axes = (0, 0, 0)
        if batch:
            pal, xla = jax.vmap(pal, axes), jax.vmap(xla, axes)
        else:
            gt, mask = gt[0], mask[0]
        out.append((name, pal, xla, [a, gt, mask], None))

    match_case("anchor_match_12996x32", 12996, True)
    match_case("anchor_match_12996x32_vmap16", 12996, True, BATCH)
    match_case("proposal_match_632x32", 632, False)
    match_case("proposal_match_632x32_vmap16", 632, False, BATCH)

    # bfloat16 features still come out float32 (the rois promote), so one
    # tolerance serves both
    for dtype, atol in ((jnp.float32, 1e-5), (jnp.bfloat16, 1e-5)):
        feat = jnp.asarray(rng.standard_normal((38, 38, 256)), dtype)
        rois = _boxes(rng, 128)
        out.append((
            f"roi_align_38x38x256_128_{jnp.dtype(dtype).name}",
            lambda f, r: roi_align_pallas(f, r, 7, 2, 1 / 16.0, interpret=interpret),
            lambda f, r: roi_ops.roi_align(f, r, 7, 2, 1 / 16.0, method="gather"),
            [feat, rois], atol,
        ))

    out.append((  # the head calls it per image under the step's vmap
        "roi_align_38x38x256_128_float32_vmap16",
        jax.vmap(lambda f, r: roi_align_pallas(f, r, 7, 2, 1 / 16.0, interpret=interpret)),
        jax.vmap(lambda f, r: roi_ops.roi_align(f, r, 7, 2, 1 / 16.0, method="gather")),
        [
            jnp.asarray(rng.standard_normal((BATCH, 38, 38, 256)), jnp.float32),
            np.stack([_boxes(rng, 128) for _ in range(BATCH)]),
        ],
        1e-5,
    ))

    x_q = rng.integers(-127, 128, (128, 25088), dtype=np.int8)
    w_q = rng.integers(-127, 128, (25088, 4096), dtype=np.int8)
    scale = rng.uniform(1e-3, 1e-1, 4096).astype(np.float32)
    out.append((
        "int8_matmul_128x25088x4096",
        lambda x, w: quant_matmul_pallas(x, w, interpret=interpret),
        lambda x, w: jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        ),
        [x_q, w_q], None,
    ))
    out.append((
        "dequantize_25088x4096",
        lambda w, s: dequantize_pallas(w, s, interpret=interpret),
        lambda w, s: w.astype(jnp.float32) * s,
        [w_q, scale], None,
    ))
    return out


def _run_case(name, pallas_fn, xla_fn, args, atol):
    rec = {"case": name, "compiled": False, "matched": False}
    args = [jnp.asarray(a) for a in args]
    try:
        got = jax.block_until_ready(jax.jit(pallas_fn)(*args))
    except Exception as e:  # the compiler's refusal IS the result
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        return rec
    rec["compiled"] = True
    want = jax.jit(xla_fn)(*args)
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        worst = max(worst, float(np.max(np.abs(g - w))) if g.size else 0.0)
    rec["max_abs_diff"] = worst
    rec["matched"] = worst == 0.0 if atol is None else worst <= atol
    rec["tolerance"] = "bitwise" if atol is None else atol
    return rec


def main() -> int:
    from replication_faster_rcnn_tpu.telemetry.mfu import require_accelerator

    device = require_accelerator("pallas_on_chip")
    only = set(sys.argv[1:])
    records = []
    for case in _cases(interpret=False):
        if only and not any(tag in case[0] for tag in only):
            continue
        rec = _run_case(*case)
        records.append(rec)
        print(json.dumps(rec), flush=True)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "pallas_on_chip.json"), "w") as f:
        json.dump({"device": device, "cases": records}, f, indent=2)
    failed = [r["case"] for r in records if not r["matched"]]
    print(json.dumps({
        "ok": not failed, "device": device, "cases": len(records),
        "failed": failed,
    }))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
