"""Micro-benchmark: the three NMS backends at the training budget.

    python benchmarks/nms_backends.py [--batch 8] [--n 12000] [--out 600]

Prints ms/call for the XLA selection loop (`ops/nms.py`), the tiled
exact algorithm (`ops/nms_tiled.py`), and the Pallas kernel
(`ops/pallas/nms_kernel.py`), plus a selection-parity check — all three
must select identically. Each row names the path that actually EXECUTED:
off-TPU the pallas row runs the interpreter, so its time is a
correctness artifact, never a device number; on a chip it prices the
Mosaic kernel (run it through the chip tool; ROADMAP S6).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _rand(batch: int, n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    ctr = rng.uniform(0, 600, (batch, n, 2))
    wh = rng.uniform(16, 120, (batch, n, 2))
    boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2], -1).astype(np.float32)
    scores = rng.uniform(0, 1, (batch, n)).astype(np.float32)
    return jnp.asarray(boxes), jnp.asarray(scores)


def _time(fn, boxes, scores, reps: int = 10):
    idx, valid = fn(boxes, scores)
    jax.device_get(idx)  # sync (block_until_ready lies on the remote plugin)
    t0 = time.time()
    for _ in range(reps):
        idx, valid = fn(boxes, scores)
    jax.device_get(idx)
    return (time.time() - t0) / reps * 1000, idx, valid


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--n", type=int, default=12000)
    ap.add_argument("--out", type=int, default=600)
    ap.add_argument("--thresh", type=float, default=0.7)
    args = ap.parse_args(argv)

    from replication_faster_rcnn_tpu import ops as ops_pkg
    from replication_faster_rcnn_tpu.ops.nms import nms_fixed
    from replication_faster_rcnn_tpu.ops.nms_tiled import nms_fixed_tiled

    boxes, scores = _rand(args.batch, args.n)
    backends = {
        "loop": jax.jit(jax.vmap(lambda b, s: nms_fixed(b, s, args.thresh, args.out))),
        "tiled": jax.jit(
            jax.vmap(lambda b, s: nms_fixed_tiled(b, s, args.thresh, args.out))
        ),
    }
    executed = {"loop": "xla", "tiled": "xla"}
    nms_fixed_pallas = ops_pkg.require_pallas("nms").nms_fixed_pallas
    interpret = ops_pkg.interpret_mode()
    backends["pallas"] = jax.jit(
        jax.vmap(
            lambda b, s: nms_fixed_pallas(
                b, s, args.thresh, args.out, interpret=interpret
            )
        )
    )
    executed["pallas"] = "pallas_interpret" if interpret else "pallas"
    results = {}
    for name, fn in backends.items():
        ms, idx, valid = _time(fn, boxes, scores)
        results[name] = (ms, np.asarray(idx), np.asarray(valid))
        print(f"{name:>7}: {ms:8.2f} ms/call  "
              f"(batch {args.batch}, {args.n}->{args.out})  "
              f"[executed: {executed[name]}]")

    ref_idx, ref_val = results["loop"][1], results["loop"][2]
    for name, (_, idx, valid) in results.items():
        if name == "loop":
            continue
        ok = bool((idx == ref_idx).all() and (valid == ref_val).all())
        print(f"{name:>7}: selections {'IDENTICAL to' if ok else 'DIFFER from'} loop")
        if not ok:
            return 1

    # the proposal-path tail, both ways (round 4: models/rpn.py sorts
    # once and passes assume_sorted): top_k + internally-sorting NMS vs
    # one argsort + assume_sorted NMS. Outputs live in truncated-candidate
    # index space, so they compare to each other, not to the raw loop.
    pre = min(args.n - args.n // 16, args.n)  # ~top-k keeps most, as in RPN

    def _pipe_topk(b, s):
        ts, ti = jax.lax.top_k(s, pre)
        tb = b[ti]
        return nms_fixed_tiled(
            tb, ts, args.thresh, args.out, mask=jnp.isfinite(ts)
        )

    def _pipe_single_sort(b, s):
        order = jnp.argsort(-s)
        ti = jax.lax.slice_in_dim(order, 0, pre)
        ts = s[ti]
        tb = b[ti]
        return nms_fixed_tiled(
            tb, ts, args.thresh, args.out, mask=jnp.isfinite(ts),
            assume_sorted=True,
        )

    ms_a, idx_a, val_a = _time(jax.jit(jax.vmap(_pipe_topk)), boxes, scores)
    ms_b, idx_b, val_b = _time(
        jax.jit(jax.vmap(_pipe_single_sort)), boxes, scores
    )
    same = bool(
        (np.asarray(idx_a) == np.asarray(idx_b)).all()
        and (np.asarray(val_a) == np.asarray(val_b)).all()
    )
    print(f"proposal tail topk+sort: {ms_a:8.2f} ms/call")
    print(f"proposal tail one-sort : {ms_b:8.2f} ms/call "
          f"({ms_a / max(ms_b, 1e-9):.2f}x; selections "
          f"{'IDENTICAL' if same else 'DIFFER'})")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
