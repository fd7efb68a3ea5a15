"""The gated delta rule alone at the shapes of `qwen3next_ep16.packed16k`, on
the chip: forward, and forward + backward, milliseconds a call.

    chiprun -- python3 benchmarks/delta_rule_on_chip.py [--chunk 64 128] [--segment 16 32 64]

One row of 16,384 tokens, 16 key and 32 value heads of 128, bfloat16 operands;
q and k of unit length, decays as the seeded weights give them. Each writing
(a chunk length and a segment length of `ops/delta_rule.py`) is compiled, run
three times to warm, then timed over `--calls` calls between two
`block_until_ready`. Beside the time: the share of the roofline that
`perf/references/qwen3next.py::delta_rule_roofline_seconds` counts for one
layer, and the largest difference from the first writing's output. One JSON
line a writing, appended to `chiprun_out/delta_rule_timing.jsonl`. PERF.md
section 6 (PR 33) has the readings.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from perf import harness  # noqa: E402
from replication_faster_rcnn_tpu.ops import delta_rule  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chunk", type=int, nargs="+", default=[delta_rule.CHUNK])
    ap.add_argument("--segment", type=int, nargs="+", default=[delta_rule.SEGMENT])
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "delta_rule_timing.jsonl"))
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"needs a TPU chip; found {device.platform}. No result.", file=sys.stderr)
        return 3
    with open(os.path.join(REPO, "perf", "configs", "qwen3_next_ep16.json")) as f:
        sizes = json.load(f)["sizes"]
    ref = harness.load_file(os.path.join(REPO, "perf", "references", "qwen3next.py"))
    least = ref.delta_rule_roofline_seconds(dict(sizes, **{"data.seq_len": args.tokens}), 1, 197e12, 819e9) / 3  # one layer
    kh, h, dk, dv = 16, 32, 128, 128
    r = np.random.RandomState(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = jnp.asarray(unit(r.randn(1, args.tokens, kh, dk)) / np.sqrt(dk), jnp.bfloat16)
    k = jnp.asarray(unit(r.randn(1, args.tokens, kh, dk)), jnp.bfloat16)
    v = jnp.asarray(r.randn(1, args.tokens, h, dv), jnp.bfloat16)
    g = jnp.asarray(-np.exp(r.uniform(np.log(1e-3), np.log(2.0), (1, args.tokens, h))), jnp.float32)
    beta = jnp.asarray(1.0 / (1.0 + np.exp(-r.randn(1, args.tokens, h))), jnp.float32)
    cot = jnp.asarray(r.randn(1, args.tokens, h, dv), jnp.bfloat16)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    first = None
    for chunk, segment in itertools.product(args.chunk, args.segment):
        delta_rule.CHUNK, delta_rule.SEGMENT = chunk, segment
        fn = lambda *a: delta_rule.gated_delta_rule(*a)[0]
        forward = jax.jit(fn)
        both = jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot), argnums=(0, 1, 2, 3, 4)))
        row = {"chunk": chunk, "segment": segment, "tokens": args.tokens, "device": device.device_kind}
        for name, call in (("forward", forward), ("forward_backward", both)):
            t0 = time.perf_counter()
            out = jax.block_until_ready(call(q, k, v, g, beta))
            row[name + "_compile_s"] = round(time.perf_counter() - t0, 2)
            for _ in range(2):
                jax.block_until_ready(call(q, k, v, g, beta))
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = call(q, k, v, g, beta)
            jax.block_until_ready(out)
            row[name + "_ms"] = 1e3 * (time.perf_counter() - t0) / args.calls
            if name == "forward":
                o = np.asarray(out, np.float32)
                first = o if first is None else first
                row["max_gap_to_first"] = float(np.max(np.abs(o - first)))
                row["output_absmax"] = float(np.max(np.abs(o)))
        row["roofline_pct"] = 100.0 * least * 1e3 / row["forward_backward_ms"]
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
