"""The attention function at a latent-attention layer's shapes on the chip:
q and k at their own width (nope + rope, 192 in `joyai_flash_ep16`) against
q and k padded with noughts to the next multiple of 128 (256) under the same
scale, forward and backward, one row of `data.seq_len` tokens, bfloat16.

    python3 benchmarks/latent_attention_on_chip.py [--preset joyai_flash_ep16] [--repeats 20]  # one TPU chip

Prints one JSON line a width: the median milliseconds of a forward and of a
forward + backward pass, and the largest gap of the padded form's output and
gradients from the unpadded one's. Exits non-zero off the TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from replication_faster_rcnn_tpu.config import get_config  # noqa: E402
from replication_faster_rcnn_tpu.ops.attention import attention  # noqa: E402


def _median_ms(fn, args, repeats):
    jax.block_until_ready(fn(*args))  # compiled and warm
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t))
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", default="joyai_flash_ep16")
    ap.add_argument("--repeats", type=int, default=20)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing measured", file=sys.stderr)
        return 2
    cfg = get_config(args.preset)
    lm = cfg.lm
    t, h, dv = cfg.data.seq_len, lm.num_heads, lm.v_head_dim
    d = lm.qk_nope_head_dim + lm.qk_rope_head_dim
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(key, (1, t, h, d), jnp.bfloat16) for key in keys[:2])
    v, g = (jax.random.normal(key, (1, t, h, dv), jnp.bfloat16) for key in keys[2:])
    scale = d ** -0.5
    results = {}
    for width in (d, -(-d // 128) * 128):
        pad = [(0, 0)] * 3 + [(0, width - d)]

        def forward(q, k, v, pad=pad):
            return attention(jnp.pad(q, pad), jnp.pad(k, pad), v, None, scale)

        def both(q, k, v, g, forward=forward):
            out, pull = jax.vjp(forward, q, k, v)
            return out, pull(g)

        fwd, fb = jax.jit(forward), jax.jit(both)
        results[width] = jax.device_get(fb(q, k, v, g))
        row = {
            "width": width, "seq_len": t, "heads": h, "v_width": dv,
            "forward_ms": _median_ms(fwd, (q, k, v), args.repeats),
            "forward_backward_ms": _median_ms(fb, (q, k, v, g), args.repeats),
        }
        if width != d:
            gap = jax.tree_util.tree_map(
                lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))), results[width], results[d]
            )
            row["largest_gap_from_unpadded"] = max(jax.tree_util.tree_leaves(gap))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
