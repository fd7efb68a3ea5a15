"""Compile-only reckoning of a configuration's per-chip memory.

    JAX_PLATFORMS=cpu python3 perf/reckon_memory.py <config> [<config> ...] [--write]

Lowers the program's own `make_train_step` (and the benchmark's float32
reference step) at the configuration's per-chip batch for a DESCRIBED TPU
v5e (`jax.experimental.topologies`, no chip attached), compiles with the
TPU compiler installed here and prints `memory_analysis()`. `--write` puts
the bytes into the configuration file's `memory_reckoning`. Nothing runs:
these are the compiler's sizes, not a chip's readings. About a minute a
program; not a test.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def reckon(config_path: str, with_reference: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perf import harness
    from replication_faster_rcnn_tpu.config import get_config
    from replication_faster_rcnn_tpu.train.train_step import (
        create_train_state, make_optimizer, make_train_step,
    )

    with open(config_path) as f:
        conf = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    b = int(conf["per_chip_batch"])
    cfg = get_config(conf["program"]["preset"])
    cfg = harness._set_dotted(cfg, dict(conf["program"].get("overrides", {}), **{"train.batch_size": b}))
    tx, _ = make_optimizer(cfg, 64)
    h, w = cfg.data.image_size
    m = cfg.data.max_boxes

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree
        )

    model = None

    def init():
        nonlocal model
        model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
        return state

    state = on_chip(jax.eval_shape(init))
    batch = on_chip(
        {
            "image": jax.ShapeDtypeStruct((b, h, w, 3), jnp.float32),
            "boxes": jax.ShapeDtypeStruct((b, m, 4), jnp.float32),
            "labels": jax.ShapeDtypeStruct((b, m), jnp.int32),
            "mask": jax.ShapeDtypeStruct((b, m), jnp.bool_),
            "difficult": jax.ShapeDtypeStruct((b, m), jnp.bool_),
        }
    )
    out = {"per_chip_batch": b, "device": "described v5e:2x2, one chip; compiler sizes, nothing ran"}

    def sizes(compiled, secs):
        ma = compiled.memory_analysis()
        total = ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
        return {
            "temp_bytes": int(ma.temp_size_in_bytes), "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes), "alias_bytes": int(ma.alias_size_in_bytes),
            "total_bytes": int(total), "share_of_16e9": round(total / 16e9, 4), "compile_s": round(secs, 1),
        }

    t = time.time()
    step = jax.jit(make_train_step(model, cfg, tx), donate_argnums=(0,))
    out["train_step"] = sizes(step.lower(state, batch).compile(), time.time() - t)
    print(conf["name"], "train_step", out["train_step"], flush=True)
    if with_reference:
        import importlib

        ref = importlib.import_module("perf.references." + conf["reference"])
        sz = ref.Sizes(conf["sizes"], b)
        params = on_chip(jax.eval_shape(lambda: ref.init_params(sz, jax.random.PRNGKey(0))))
        adam = {"mu": params, "nu": params}
        rbatch = {k: batch[k] for k in ("image", "boxes", "labels", "mask")}
        key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
        i = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        t = time.time()
        rstep = jax.jit(lambda p, a, bb, r, s: ref.train_step(p, a, bb, r, s, sz))
        out["reference_step"] = sizes(rstep.lower(params, adam, rbatch, key, i).compile(), time.time() - t)
        print(conf["name"], "reference_step", out["reference_step"], flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("configs", nargs="+")
    ap.add_argument("--write", action="store_true")
    ap.add_argument("--no-reference", action="store_true")
    args = ap.parse_args()
    for name in args.configs:
        path = name if name.endswith(".json") else os.path.join(ROOT, "perf", "configs", name + ".json")
        got = reckon(path, not args.no_reference)
        if args.write:
            with open(path) as f:
                conf = json.load(f)
            conf["memory_reckoning"] = got
            with open(path, "w") as f:
                json.dump(conf, f, indent=1)
                f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
