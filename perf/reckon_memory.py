"""Compile-only reckoning of a configuration's per-chip memory.

    JAX_PLATFORMS=cpu python3 perf/reckon_memory.py <config> [<config> ...] [--write]

Lowers the program's own train step (and the benchmark's float32 reference
step, donated as the harness runs it) over the batch the configuration's
data module describes (`batch_spec`), at the per-chip batch, for a DESCRIBED
TPU v5e (`jax.experimental.topologies`, no chip attached), compiles with the
TPU compiler installed here and prints `memory_analysis()`, and for the
reference the state it holds (16 B a parameter) beside its step's
temporaries: the comparison adds nothing parameter-sized to either, so
the larger of the two totals is what a cell needs of the chip. `--write` puts
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


def package_step(cfg):
    """The package's own `(step, state)`: the un-jitted train step
    `(state, batch) -> (state, metrics)` and the shapes of its state."""
    import jax

    from replication_faster_rcnn_tpu.train.train_step import (
        create_train_state, make_optimizer, make_train_step,
    )

    tx, _ = make_optimizer(cfg, 64)
    model = None

    def init():
        nonlocal model
        model, state = create_train_state(cfg, jax.random.PRNGKey(0), tx)
        return state

    state = jax.eval_shape(init)
    return make_train_step(model, cfg, tx), state


def reckon(config_path: str, with_reference: bool, program=None, chip=None) -> dict:
    """`program` is `(get_config, step_and_state)`, the package's own unless
    a rehearsal brings another; `chip` the sharding compiled for, a described
    v5e chip unless a test has none to describe."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perf import harness, manifest

    with open(config_path) as f:
        conf = json.load(f)
    device = "described v5e:2x2, one chip" if chip is None else str(chip)
    if chip is None:
        from jax.experimental import topologies

        chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    get_config, step_and_state = program or (harness.package_program()[0], package_step)
    b = int(conf["per_chip_batch"])
    cfg = get_config(conf["program"]["preset"])
    cfg = harness._set_dotted(cfg, dict(conf["program"].get("overrides", {}), **{"train.batch_size": b}))
    data_dir = manifest.data_dir_of(config_path)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree
        )

    train_step, state = step_and_state(cfg)
    state = on_chip(state)
    spec = harness.load_module(data_dir, "references", conf["feed_reference"]).batch_spec(conf["sizes"], b)
    batch = on_chip({k: jax.ShapeDtypeStruct(shape, dtype) for k, (shape, dtype) in spec.items()})
    out = {"per_chip_batch": b, "device": device + "; compiler sizes, nothing ran"}

    def sizes(compiled, secs):
        ma = compiled.memory_analysis()
        total = ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
        return {
            "temp_bytes": int(ma.temp_size_in_bytes), "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes), "alias_bytes": int(ma.alias_size_in_bytes),
            "total_bytes": int(total), "share_of_16e9": round(total / 16e9, 4), "compile_s": round(secs, 1),
        }

    t = time.time()
    step = jax.jit(train_step, donate_argnums=(0,))
    out["train_step"] = sizes(step.lower(state, batch).compile(), time.time() - t)
    print(conf["name"], "train_step", out["train_step"], flush=True)
    if with_reference:
        ref = harness.load_module(data_dir, "references", conf["reference"])
        sz = ref.Sizes(conf["sizes"], b)
        params = on_chip(jax.eval_shape(lambda: ref.init_params(sz, jax.random.PRNGKey(0))))
        adam = {"mu": params, "nu": params}
        rbatch = {k: batch[k] for k in ref.BATCH_KEYS}
        key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
        i = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        t = time.time()
        # as `harness.reference_numbers` runs it: parameters and moments written over
        rstep = jax.jit(lambda p, a, bb, r, s: ref.train_step(p, a, bb, r, s, sz), donate_argnums=(0, 1))
        out["reference_step"] = got = sizes(rstep.lower(params, adam, rbatch, key, i).compile(), time.time() - t)
        print(conf["name"], "reference_step", got, flush=True)
        # what the chip holds while the reference runs: parameters and both
        # moments in, one gradient out, beside the step's temporaries
        leaves = jax.tree_util.tree_leaves(params)
        n, state = sum(v.size for v in leaves), 4 * sum(v.size * v.dtype.itemsize for v in leaves)
        print(
            f"{conf['name']} reference holds: state {state:,} B ({state / n:.0f} B x {n:,} parameters) + "
            f"step temp {got['temp_bytes']:,} B = {state + got['temp_bytes']:,} B = "
            f"{100 * (state + got['temp_bytes']) / 16e9:.1f} % of 16e9", flush=True,
        )
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
