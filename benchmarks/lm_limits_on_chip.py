"""The readings the limits of a sequence model's cell are set from
(`trinity_ep8.packed8k`, `qwen3next_ep16.packed16k`): many seeds in one
process on the chip.

    chiprun --timeout 2400 -- python3 benchmarks/lm_limits_on_chip.py [--seeds 12 --control-seeds 3]
    chiprun --timeout 3000 -- python3 benchmarks/lm_limits_on_chip.py --workload qwen3next_ep16.packed16k \
        --out chiprun_out/limits_qwen3next.jsonl
    python3 benchmarks/lm_limits_on_chip.py --workload qwen3next_ep16.packed16k \
        --judge-saved chiprun_out/limits_qwen3next.jsonl      # no chip: the saved numbers under the limits as they stand

For each seed: documents, weights and batches from the seed; the trainer's
first steps through `train_one_batch` (the LOWER readings: the program
against the float32 reference). On the first `--control-seeds` seeds the
UPPER readings, each put in the program's place: the reference with operands
rounded to float8 e4m3 (the control: the nearest precision below the
configuration's bfloat16) and to bfloat16 (the program's own precision: it
passes), each further precision the reference module names (`PROBE_PRECISIONS`:
the delta rule's state kept in bfloat16; read, not judged), the reference on half of each
batch's rows (of a batch of one row: on the first half of the row), the
program's own numbers with the state left unchanged, and every row of the
feed shifted by a token. Each set of numbers is judged by the cell's limits (`correct`), so a
rerun says at once whether the program still passes on every seed and every
control still fails. Readings come before the limits they set: `--judge-saved`
judges a saved file's sets again by the limits the configuration has now, on
the host. `--probe delta_bfloat16` puts a lower precision into the PROGRAM's
own delta rule (`PROGRAM_PROBES`) and reads it as the program is read; it is
judged and reported, and counts neither way.

`perf/readings.py` is the harness's tool for this and keeps the trainer's
state on the chip while the reference runs; this configuration's state
(8 GB) and the reference's (10.6 GB + its step) do not fit together, so the
state is freed first. One JSON line a seed, appended to
`chiprun_out/limits_trinity.jsonl`; PERF.md section 6 (PR 31) has the table
the readings went into. The same controls at the tiny size, in tier-1:
`tests/perf_yardstick/test_lm_cell.py`.
"""

from __future__ import annotations

import argparse
import gc
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
from flax import traverse_util  # noqa: E402

from perf import compare, harness, manifest, readings  # noqa: E402


PASS = ("program", "reference_bfloat16")
MUST_FAIL = ("control_float8", "half_rows", "state_unchanged", "feed_shifted")


def _delta_rule_in_bfloat16():
    """ISSUE 33's planted precision, in the program's own path: what
    `ops/delta_rule.py` computes at `Precision.HIGHEST` (the products that make
    `T`, then `U` and `W`) takes the chip's default, bfloat16 operands, and the
    state is handed from chunk to chunk in bfloat16."""
    from replication_faster_rcnn_tpu.ops import delta_rule as dr

    dr.HI = jax.lax.Precision.DEFAULT

    def states(state, u, w, kd, a):
        dtype = w.dtype

        def one(s, chunk):
            u, w, kd, a = chunk
            vn = u - dr._mm("nhcd,nhde->nhce", w, s, dtype)
            after = a[..., None, None] * s + dr._mm("nhcd,nhce->nhde", kd, vn, dtype)
            return after.astype(jnp.bfloat16).astype(jnp.float32), (s, vn)

        by_chunk = lambda x: jnp.moveaxis(x, 2, 0)
        state, (starts, vn) = jax.lax.scan(one, state, tuple(map(by_chunk, (u, w, kd, a))))
        return starts, vn, state

    dr._states = states


PROGRAM_PROBES = {"delta_bfloat16": _delta_rule_in_bfloat16}


def judge_saved(path, limits) -> int:
    """Every set of numbers in a file this script wrote, judged by ``limits``."""
    tally, unexpected = {}, []
    with open(path) as f:
        for row in map(json.loads, f):
            for name, nums in row.items():
                if not (isinstance(nums, dict) and "correct" in nums):
                    continue
                entries = {k: {"value": v} for k, v in nums.items() if k != "correct"}
                correct = compare.judge(entries, limits)  # attaches each number's limit
                over = [k for k, e in entries.items() if e["limit"] is not None and not e["value"] <= e["limit"]]
                passed, of, failed_by = tally.get(name, (0, 0, set()))
                tally[name] = (passed + correct, of + 1, failed_by | set(over))
                if correct != (name not in MUST_FAIL) and name in PASS + MUST_FAIL:
                    unexpected.append((row["seed"], name))
    for name, (passed, of, failed_by) in tally.items():
        print(json.dumps({"set": name, "correct": passed, "of": of, "over_their_limit": sorted(failed_by)}))
    print(json.dumps({"ok": not unexpected, "unexpected": unexpected, "limits": limits}))
    return 0 if not unexpected else 1


def _free(state):
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()


def _seeded_state(trainer, ref, sz, stats0, seed):
    """The trainer's state with the reference's weights of this seed, as
    `harness.inject_weights` leaves it, built with no second copy alive."""
    from replication_faster_rcnn_tpu.train.train_step import TrainState

    key = jax.random.PRNGKey(seed % (2**31 - 1))
    flat = jax.jit(lambda k: ref.init_params(sz, jax.random.fold_in(k, 1)))(key)
    params = traverse_util.unflatten_dict({tuple(k.split("/")): v for k, v in flat.items()})
    sh = trainer._state_shardings
    params = jax.device_put(params, sh.params)
    return TrainState(
        step=jax.device_put(jnp.zeros((), jnp.int32), sh.step), params=params,
        batch_stats=jax.device_put(stats0, sh.batch_stats),
        opt_state=jax.device_put(trainer.tx.init(params), sh.opt_state),
        rng=jax.device_put(jax.random.fold_in(key, 2), sh.rng),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first", type=int, default=2_100_000_000, help="the first seed; the others follow 7,919 apart")
    ap.add_argument("--manifest", default=os.path.join(REPO, "BENCHMARK.json"))
    ap.add_argument("--workload", default="trinity_ep8.packed8k")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "limits_trinity.jsonl"))
    ap.add_argument("--judge-saved", metavar="FILE", help="judge a saved file's sets by the cell's limits; runs nothing")
    ap.add_argument("--probe", choices=sorted(PROGRAM_PROBES), help="a lower precision planted in the program's own path")
    args = ap.parse_args(argv)

    cell = manifest.Cell(REPO, args.manifest, args.workload)
    limits = cell.config["limits"]
    if args.judge_saved:
        return judge_saved(args.judge_saved, limits)
    if args.probe:
        PROGRAM_PROBES[args.probe]()
    program_set = "probe_program_" + args.probe if args.probe else "program"
    ref, feed_ref = harness.load_reference(cell), harness.load_feed_reference(cell)
    get_config, Trainer = harness.package_program()
    scratch = os.path.join(REPO, ".perf_scratch", "limits")
    kit = os.path.join(scratch, "data")
    seeds = [args.first + 7919 * i for i in range(args.seeds)]
    cfg = harness.program_config(
        cell, seeds[0], feed_ref.overrides(kit), os.path.join(REPO, ".compile_cache"), get_config
    )
    feed_ref.make(kit, seeds[0], cell.mix)
    trainer = Trainer(cfg, workdir=os.path.join(scratch, "workdir"), devices=jax.devices()[:1])
    batch = cfg.train.batch_size
    sz = ref.Sizes(cell.config["sizes"], batch)
    stats0 = jax.device_get(trainer.state.batch_stats)
    jitted, halved = {}, {}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    def judged(nums):
        correct = compare.judge(nums, limits)
        return {"correct": correct, **{k: v["value"] for k, v in nums.items()}}

    unexpected = []
    with open(args.out, "a") as out:
        for n, seed in enumerate(seeds):
            t0 = time.time()
            feed_ref.make(kit, seed, cell.mix)
            host = []
            for b in readings.fresh_loader(trainer, kit, seed % (2**31 - 1)):
                host.append(b)
                if len(host) == harness.WARM_STEPS:
                    break
            if trainer.state is not None:
                _free(trainer.state)
            trainer.state = _seeded_state(trainer, ref, sz, stats0, seed)
            program = harness.first_steps(
                trainer, iter([{"batch": b} for b in host]), lambda kw: trainer.train_one_batch(**kw), ref.LOSS_PARTS
            )
            _free(trainer.state)
            trainer.state = None
            gc.collect()
            t1 = time.time()
            three = host[: harness.CHECK_STEPS]
            reference = harness.reference_numbers(ref, sz, seed, three, jitted=jitted)
            row = {
                "seed": seed, "losses_program": program["losses"], "losses_reference": reference["losses"],
                program_set: judged({
                    **compare.numbers(program, reference), **feed_ref.numbers(kit, three, cell.config["sizes"])
                }),
                "program_s": round(t1 - t0, 1), "reference_s": round(time.time() - t1, 1),
            }
            must_fail = []
            if n < args.control_seeds:
                probes = [("probe_" + name, {"precision": name}) for name in getattr(ref, "PROBE_PRECISIONS", ())]
                for name, kw in [
                    ("control_float8", {"precision": "float8"}), ("reference_bfloat16", {"precision": "bfloat16"}),
                ] + probes:
                    other = harness.reference_numbers(ref, sz, seed, three, jitted=jitted, **kw)
                    row[name] = judged(compare.numbers(other, reference))
                if batch > 1:
                    half = harness.reference_numbers(ref, sz, seed, three, jitted=halved, rows=batch // 2)
                else:
                    cut = [{k: v[:, : v.shape[1] // 2] for k, v in b.items()} for b in three]
                    half = harness.reference_numbers(ref, sz, seed, cut, jitted=halved)
                row["half_rows"] = judged(compare.numbers(half, reference))
                unchanged = dict(program, change_norms={k: 0.0 for k in program["change_norms"]})
                row["state_unchanged"] = judged(compare.numbers(unchanged, reference))
                row["feed_shifted"] = judged(feed_ref.numbers(kit, three, cell.config["sizes"], in_place="shift"))
                must_fail = MUST_FAIL
            unexpected += [(seed, k) for k in PASS if k in row and not row[k]["correct"]]
            unexpected += [(seed, k) for k in must_fail if row[k]["correct"]]
            row["seconds"] = round(time.time() - t0, 1)
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps(row), flush=True)
    strict = trainer.strict.report() if trainer.strict is not None else {}
    print(json.dumps({"ok": not unexpected, "unexpected": unexpected, "strict": strict}))
    return 0 if not unexpected else 1


if __name__ == "__main__":
    sys.exit(main())
