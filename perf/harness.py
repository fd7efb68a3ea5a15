"""One run of one cell: set-up, the timed window, the comparison with the
reference, the result line.

Every cell drives the program's `Trainer` (built as `cli train` builds it)
through `Trainer.train_one_batch` over the feed its traffic mix describes:
the bounded-step loop of `cli.py` with a deadline in place of the step
count. `next(feed)` -> `train_one_batch` -> a host sync of the metrics every
`SYNC_EVERY` steps (the trainer's `log_every` default); the window closes
with `block_until_ready` on state and metrics.

The harness owns what is true of any training cell: the manifest, set-up and
its clock, weights and Adam state injected from the seed, the first-steps
capture, the window loop, the trace reduction, memory, the recompile count,
the generic comparison (perf/compare.py), the result line. The rest is
behind the two names in the configuration's file, each a module looked up as
a reader is (`load_module`: `references/<name>.py` beside the cell's data
files first, else perf/references/):

`reference`, the model's side: `Sizes(sizes, batch)`, `init_params(sz, key)`
(flat, "/"-joined leaf names), `init_adam`, `train_step(params, adam, batch,
rng, step, sz, precision)`, `leaf_norms`; `BATCH_KEYS` the step takes,
`LOSS_PARTS` compared at step 1, `LEAF_NUMBERS` {number: leaf prefixes},
`SCOPE_PREFIX` of the program's stage scopes, and the functions the cell's
readers call on `ctx["flops"]` (`train_flops_per_image(sizes)`: per sample).
`feed_reference`, the data's side: `make(directory, seed, mix)` -> record,
`overrides(directory)` -> dotted program keys, `batch_spec(sizes, batch)`,
`notes(record)`, `numbers(directory, host batches, sizes)`.

The program (`run_cell`'s `program`, default `package_program()`) offers
`get_config(preset)`: a config with `replace` and dataclass sections, of
which `train.batch_size`, `train.seed`, `debug.strict`, `compile.cache_dir`
and what the overrides and `sizes` name; and `Trainer(cfg, workdir=,
devices=, telemetry_dir=)` with `state` (`params`, `opt_state` holding Adam's
`mu`, `rng`, `replace`), `tx`, `_state_shardings`, `loader` (`set_epoch`,
iteration), `_stage_batch(batch, wait=)`, `train_one_batch(batch=|staged=)`
giving metrics with `loss`, the parts (and `skipped` where the program has
it), `strict_session()`, `strict` (None or `report()`), `tracer` (`span`,
`now_us`), `jitted_step`, `flush_telemetry()`.

What the harness itself may hold on the chip. While a step that the harness
drives is running, no device buffer of the harness's own making is alive
except the staged batches; between steps, at most one parameter-sized buffer
(4 B a parameter); the reference runs at its own state's size, 16 B a
parameter, plus its step. So `memory_peak_bytes` measures the program and
not the yardstick, the set-up's peak is the window's, and a configuration
may size its parameters to the chip at 16 B each. Function by function:
`inject_weights`: the seed's parameters beside the trainer's own first state
(16 B), that state released before Adam's is made anew (12 B);
`first_steps`: the first parameters wait on the host, and after step 1 and
step 3 one reduction to per-leaf scalars is dispatched on the state where
it lies (after step 3 with the first parameters put back beside it, 4 B,
gone before step 4 is dispatched); `reference_numbers`: parameters and both
moments donated to each step, one gradient out (16 B), the first parameters
on the host until the moments are dropped.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from perf import compare, manifest, peaks, stagecut, traffic, xtrace

SYNC_EVERY = 10  # the trainer's log_every default
CHECK_STEPS = 3  # steps the reference follows
WARM_STEPS = 4  # steps driven during set-up, through the window's own call
TRACE_SLICE_S = 6.0  # the profiler is started this long before the window closes
MIN_TRACED_S = 3.0  # and runs at least this long (a traced window may run over)
EXIT_NO_CHIP = 3


class RunFailed(RuntimeError):
    pass


# ------------------------------------------------------- program config


def _set_dotted(cfg, dotted: Dict[str, Any]):
    by_section: Dict[str, Dict[str, Any]] = {}
    for key, value in dotted.items():
        section, field = key.split(".", 1)
        by_section.setdefault(section, {})[field] = _tuples(value)
    for section, fields in by_section.items():
        cfg = cfg.replace(**{section: dataclasses.replace(getattr(cfg, section), **fields)})
    return cfg


def _tuples(v):
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


def _lists(v):
    return [_lists(x) for x in v] if isinstance(v, (list, tuple)) else v


def package_program() -> Tuple[Callable, Any]:
    """The package's own `(get_config, Trainer)`."""
    from replication_faster_rcnn_tpu.config import get_config
    from replication_faster_rcnn_tpu.train import Trainer

    return get_config, Trainer


def program_config(cell: manifest.Cell, seed: int, data: Dict[str, Any], cache_dir: str, get_config: Callable):
    """The preset with the cell's overrides and the data module's (`data`);
    the run fails where the program disagrees with a size the
    configuration's file states."""
    conf, mix = cell.config, cell.mix
    cfg = get_config(conf["program"]["preset"])
    dotted = {
        **conf["program"].get("overrides", {}), **mix.get("overrides", {}), **data,
        "train.batch_size": int(conf["per_chip_batch"]) * cell.chips,
        "train.seed": seed % (2**31 - 1),
        "debug.strict": True,
        "compile.cache_dir": cache_dir,
    }
    cfg = _set_dotted(cfg, dotted)
    for key, want in conf["sizes"].items():
        section, field = key.split(".", 1)
        have = _lists(getattr(getattr(cfg, section), field))
        if have != want:
            raise RunFailed(f"the program has {key}={have!r}, the configuration file {want!r}")
    return cfg


# ---------------------------------------------------------- the program


def _adam_mu(opt_state):
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise RunFailed("no Adam moments in the optimizer state")


def _flat(tree) -> Dict[str, Any]:
    from flax import traverse_util

    return traverse_util.flatten_dict(tree, sep="/")


def inject_weights(trainer, ref, sz, seed: int) -> None:
    """Weights, Adam state and sampling key from the seed, made by the
    benchmark in one jitted call and put where the trainer keeps its own.
    The trainer's own first parameters and optimizer state are released
    once the seed's parameters are made and found to match, before Adam's
    state is made anew: the seed's state replaces them and never stands
    beside them (16 B a parameter at the most, 12 B after)."""
    import jax
    from flax import traverse_util

    key = jax.random.PRNGKey(seed % (2**31 - 1))
    flat = jax.jit(lambda k: ref.init_params(sz, jax.random.fold_in(k, 1)))(key)
    own = trainer.state
    have = _flat(own.params)
    if {k: v.shape for k, v in flat.items()} != {k: v.shape for k, v in have.items()}:
        odd = sorted(set(flat) ^ set(have))[:6]
        raise RunFailed(f"the reference's parameters do not match the program's: {odd}")
    for leaf in jax.tree_util.tree_leaves((own.params, own.opt_state)):
        leaf.delete()
    params = traverse_util.unflatten_dict({tuple(k.split("/")): v for k, v in flat.items()})
    sh = trainer._state_shardings
    params = jax.device_put(params, sh.params)
    trainer.state = own.replace(
        params=params,
        opt_state=jax.device_put(trainer.tx.init(params), sh.opt_state),
        rng=jax.device_put(jax.random.fold_in(key, 2), sh.rng),
    )


def _norms_program():
    import jax
    import jax.numpy as jnp

    def leaf_norms(tree):
        return jax.tree_util.tree_map(lambda v: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))), tree)

    return (
        jax.jit(leaf_norms),
        jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(lambda x, y: x - y, a, b))),
    )


def first_steps(trainer, feed, step_call: Callable, parts: Sequence[str]) -> Dict[str, Any]:
    """Drive the trainer through its first steps with the window's own call
    and feed, and take what the comparison needs from that same object:
    each step's loss (the first's `parts` too), Adam's first moment after
    step 1 (the first gradient is mu / (1 - b1)) and the parameters' change
    over the steps. Nothing parameter-sized of the harness's is on the chip
    while a step runs: the first parameters wait on the host, and each
    reduction to per-leaf norms is dispatched on the state where it lies,
    straight after the step's call returns and before the next call donates
    it (the order of dispatch is the order of execution)."""
    import jax

    norms, diff_norms = _norms_program()
    first_params = jax.device_get(trainer.state.params)
    losses, grad, change = [], None, None
    with trainer.strict_session():
        for i in range(WARM_STEPS):
            metrics = step_call(next(feed))
            if i < CHECK_STEPS:
                losses.append(metrics)
            if i == 0:
                grad = norms(_adam_mu(trainer.state.opt_state))
            if i == CHECK_STEPS - 1:
                # fetched at once: the first parameters' buffer is gone
                # before the next step is dispatched
                back = jax.device_put(first_params, trainer._state_shardings.params)
                change = jax.device_get(diff_norms(trainer.state.params, back))
                del first_params, back
    grad = jax.device_get(grad)
    rows = jax.device_get(losses)
    return {
        "losses": [float(r["loss"]) for r in rows],
        "parts": {k: float(rows[0][k]) for k in parts},
        "skipped": [float(r.get("skipped", 0.0)) for r in rows],
        "grad_norms": {k: float(v) / 0.1 for k, v in _flat(grad).items()},
        "change_norms": {k: float(v) for k, v in _flat(change).items()},
    }


# -------------------------------------------------------- the reference


def reference_numbers(
    ref, sz, seed: int, batches: List[Dict[str, np.ndarray]], precision: str = "float32",
    rows: Optional[int] = None, jitted: Optional[Dict[Any, Any]] = None,
) -> Dict[str, Any]:
    """The reference over the same batches from the same seed: its own
    weights, its own steps. `rows` keeps only the first rows of each batch
    (the half-batch fault of the control tests). A caller that runs many
    seeds passes one `jitted` dict to keep the traced programs.

    Each step is given its parameters and moments to write over, so that
    parameters, both moments and one gradient are alive at once: 16 B a
    parameter beside the step's own temporaries. A reference whose step
    would not fit computes it in blocks (a sequence or a few rows at a time,
    summed) inside its own `train_step`."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed % (2**31 - 1))
    params = jax.jit(lambda k: ref.init_params(sz, jax.random.fold_in(k, 1)))(key)
    rng = jax.random.fold_in(key, 2)
    jitted = {} if jitted is None else jitted
    if precision not in jitted:
        jitted[precision] = jax.jit(
            lambda p, a, b, r, s: ref.train_step(p, a, b, r, s, sz, precision), donate_argnums=(0, 1)
        )
        jitted.setdefault("norms", jax.jit(ref.leaf_norms))
        # a reference may hand out one buffer of zeros as both moments, and a
        # buffer is donated once: each moment gets its own
        jitted.setdefault("unshared", jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t)))
    step, norms = jitted[precision], jitted["norms"]
    adam = jitted["unshared"](ref.init_adam(params))
    first_params = jax.device_get(params)
    losses, grad, first = [], None, None
    for i, host in enumerate(batches):
        batch = {k: jnp.asarray(host[k][:rows]) for k in ref.BATCH_KEYS}
        params, adam, parts, seen = step(params, adam, batch, rng, jnp.asarray(i, jnp.int32))
        losses.append(float(parts["loss"]))
        if i == 0:
            grad = jax.device_get(norms(seen))
            first = {k: float(parts[k]) for k in ref.LOSS_PARTS}
        del seen
    del adam
    change = jax.device_get(norms({k: params[k] - jnp.asarray(first_params[k]) for k in params}))
    return {
        "losses": losses,
        "parts": first,
        "grad_norms": {k: float(v) for k, v in grad.items()},
        "change_norms": {k: float(v) for k, v in change.items()},
        "named_leaves": ref.LEAF_NUMBERS,
    }


def load_file(path: str):
    """The module a file holds, loaded once a process."""
    name = "perf_file_" + re.sub(r"\W", "_", os.path.abspath(path))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = mod = importlib.util.module_from_spec(spec)
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def load_module(data_dir: str, kind: str, stem: str):
    """`<kind>/<stem>.py` beside a cell's data files, else the harness's own."""
    return load_file(manifest.beside(data_dir, kind, stem))


def load_reference(cell: manifest.Cell):
    return load_module(cell.data_dir, "references", cell.config["reference"])


def load_feed_reference(cell: manifest.Cell):
    return load_module(cell.data_dir, "references", cell.config["feed_reference"])


def memory_held(stats: Dict[str, Any]) -> int:
    """Peak bytes a chip held: the buffers the allocator counts
    (`peak_bytes_in_use`: weights, optimizer state, staged batches) plus what
    the runtime reserves for the programs' scratch, which it counts apart
    (`peak_bytes_reserved`; on the v5e free = limit - in use - reserved)."""
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


# --------------------------------------------------------------- window


def _annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def window(trainer, feed, step_call: Callable, seconds: float, batch: int,
           trace_dir: Optional[str]) -> Dict[str, Any]:
    """The timed loop. With `trace_dir` the profiler runs over the last
    `TRACE_SLICE_S` seconds, between two drained queues; `pre` holds the
    images and time before it started."""
    import jax

    tracing = trace_dir is not None
    tracer = trainer.tracer
    steps = bad = 0
    metrics = None
    out: Dict[str, Any] = {}
    profiling = False
    span_t0 = tracer.now_us() if tracing else 0.0
    with trainer.strict_session():
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            now = time.perf_counter()
            if tracing and not profiling and steps > 0 and now >= deadline - TRACE_SLICE_S:
                jax.block_until_ready((trainer.state, metrics))
                t_pre = time.perf_counter()
                out["pre"] = {"images": steps * batch, "seconds": t_pre - t0, "steps": steps}
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                profiling = True
                now = out["profile_t0"] = time.perf_counter()
                # starting the profiler can take seconds on a busy host, and a
                # sync can outlast the slice: a traced window is never cut
                # shorter than MIN_TRACED_S after the profiler is up
                deadline = max(deadline, now + MIN_TRACED_S)
            if now >= deadline:
                break
            with tracer.span("data/fetch", cat="data"), _annotate(profiling, "bench/fetch"):
                kw = next(feed)
            with _annotate(profiling, "bench/step"):
                metrics = step_call(kw)
            steps += 1
            if steps % SYNC_EVERY == 0:
                with tracer.span("step/sync", cat="sync"), _annotate(profiling, "bench/sync"):
                    row = jax.device_get(metrics)
                if row.get("skipped", 0.0) > 0 or not math.isfinite(float(row["loss"])):
                    bad += 1
        with _annotate(profiling, "bench/drain"):
            jax.block_until_ready((trainer.state, metrics))
        t1 = time.perf_counter()
    if profiling:
        jax.profiler.stop_trace()
        out["profile_window_s"] = t1 - out.pop("profile_t0")
        out["traced_steps"] = steps - out["pre"]["steps"]
    last = jax.device_get(metrics)
    if last.get("skipped", 0.0) > 0 or not math.isfinite(float(last["loss"])):
        bad += 1
    out.update(steps=steps, images=steps * batch, seconds=t1 - t0, bad=bad)
    if tracing:
        out["span_window_us"] = (span_t0, tracer.now_us())
    return out


def step_executable(trainer, host_batch) -> Dict[str, Any]:
    """The step program a traced window drove, looked up again (the
    persistent cache holds it): the names of its operations that hold a
    convolution, which the trace alone does not say (a fused convolution is
    printed as `%fusion.N`), and each operation's `op_name` metadata (the
    jaxpr path, flax module names in it), with which the breakdown labels
    the operations. The compiler's sizes of the program are in the
    configuration's file, from `reckon_memory.py`."""
    staged = trainer._stage_batch(host_batch)
    compiled = trainer.jitted_step.lower(trainer.state, staged).compile()
    text = compiled.as_text()
    conv_comps, comp = set(), None
    calls: Dict[str, str] = {}
    convs = set()
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        m = stagecut.INSTRUCTION_RE.match(line)
        if not m:
            continue
        name, opcode = m.groups()
        if opcode == "convolution":
            convs.add(name)
            if comp:
                conv_comps.add(comp)
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if opcode == "fusion" and called:
            calls[name] = called.group(1)
    convs |= {name for name, c in calls.items() if c in conv_comps}
    return {"conv_ops": convs, "origin": stagecut.load_origin(text), "hlo_text": text}


# ------------------------------------------------------------------ run


def run_cell(
    root: str,
    manifest_path: str,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: Optional[float] = None,
    scratch: Optional[str] = None,
    require_tpu: bool = True,
    break_step: Optional[Callable] = None,
    program: Optional[Tuple[Callable, Any]] = None,
    err=sys.stderr,
) -> Tuple[Optional[Dict[str, Any]], int]:
    """Run one cell once. Returns (result, exit code); the result is None
    where no result line may be printed. `require_tpu=False` and `scratch`
    are for the CPU rehearsals; `break_step` wraps the step call with a
    fault, for the tests that must see `correct` come out false; `program`
    is the `(get_config, Trainer)` driven, the package's own unless a
    rehearsal brings another."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = manifest.Cell(root, manifest_path, workload)
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        print(
            f"{workload} needs {cell.chips} TPU chip(s); found {len(devices)} x "
            f"{devices[0].platform}. No result.", file=err,
        )
        return None, EXIT_NO_CHIP
    devices = devices[: cell.chips]
    kind = devices[0].device_kind
    # a rehearsal only walks the readers' code: its numbers are never reported
    peak = peaks.peaks_for(kind) if require_tpu else peaks.PEAKS["TPU v5 lite"]

    scratch = scratch or os.path.join(root, ".perf_scratch")
    run_dir = os.path.join(scratch, "runs", workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    # written anew in every run, at the cell's own fixed path: the same
    # set-up work whether or not the seed was seen before
    feed_ref = load_feed_reference(cell)
    data_dir = os.path.join(run_dir, "data")
    record = feed_ref.make(data_dir, seed, cell.mix)

    get_config, Trainer = program or package_program()
    cfg = program_config(cell, seed, feed_ref.overrides(data_dir), os.path.join(root, ".compile_cache"), get_config)
    batch = cfg.train.batch_size
    telemetry = os.path.join(run_dir, "telemetry") if trace else None
    trace_dir = os.path.join(run_dir, "profile") if trace else None
    trainer = Trainer(
        cfg, workdir=os.path.join(run_dir, "workdir"), devices=devices, telemetry_dir=telemetry
    )
    ref = load_reference(cell)
    sz = ref.Sizes(cell.config["sizes"], batch)
    inject_weights(trainer, ref, sz, seed)
    feed = traffic.Feed(trainer, cell.mix, keep=CHECK_STEPS)

    def step_call(kw):
        return trainer.train_one_batch(**kw)

    if break_step is not None:
        step_call = break_step(trainer, step_call)
    try:
        first = first_steps(trainer, feed, step_call, ref.LOSS_PARTS)
        jax.block_until_ready(trainer.state)
        setup_s = time.perf_counter() - t_start
        win = window(trainer, feed, step_call, seconds, batch, trace_dir)
    finally:
        feed.close()
    del step_call
    strict = trainer.strict.report() if trainer.strict is not None else {"programs": {}}
    recompiles = sum(p["recompiles_after_warmup"] for p in strict["programs"].values())
    stats = max(((d.memory_stats() or {}) for d in devices), key=memory_held)
    mem_peak = memory_held(stats)
    host_batches = feed.first_host_batches
    if trace:
        exe = step_executable(trainer, host_batches[0])
        trainer.flush_telemetry()
        with open(os.path.join(run_dir, "step_hlo.txt"), "w") as f:
            f.write(exe["hlo_text"])
        with open(os.path.join(run_dir, stagecut.SCOPE_FILE), "w") as f:
            f.write(ref.SCOPE_PREFIX)
    # free the program's state before the reference takes the chip
    feed.free()
    trainer.state = None
    del trainer, feed
    import gc

    gc.collect()

    t_ref = time.perf_counter()
    reference = reference_numbers(ref, sz, seed, host_batches)
    nums = compare.numbers(first, reference)
    nums.update(feed_ref.numbers(data_dir, host_batches, cell.config["sizes"]))
    ref_s = time.perf_counter() - t_ref
    correct = compare.judge(nums, cell.config["limits"])
    sound = recompiles == 0 and win["bad"] == 0 and not any(first["skipped"])
    correct = bool(correct and sound)

    values: Dict[str, float] = {}
    if not trace:
        # a cell's end-to-end metrics are `setup_s` and its rate in samples
        # (what its data module batches), under the name the manifest gives
        # the rate in this cell
        for metric in cell.end_to_end:
            is_setup = metric["name"] == "setup_s"
            values[metric["name"]] = setup_s if is_setup else win["images"] / win["seconds"]
    device = {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": int(mem_peak),
    }
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": win["steps"] + WARM_STEPS,
        "failed": win["bad"] + int(sum(1 for s in first["skipped"] if s)),
    }
    if trace:
        reduced = xtrace.reduce(
            xtrace.load_xplane(xtrace.find_xplane(trace_dir), rehearsal=not require_tpu),
            win["profile_window_s"], exe["conv_ops"], exe["origin"],
        )
        with open(os.path.join(telemetry, "trace.json")) as f:
            spans = json.load(f)["traceEvents"]
        ctx = {
            "cell": cell.name, "chips": cell.chips, "batch": batch, "sizes": cell.config["sizes"],
            "mix": cell.mix, "peaks": peak, "window": win, "trace": reduced, "spans": spans,
            "memory_peak_bytes": int(mem_peak), "flops": ref,
        }
        for metric in cell.per_layer:
            value = load_file(cell.reader_path(metric["name"])).read(ctx)
            if value is not None:
                values[metric["name"]] = float(value)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["trace_window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
        }
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    result["device"] = device
    result["notes"] = {
        "steps": win["steps"], "window_s": win["seconds"], "recompiles": recompiles,
        "reference_s": ref_s, "memory_stats": {k: int(v) for k, v in stats.items()},
        **feed_ref.notes(record),
        "losses_program": first["losses"], "losses_reference": reference["losses"],
    }
    compared = {k: {"value": v["value"], "limit": v["limit"]} for k, v in nums.items()}
    compared["recompiles"] = {"value": recompiles, "limit": 0}
    compared["bad_steps"] = {"value": result["failed"], "limit": 0}
    result["compared"] = compared  # last, as the contract asks
    for name, entry in compared.items():
        where = f" at {nums[name]['at']}" if name in nums and nums[name].get("at") else ""
        print(f"compared {name} = {entry['value']!r} limit {entry['limit']!r}{where}", file=err)
    return result, 0
