"""Declarative compile plans — ONE dispatch layer for every jitted program.

Before this module the jit wrapping of each program was hand-threaded at
its call site: the Trainer picked donation/out_shardings per feed, the
shard_map backend wrapped its own body, the warmup registry duplicated
both, and the serving engine jitted bare. A :class:`Plan` captures that
choice declaratively — mesh, shard_map in/out specs OR jit out-shardings,
donation, per-module parameter PartitionSpecs, warmup policy, the
strict-mode dispatch label — and :func:`compile_step_with_plan` is the
single place that turns (step_fn, plan) into the jitted callable:

  * ``in_specs``/``out_specs`` present  -> ``jax.jit(shard_map(fn, ...))``
    (the explicit-collective backend, `parallel/spmd.py`);
  * ``out_shardings`` present           -> ``jax.jit`` with donation +
    out-shardings (jit auto-partitioning, GSPMD inserts collectives);
  * neither                             -> plain ``jax.jit`` (inference:
    eval sweep, serving buckets).

The wrappings are byte-identical to the pre-Plan call sites — the
committed HLO fingerprints (`analysis/fingerprints/ci_cpu.json`) pin
that.

:meth:`Plan.validate` is the companion DECISION TABLE: every
feed × backend × optimizer compatibility rule that used to live scattered
across `Trainer.__init__` and `parallel/mesh.py`, one cell per rule, each
cell unit-testable in isolation (tests/test_plan.py).

This module deliberately imports nothing from the config layer, so the
config module stays jax-free (the elastic supervisor and `frcnn audit`
rely on configuring XLA_FLAGS before jax loads) — and it imports jax
lazily, so the decision table and the sharding-intent declarations below
are readable by the jax-free static gates (`frcnn check` runs shardlint
over the fingerprint bank without initializing a backend).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, Optional, Tuple


# ------------------------------------------------ declarative sharding intent
#
# What each train/serve feed DECLARES about the state tree's placement —
# the single source shardlint (analysis/shardlint.py) audits the banked
# program fingerprints against, and the prose the Plan docstrings tell.
# Axes name the mesh axes a role's leaves shard over when a divisible dim
# exists (`parallel/zero.py::shard_dim` / `compose_spec`); an empty tuple
# means the role is replicated by design on that feed.

# feeds whose optimizer state is ZeRO-1 sharded (train.shard_opt_state)
ZERO_INTENT_FEEDS: Tuple[str, ...] = ("zero", "zero_lamb", "mp_zero")
# feeds that shard parameters over the model axis (mesh.param_sharding)
MP_INTENT_FEEDS: Tuple[str, ...] = ("mp", "mp_zero")

FEED_STATE_INTENT: Dict[str, Dict[str, Tuple[str, ...]]] = {
    "loader": {"params": (), "opt_state": ()},
    "cached": {"params": (), "opt_state": ()},
    "spmd": {"params": (), "opt_state": ()},
    "zero": {"params": (), "opt_state": ("data",)},
    "zero_lamb": {"params": (), "opt_state": ("data",)},
    "mp": {"params": ("model",), "opt_state": ()},
    "mp_zero": {"params": ("model",), "opt_state": ("model", "data")},
    "eval": {"params": (), "opt_state": ()},
    # serving under an mp mesh routes params through zero.param_shardings
    # (train/warmup.py::build_serving_specs); on a 1-device/dp-only
    # serving mesh the engine keeps them replicated
    "serve": {"params": ("model",), "opt_state": ()},
}


@dataclasses.dataclass(frozen=True)
class Plan:
    """How one program compiles: the mesh it runs on, the partitioning
    mode (shard_map specs, jit out-shardings, or neither), donation, and
    the metadata its consumers read (per-module param specs for the
    model-parallel axis, the strict-mode dispatch label, whether AOT
    warmup should pre-compile it).

    Exactly one partitioning mode may be populated:
    ``in_specs``/``out_specs`` (shard_map) or ``out_shardings`` (jit
    auto-partitioning); with neither the program jits plain (single-
    device inference). ``param_specs`` is documentation-grade truth for
    the (dp, mp) layout — the pytree of `PartitionSpec`s the state
    placement used — not an input to compilation (the shardings ride the
    abstract inputs / out_shardings)."""

    mesh: Any = None
    # explicit shard_map mode (both or neither)
    in_specs: Any = None
    out_specs: Any = None
    # jit auto-partitioning mode
    out_shardings: Any = None
    donate_argnums: Tuple[int, ...] = ()
    # metadata
    param_specs: Any = None
    label: Optional[str] = None
    warmup: bool = True

    @property
    def mode(self) -> str:
        """"shard_map" | "pjit" | "jit" — what compile_step_with_plan does."""
        if self.in_specs is not None or self.out_specs is not None:
            return "shard_map"
        if self.out_shardings is not None:
            return "pjit"
        return "jit"

    @classmethod
    def validate(
        cls,
        config,
        n_devices: Optional[int] = None,
        process_count: Optional[int] = None,
    ) -> None:
        """Run the full decision table against a FasterRCNNConfig, raising
        ValueError on the first failing cell (and warning on warn-severity
        cells). The one entry point behind `parallel.validate_parallel`
        and `Trainer.__init__`."""
        ctx = PlanContext.from_config(
            config, n_devices=n_devices, process_count=process_count
        )
        apply_table(ctx)


def compile_step_with_plan(step_fn: Callable, plan: Plan):
    """(step_fn, plan) -> the jitted callable, via the plan's mode.

    The three wrappings reproduce the historical call sites byte-for-byte
    (fingerprint-pinned): shard_map plans wrap the per-shard body first;
    pjit plans jit with donation + out_shardings; bare plans jit plain.
    Empty donation / absent out_shardings are NOT passed through, so a
    bare plan lowers the identical program a bare ``jax.jit`` did."""
    import jax

    if plan.mode == "shard_map":
        if plan.mesh is None:
            raise ValueError("a shard_map plan needs a mesh")
        if plan.in_specs is None or plan.out_specs is None:
            raise ValueError(
                "a shard_map plan needs both in_specs and out_specs"
            )
        step_fn = jax.shard_map(
            step_fn,
            mesh=plan.mesh,
            in_specs=plan.in_specs,
            out_specs=plan.out_specs,
            check_vma=False,
        )

    kwargs = {}
    if plan.donate_argnums:
        kwargs["donate_argnums"] = plan.donate_argnums
    if plan.mode == "pjit":
        kwargs["out_shardings"] = plan.out_shardings
    return jax.jit(step_fn, **kwargs)


# --------------------------------------------------------- decision table


@dataclasses.dataclass(frozen=True)
class PlanContext:
    """The flattened inputs the compatibility table reads — a plain value
    object so every cell is testable without building a full config or
    initializing jax."""

    backend: str = "auto"
    optimizer: str = "adam"
    lars: bool = False
    shard_opt_state: bool = False
    cache_device: bool = False
    spatial: bool = False
    param_sharding: bool = False
    num_data: int = -1
    num_model: int = 1
    image_rows: int = 0
    batch_size: int = 0
    n_devices: int = 1
    process_count: int = 1
    train_buckets: int = 0  # len(data.train_resolutions); 0 = off
    # the actual bucket resolutions, for per-resolution cells (empty when
    # multi-scale is off; kept alongside train_buckets so cells that only
    # need the count stay constructible without inventing shapes)
    train_resolutions: Tuple[Tuple[int, int], ...] = ()

    @property
    def n_model(self) -> int:
        return max(1, self.num_model)

    @classmethod
    def from_config(
        cls,
        config,
        n_devices: Optional[int] = None,
        process_count: Optional[int] = None,
    ) -> "PlanContext":
        if n_devices is None or process_count is None:
            import jax

            if n_devices is None:
                n_devices = len(jax.devices())
            if process_count is None:
                process_count = jax.process_count()
        return cls(
            backend=config.train.backend,
            optimizer=config.train.optimizer,
            lars=config.train.lars,
            shard_opt_state=config.train.shard_opt_state,
            cache_device=config.data.cache_device,
            spatial=config.mesh.spatial,
            param_sharding=config.mesh.param_sharding,
            num_data=config.mesh.num_data,
            num_model=config.mesh.num_model,
            image_rows=config.data.image_size[0],
            batch_size=config.train.batch_size,
            n_devices=n_devices,
            process_count=process_count,
            train_buckets=len(config.data.train_resolutions),
            train_resolutions=tuple(
                tuple(r) for r in config.data.train_resolutions
            ),
        )


@dataclasses.dataclass(frozen=True)
class Cell:
    """One row of the table: a named predicate over PlanContext plus the
    uniform error (or warning) it produces when it fires."""

    name: str
    severity: str  # "error" | "warn"
    applies: Callable[[PlanContext], bool]
    message: Callable[[PlanContext], str]


# Ordered: earlier cells win when several fire (the order the scattered
# checks historically ran in: spatial, optimizer, multiprocess, mesh fit,
# model parallelism, device-cache feed). Messages are pinned by tests —
# change them only with their tests.
DECISION_TABLE: Tuple[Cell, ...] = (
    Cell(
        "model_axis_unused",
        "warn",
        lambda c: (
            not c.spatial and not c.param_sharding and c.num_model > 1
        ),
        lambda c: (
            f"mesh.num_model={c.num_model} with spatial=False: the model "
            f"axis carries no sharding, so {c.num_model - 1} of every "
            f"{c.num_model} chips duplicate work; pass --spatial or drop "
            "--num-model"
        ),
    ),
    Cell(
        "spatial_backend",
        "error",
        lambda c: c.spatial and c.backend == "spmd",
        lambda c: (
            "spatial partitioning requires the jit auto-partitioning "
            "backend (GSPMD places the conv halo exchanges); the "
            "explicit shard_map backend shards batch dims only"
        ),
    ),
    Cell(
        "spatial_num_model",
        "error",
        lambda c: c.spatial and c.num_model < 2,
        lambda c: (
            "spatial partitioning shards image rows over the model "
            "axis; set mesh.num_model >= 2 (--num-model), got "
            f"{c.num_model}"
        ),
    ),
    Cell(
        "spatial_rows",
        "error",
        lambda c: (
            c.spatial and c.num_model >= 2 and c.image_rows % c.num_model != 0
        ),
        lambda c: (
            "spatial partitioning needs image rows "
            f"({c.image_rows}) divisible by the model "
            f"axis ({c.num_model})"
        ),
    ),
    Cell(
        "lamb_lars",
        "error",
        lambda c: c.optimizer == "lamb" and c.lars,
        lambda c: (
            "optimizer='lamb' already applies the per-layer trust "
            "ratio after Adam; combining it with lars=True would "
            "rescale twice — drop one"
        ),
    ),
    Cell(
        "lars_sharded_spmd",
        "error",
        lambda c: c.shard_opt_state and c.backend == "spmd" and c.lars,
        lambda c: (
            "lars trust ratios need full-leaf norms, but the shard_map "
            "ZeRO-1 backend updates 1/N parameter slices (partial norms); "
            "use the jit auto-partitioning backend (backend='auto') for "
            "lars + shard_opt_state"
        ),
    ),
    Cell(
        "spatial_multiprocess",
        "error",
        lambda c: c.process_count > 1 and c.spatial,
        lambda c: (
            "spatial partitioning is single-process only: the "
            "per-process feed ships batch rows, not image-row shards"
        ),
    ),
    Cell(
        "multiprocess_batch",
        "error",
        lambda c: c.process_count > 1 and c.batch_size % c.process_count != 0,
        lambda c: (
            f"global batch_size={c.batch_size} must divide "
            f"evenly over {c.process_count} processes (each feeds "
            "its own contiguous rows of the global batch)"
        ),
    ),
    Cell(
        "mesh_fit",
        "error",
        lambda c: c.num_data > 0 and c.num_data * c.n_model > c.n_devices,
        lambda c: (
            f"mesh {c.num_data}x{c.n_model} needs "
            f"{c.num_data * c.n_model} "
            f"device(s) but only {c.n_devices} are available"
        ),
    ),
    Cell(
        "model_axis_width",
        "error",
        lambda c: c.num_data <= 0 and c.n_model > c.n_devices,
        lambda c: (
            f"num_model={c.n_model} exceeds the {c.n_devices} available "
            "device(s); the model axis cannot be wider than the mesh"
        ),
    ),
    Cell(
        "model_axis_divide",
        "error",
        lambda c: c.num_data <= 0 and c.n_devices % c.n_model != 0,
        lambda c: (
            f"{c.n_devices} device(s) cannot be split evenly into model "
            f"groups of {c.n_model}; pick num_model dividing {c.n_devices}"
        ),
    ),
    Cell(
        "mp_backend",
        "error",
        lambda c: c.param_sharding and c.backend == "spmd",
        lambda c: (
            "model-parallel parameter sharding (mesh.param_sharding / "
            "--mesh-shape) requires the jit auto-partitioning backend "
            "(GSPMD places the weight all-gathers); the explicit "
            "shard_map backend shards batch dims only"
        ),
    ),
    Cell(
        "mp_spatial",
        "error",
        lambda c: c.param_sharding and c.spatial,
        lambda c: (
            "param_sharding and spatial both claim the model axis; "
            "pick ONE sharding story per mesh axis (--mesh-shape for "
            "weights, --spatial for image rows)"
        ),
    ),
    Cell(
        "mp_cache",
        "error",
        lambda c: c.param_sharding and c.cache_device,
        lambda c: (
            "cache_device pairs with replicated parameters; the "
            "model-parallel feed (--mesh-shape with MP > 1) uses the "
            "host loader — drop --cache-device or --mesh-shape"
        ),
    ),
    Cell(
        "cache_backend",
        "error",
        lambda c: c.cache_device and c.backend == "spmd",
        lambda c: (
            "cache_device currently pairs with the jit auto-"
            "partitioned backend only (train.backend='auto'); the "
            "explicit shard_map backend feeds host batches"
        ),
    ),
    # Bucketed multi-scale composes with every backend: the shard_map
    # in/out specs shard batch dims only, so they are resolution-
    # independent, and each bucket compiles its own program with the
    # resample traced into the body (train/warmup.py bucket builders).
    # The only genuine constraint is spatial row divisibility, checked
    # PER RESOLUTION below — a bucket set is rejected only when a named
    # resolution actually violates it.
    Cell(
        "buckets_spatial_rows",
        "error",
        lambda c: (
            c.train_buckets > 0
            and c.spatial
            and c.num_model >= 2
            and any(r[0] % c.num_model != 0 for r in c.train_resolutions)
        ),
        lambda c: (
            "spatial partitioning needs every bucket's image rows "
            f"divisible by the model axis ({c.num_model}); offending "
            "data.train_resolutions: "
            + ", ".join(
                f"{r[0]}x{r[1]} ({r[0]} rows)"
                for r in c.train_resolutions
                if r[0] % c.num_model != 0
            )
        ),
    ),
    Cell(
        "cache_multiprocess",
        "error",
        lambda c: c.cache_device and c.process_count > 1,
        lambda c: (
            "cache_device requires a single-process runtime: "
            "DeviceCache device_puts the full dataset from this "
            "host to a replicated sharding, which one process "
            "cannot place across a multi-host mesh. Drop "
            "--cache-device (use the host loader, optionally with "
            "device_normalize) on multi-host runs."
        ),
    ),
)


def check_cells(ctx: PlanContext, names: Optional[Tuple[str, ...]] = None):
    """Every firing cell (optionally restricted to ``names``), in table
    order, as (cell, message) pairs. Pure — no raising, no warning."""
    out = []
    for cell in DECISION_TABLE:
        if names is not None and cell.name not in names:
            continue
        if cell.applies(ctx):
            out.append((cell, cell.message(ctx)))
    return out


def apply_table(
    ctx: PlanContext, names: Optional[Tuple[str, ...]] = None
) -> None:
    """Evaluate the table: warn on warn-severity cells, raise ValueError
    on the first error cell (table order)."""
    for cell, message in check_cells(ctx, names):
        if cell.severity == "warn":
            warnings.warn(message, stacklevel=3)
        else:
            raise ValueError(message)


SPATIAL_CELLS: Tuple[str, ...] = (
    "model_axis_unused",
    "spatial_backend",
    "spatial_num_model",
    "spatial_rows",
    "buckets_spatial_rows",
)
