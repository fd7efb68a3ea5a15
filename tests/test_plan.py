"""The Plan dispatch layer (`parallel/plan.py`): the jit/pjit/shard_map
mode decision, byte-identical wrappings vs the hand-threaded call sites
they replaced (the committed fingerprints pin the real programs; here a
toy program pins the mechanism), and the feed×backend×optimizer decision
table — every cell unit-tested in isolation on a plain PlanContext.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from replication_faster_rcnn_tpu.parallel import plan as plan_mod
from replication_faster_rcnn_tpu.parallel.plan import (
    DECISION_TABLE,
    Plan,
    PlanContext,
    SPATIAL_CELLS,
    apply_table,
    check_cells,
    compile_step_with_plan,
)


def _mesh(dp=2, mp=1):
    devs = np.asarray(jax.devices()[: dp * mp]).reshape(dp, mp)
    return Mesh(devs, ("data", "model"))


# ------------------------------------------------------------------ the modes


class TestPlanModes:
    def test_bare_plan_is_jit(self):
        assert Plan().mode == "jit"

    def test_out_shardings_is_pjit(self):
        assert Plan(out_shardings=(None, None)).mode == "pjit"

    def test_in_out_specs_is_shard_map(self):
        assert Plan(in_specs=(P(),), out_specs=P()).mode == "shard_map"

    def test_bare_plan_lowers_identically_to_bare_jit(self):
        fn = lambda x: x * 2.0 + 1.0  # noqa: E731
        ours = compile_step_with_plan(fn, Plan()).lower(1.0).as_text()
        theirs = jax.jit(fn).lower(1.0).as_text()
        assert ours == theirs

    def test_pjit_plan_lowers_identically_to_hand_jit(self):
        mesh = _mesh()
        s = NamedSharding(mesh, P("data"))
        fn = lambda x: x + 1.0  # noqa: E731
        x = jnp.zeros((4,), jnp.float32)
        p = Plan(mesh=mesh, donate_argnums=(0,), out_shardings=s)
        ours = compile_step_with_plan(fn, p).lower(x).as_text()
        theirs = (
            jax.jit(fn, donate_argnums=(0,), out_shardings=s).lower(x).as_text()
        )
        assert ours == theirs

    def test_shard_map_plan_lowers_identically_to_hand_wrap(self):
        mesh = _mesh()
        fn = lambda x: x + 1.0  # noqa: E731
        x = jnp.zeros((4,), jnp.float32)
        p = Plan(
            mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
            donate_argnums=(0,),
        )
        ours = compile_step_with_plan(fn, p).lower(x).as_text()
        hand = jax.shard_map(
            fn, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
            check_vma=False,
        )
        theirs = jax.jit(hand, donate_argnums=(0,)).lower(x).as_text()
        assert ours == theirs

    def test_shard_map_plan_without_mesh_raises(self):
        p = Plan(in_specs=(P(),), out_specs=P())
        with pytest.raises(ValueError, match="mesh"):
            compile_step_with_plan(lambda x: x, p)

    def test_shard_map_plan_with_one_spec_raises(self):
        p = Plan(mesh=_mesh(), in_specs=(P(),))
        with pytest.raises(ValueError, match="both in_specs and out_specs"):
            compile_step_with_plan(lambda x: x, p)

    def test_donation_survives_compile(self):
        mesh = _mesh()
        s = NamedSharding(mesh, P())
        p = Plan(mesh=mesh, donate_argnums=(0,), out_shardings=s)
        x = jnp.zeros((8,), jnp.float32)
        text = (
            compile_step_with_plan(lambda v: v * 2.0, p)
            .lower(x)
            .compile()
            .as_text()
        )
        assert "input_output_alias" in text


# ------------------------------------------------------------ decision table


def _ctx(**over):
    """A context every cell is silent on."""
    base = dict(
        backend="auto", optimizer="adam", lars=False, shard_opt_state=False,
        cache_device=False, spatial=False, param_sharding=False,
        num_data=2, num_model=1, image_rows=64, batch_size=8,
        n_devices=8, process_count=1,
    )
    base.update(over)
    return PlanContext(**base)


def _fired(ctx):
    return [cell.name for cell, _ in check_cells(ctx)]


class TestDecisionTableCells:
    def test_clean_context_fires_nothing(self):
        assert _fired(_ctx()) == []

    def test_model_axis_unused(self):
        ctx = _ctx(num_model=2)
        [(cell, msg)] = check_cells(ctx)
        assert cell.name == "model_axis_unused" and cell.severity == "warn"
        assert "--spatial" in msg
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            apply_table(ctx)  # warn severity: must not raise
        assert any("model axis carries no sharding" in str(w.message) for w in rec)

    def test_spatial_backend(self):
        ctx = _ctx(spatial=True, num_model=2, backend="spmd")
        assert "spatial_backend" in _fired(ctx)
        with pytest.raises(ValueError, match="spatial"):
            apply_table(ctx)

    def test_spatial_num_model(self):
        ctx = _ctx(spatial=True, num_model=1)
        assert _fired(ctx) == ["spatial_num_model"]
        with pytest.raises(ValueError, match="num_model"):
            apply_table(ctx)

    def test_spatial_rows(self):
        ctx = _ctx(spatial=True, num_model=2, image_rows=63)
        assert _fired(ctx) == ["spatial_rows"]
        with pytest.raises(ValueError, match="divisible"):
            apply_table(ctx)

    def test_lamb_lars(self):
        ctx = _ctx(optimizer="lamb", lars=True)
        assert _fired(ctx) == ["lamb_lars"]
        with pytest.raises(ValueError, match="lars"):
            apply_table(ctx)

    def test_lars_sharded_spmd(self):
        ctx = _ctx(lars=True, shard_opt_state=True, backend="spmd")
        assert _fired(ctx) == ["lars_sharded_spmd"]
        with pytest.raises(ValueError, match="lars"):
            apply_table(ctx)

    def test_spatial_multiprocess(self):
        ctx = _ctx(spatial=True, num_model=2, process_count=2, batch_size=8)
        assert "spatial_multiprocess" in _fired(ctx)

    def test_multiprocess_batch(self):
        ctx = _ctx(process_count=3, batch_size=8)
        assert _fired(ctx) == ["multiprocess_batch"]
        with pytest.raises(ValueError, match="evenly"):
            apply_table(ctx)

    def test_mesh_fit(self):
        ctx = _ctx(num_data=8, num_model=2)
        fired = _fired(ctx)
        assert "mesh_fit" in fired
        with pytest.raises(ValueError, match="needs 16"):
            apply_table(ctx)

    def test_model_axis_width(self):
        ctx = _ctx(num_data=0, num_model=16, spatial=True)
        assert "model_axis_width" in _fired(ctx)
        with pytest.raises(ValueError, match="exceeds the 8 available"):
            apply_table(ctx)

    def test_model_axis_divide(self):
        ctx = _ctx(num_data=0, num_model=3, spatial=True, image_rows=63)
        assert "model_axis_divide" in _fired(ctx)
        with pytest.raises(ValueError, match="split evenly"):
            apply_table(ctx)

    def test_mp_backend(self):
        ctx = _ctx(param_sharding=True, num_model=4, backend="spmd")
        assert _fired(ctx) == ["mp_backend"]
        with pytest.raises(ValueError, match="param_sharding"):
            apply_table(ctx)

    def test_mp_spatial(self):
        ctx = _ctx(param_sharding=True, spatial=True, num_model=2)
        assert _fired(ctx) == ["mp_spatial"]
        with pytest.raises(ValueError, match="ONE sharding story"):
            apply_table(ctx)

    def test_mp_cache(self):
        ctx = _ctx(param_sharding=True, num_model=4, cache_device=True)
        assert _fired(ctx) == ["mp_cache"]
        with pytest.raises(ValueError, match="mesh-shape"):
            apply_table(ctx)

    def test_cache_backend(self):
        ctx = _ctx(cache_device=True, backend="spmd")
        assert _fired(ctx) == ["cache_backend"]
        with pytest.raises(ValueError, match="cache_device currently pairs"):
            apply_table(ctx)

    def test_cache_multiprocess(self):
        ctx = _ctx(cache_device=True, process_count=2, batch_size=8)
        assert _fired(ctx) == ["cache_multiprocess"]
        with pytest.raises(ValueError, match="single-process"):
            apply_table(ctx)

    def test_table_order_is_precedence(self):
        # several cells fire; apply_table must raise the EARLIEST error
        ctx = _ctx(
            spatial=True, num_model=1, optimizer="lamb", lars=True,
            cache_device=True, backend="spmd",
        )
        fired = _fired(ctx)
        assert fired[0] == "spatial_backend"
        with pytest.raises(ValueError, match="spatial"):
            apply_table(ctx)

    def test_buckets_spmd_composes(self):
        # the old buckets_backend blanket rejection is gone: the shard_map
        # specs shard batch dims only, so buckets compile per-resolution
        ctx = _ctx(
            train_buckets=2,
            train_resolutions=((32, 32), (64, 64)),
            backend="spmd",
        )
        assert _fired(ctx) == []
        apply_table(ctx)  # must not raise

    def test_buckets_spatial_rows(self):
        # per-resolution check: only the indivisible bucket is named
        ctx = _ctx(
            train_buckets=2,
            train_resolutions=((30, 30), (64, 64)),
            spatial=True,
            num_model=4,
        )
        [(cell, msg)] = check_cells(ctx)
        assert cell.name == "buckets_spatial_rows"
        assert "30x30" in msg and "64x64" not in msg
        with pytest.raises(ValueError, match="30x30"):
            apply_table(ctx)

    def test_buckets_spatial_divisible_composes(self):
        # every bucket's rows divide the model axis -> spatial + buckets
        # is legal (the old buckets_spatial blanket rejection is gone)
        ctx = _ctx(
            train_buckets=2,
            train_resolutions=((32, 32), (64, 64)),
            spatial=True,
            num_model=2,
        )
        assert _fired(ctx) == []
        apply_table(ctx)  # must not raise

    def test_buckets_mp_zero_composes(self):
        # bucket x model-parallel mesh x ZeRO-1: no cell fires
        ctx = _ctx(
            train_buckets=2,
            train_resolutions=((32, 32), (64, 64)),
            param_sharding=True,
            num_model=4,
            num_data=2,
            shard_opt_state=True,
        )
        assert _fired(ctx) == []
        apply_table(ctx)  # must not raise

    def test_names_filter_restricts_cells(self):
        ctx = _ctx(optimizer="lamb", lars=True, spatial=True, num_model=1)
        only = check_cells(ctx, names=SPATIAL_CELLS)
        assert [c.name for c, _ in only] == ["spatial_num_model"]

    def test_every_cell_has_a_test(self):
        tested = {
            name[len("test_"):]
            for name in dir(self)
            if name.startswith("test_")
        }
        for cell in DECISION_TABLE:
            assert cell.name in tested, f"decision cell {cell.name} untested"


# ------------------------------------------------------- config entry point


class TestPlanValidate:
    def _cfg(self, **mesh_over):
        from replication_faster_rcnn_tpu.config import get_config

        cfg = get_config("voc_resnet18")
        if mesh_over:
            cfg = cfg.replace(
                mesh=dataclasses.replace(cfg.mesh, **mesh_over)
            )
        return cfg

    def test_default_config_validates(self):
        Plan.validate(self._cfg(), n_devices=8, process_count=1)

    def test_mesh_shape_2x4_validates(self):
        Plan.validate(
            self._cfg(num_data=2, num_model=4, param_sharding=True),
            n_devices=8,
            process_count=1,
        )

    def test_oversubscribed_mesh_raises(self):
        with pytest.raises(ValueError, match="needs 16"):
            Plan.validate(
                self._cfg(num_data=4, num_model=4, param_sharding=True),
                n_devices=8,
                process_count=1,
            )

    def test_from_config_reads_the_mesh_axes(self):
        ctx = PlanContext.from_config(
            self._cfg(num_data=2, num_model=4, param_sharding=True),
            n_devices=8,
            process_count=1,
        )
        assert (ctx.num_data, ctx.num_model, ctx.param_sharding) == (2, 4, True)
        assert ctx.n_model == 4
