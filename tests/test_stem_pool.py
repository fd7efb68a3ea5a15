"""The ResNet stem's norm + ReLU + 3x3/2 max-pool as one function
(`ops/pool_ops.py::norm_relu_max_pool`, `models/resnet.py::_stem_pool`)
against the old writing, `nn.max_pool(nn.relu(norm(y)))` (`tests/oracles.py`).

CPU, the kernels in interpret mode. Comparisons "to the bit" run op by op
(no enclosing `jax.jit`): under one jit XLA may keep excess precision
through a bfloat16 rounding on one side and not the other.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replication_faster_rcnn_tpu.models import fpn, resnet
from replication_faster_rcnn_tpu.ops import pool_ops
from tests import oracles

EXTENTS = [(300, 300), (75, 75), (7, 7), (8, 8), (6, 9)]


def _terms(rng, n, c, per_sample, unit=False):
    """mean, mul, bias as a norm layer hands them over: float32,
    `[1|N, 1, 1, C]`; `unit` gives the identity affine. `mul` is a power of
    two of either sign: its product is exact, so whether a compiler fuses the
    multiply and the add (XLA:CPU does inside a jitted loop, as the
    interpreted kernel is, and not op by op) cannot show."""
    shape = (n if per_sample else 1, 1, 1, c)
    if unit:
        return jnp.zeros(shape), jnp.ones(shape), jnp.zeros(shape)
    mul = rng.choice([-2.0, -1.0, -0.5, 0.5, 1.0, 2.0], shape)
    return tuple(jnp.asarray(t, jnp.float32) for t in (rng.normal(size=shape), mul, rng.normal(size=shape)))


def _bits(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("extent", EXTENTS, ids=lambda e: f"{e[0]}x{e[1]}")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("differentiated", [False, True], ids=["plain", "differentiated"])
def test_forward_equals_the_oracle_to_the_bit(extent, dtype, differentiated):
    rng = np.random.default_rng(sum(extent))
    n, c = (1, 4) if extent[0] > 100 else (3, 8)
    y = jnp.asarray(rng.normal(size=(n,) + extent + (c,)), dtype)
    terms = _terms(rng, n, c, per_sample=extent[0] % 2 == 1)
    want = oracles.norm_relu_max_pool_oracle(y, *terms, dtype)
    if differentiated:
        got, _ = jax.vjp(lambda y: pool_ops.norm_relu_max_pool(y, *terms, dtype), y)
    else:
        got = pool_ops.norm_relu_max_pool(y, *terms, dtype)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _grads(fn, y, terms, ct):
    loss = lambda y, *t: jnp.sum(fn(y, *t).astype(jnp.float32) * ct)
    return jax.grad(loss, argnums=(0, 1, 2, 3))(y, *terms)


@pytest.mark.parametrize("extent", [(75, 75), (7, 7), (8, 8), (6, 9)], ids=lambda e: f"{e[0]}x{e[1]}")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_gradient_on_quantised_inputs_equals_the_oracles_to_the_bit(extent, dtype):
    """A few integer values: ties in every window, and sums that are exact
    in either dtype, so the order of additions cannot show."""
    rng = np.random.default_rng(7 + sum(extent))
    n, c = 2, 8
    y = jnp.asarray(rng.integers(-2, 3, size=(n,) + extent + (c,)), dtype)
    ct = jnp.asarray(rng.integers(-3, 4, size=(n, -(-extent[0] // 2), -(-extent[1] // 2), c)), jnp.float32)
    terms = _terms(rng, n, c, per_sample=False, unit=True)
    got = _grads(lambda *a: pool_ops.norm_relu_max_pool(*a, dtype), y, terms, ct)
    want = _grads(lambda *a: oracles.norm_relu_max_pool_oracle(*a, dtype), y, terms, ct)
    assert np.abs(_bits(want[0])).max() > 0
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("per_sample", [False, True], ids=["batch_terms", "sample_terms"])
@pytest.mark.parametrize("extent", [(75, 75), (8, 8), (6, 9)], ids=lambda e: f"{e[0]}x{e[1]}")
def test_gradient_on_random_inputs_equals_the_oracles_to_float32_rounding(extent, per_sample):
    rng = np.random.default_rng(11 + sum(extent))
    n, c = 3, 8
    y = jnp.asarray(rng.normal(size=(n,) + extent + (c,)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(n, -(-extent[0] // 2), -(-extent[1] // 2), c)), jnp.float32)
    terms = _terms(rng, n, c, per_sample)
    got = _grads(lambda *a: pool_ops.norm_relu_max_pool(*a, jnp.float32), y, terms, ct)
    want = _grads(lambda *a: oracles.norm_relu_max_pool_oracle(*a, jnp.float32), y, terms, ct)
    np.testing.assert_array_equal(_bits(got[0]), _bits(want[0]))  # one product a pixel: no sum to reorder
    for g, w in zip(got[1:], want[1:]):
        assert g.shape == w.shape
        np.testing.assert_allclose(_bits(g), _bits(w), rtol=2e-5, atol=2e-5 * float(np.abs(_bits(w)).max()))


def test_windows_without_a_positive_value_pass_no_gradient():
    """ReLU passes nothing at or below 0: ties at zero take none."""
    rng = np.random.default_rng(3)
    y = -jnp.abs(jnp.asarray(rng.integers(0, 3, size=(2, 9, 8, 4)), jnp.float32))  # zeros among negatives
    terms = _terms(rng, 2, 4, per_sample=False, unit=True)
    got = _grads(lambda *a: pool_ops.norm_relu_max_pool(*a, jnp.float32), y, terms, jnp.ones((2, 5, 4, 4)))
    for g in got:
        assert not np.asarray(g).any()


# ------------------------------------------------------------- the trunks


def _build(module, norm, frozen, dtype):
    if module == "trunk":
        return resnet.ResNetTrunk("resnet18", dtype, norm=norm, frozen_bn=frozen)
    return fpn.ResNetFeatures("resnet18", dtype, norm=norm, frozen_bn=frozen)


def _run(mod, x):
    """Op by op: see the top of the file."""
    variables = jax.jit(mod.init, static_argnums=2)(jax.random.PRNGKey(0), x, False)

    def loss(params, x):
        out, updated = mod.apply({**variables, "params": params}, x, True, mutable=["batch_stats"])
        outs = out if isinstance(out, list) else [out]
        return sum(jnp.sum(jnp.square(o.astype(jnp.float32))) for o in outs), (outs, updated)

    (_, (outs, updated)), grads = jax.value_and_grad(loss, has_aux=True)(variables["params"], x)
    return variables, outs, updated, grads


@pytest.mark.parametrize("module", ["trunk", "features"])
@pytest.mark.parametrize(
    "norm,frozen", [("batch", False), ("group", False), ("batch", True)], ids=["batch", "group", "frozen_bn"]
)
def test_trunks_equal_themselves_with_the_oracle_stem(module, norm, frozen, monkeypatch):
    """Same parameter and `batch_stats` trees and values, same outputs to
    the bit, same updated statistics, same gradients (float32: to rounding,
    the norm's sums run in another order)."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 38, 42, 3)), jnp.float32)
    got = _run(_build(module, norm, frozen, jnp.float32), x)
    for holder in (resnet, fpn):
        monkeypatch.setattr(holder, "_stem_pool", oracles.oracle_stem_pool)
    want = _run(_build(module, norm, frozen, jnp.float32), x)
    for g, w in zip(got[:3], want[:3]):  # variables, outputs, updated statistics
        assert jax.tree.structure(g) == jax.tree.structure(w)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(_bits(a), _bits(b))
    assert jax.tree.structure(got[3]) == jax.tree.structure(want[3])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got[3]), jax.tree.leaves(want[3])):
        scale = float(np.abs(_bits(b)).max()) + 1e-30
        np.testing.assert_allclose(_bits(a), _bits(b), rtol=0, atol=2e-5 * scale, err_msg=str(path))


def test_trunk_outputs_in_bfloat16_equal_the_oracle_stems_to_the_bit(monkeypatch):
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 38, 42, 3)), jnp.float32)
    got = _run(_build("trunk", "batch", False, jnp.bfloat16), x)
    monkeypatch.setattr(resnet, "_stem_pool", oracles.oracle_stem_pool)
    want = _run(_build("trunk", "batch", False, jnp.bfloat16), x)
    for a, b in zip(jax.tree.leaves(got[1:3]), jax.tree.leaves(want[1:3])):
        np.testing.assert_array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("module", ["trunk", "features"])
def test_trunk_gradient_lowers_without_select_and_scatter(module, monkeypatch):
    """The lowered text of the trunk's gradient holds no `select_and_scatter`;
    with the oracle stem it holds one, the pool's backward."""
    mod = _build(module, "batch", False, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 64, 64, 3), jnp.float32)
    variables = jax.eval_shape(lambda x: mod.init(jax.random.PRNGKey(0), x, False), x)

    def loss(variables, x):
        out, _ = mod.apply(variables, x, True, mutable=["batch_stats"])
        return sum(jnp.sum(o.astype(jnp.float32)) for o in (out if isinstance(out, list) else [out]))

    lower = lambda: jax.jit(jax.grad(loss)).lower(variables, x).as_text()
    text = lower()
    assert "select_and_scatter" not in text
    for holder in (resnet, fpn):
        monkeypatch.setattr(holder, "_stem_pool", oracles.oracle_stem_pool)
    assert lower().count("select_and_scatter") == 1


def test_sharded_batch_gives_the_unsharded_result():
    """Under jit's auto-partitioning the kernels split along the batch
    (`pool_ops._by_batch`), whatever else the map is sharded by."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
    rng = np.random.default_rng(5)
    y = jnp.asarray(rng.normal(size=(8, 10, 12, 8)), jnp.float32)
    terms = _terms(rng, 8, 8, per_sample=False)
    ct = jnp.asarray(rng.normal(size=(8, 5, 6, 8)), jnp.float32)
    step = jax.jit(lambda y, *t: _grads(lambda *a: pool_ops.norm_relu_max_pool(*a, jnp.float32), y, t, ct))
    want = step(y, *terms)
    for spec in (P("data"), P(("data", "model")), P("data", "model")):
        sharded = jax.device_put(y, NamedSharding(mesh, spec))
        for g, w in zip(step(sharded, *terms), want):
            np.testing.assert_allclose(_bits(g), _bits(w), rtol=1e-6, atol=1e-6)
    gathers = step.lower(jax.device_put(y, NamedSharding(mesh, P("data"))), *terms).compile().as_text()
    assert "all-gather" not in gathers
