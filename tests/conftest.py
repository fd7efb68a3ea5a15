"""Test env: force JAX onto CPU with 8 virtual devices so multi-chip
sharding paths are exercised without TPU hardware (SURVEY.md §4e).

`jax.config` takes effect at first backend initialization (no test has
touched a backend yet at collection time); XLA_FLAGS is read by the CPU
client at creation, so setting it here works too.

The persistent compilation cache is turned off for the test process and,
through the environment, for every child it spawns: the program would
otherwise keep it in `.compile_cache/` inside the checkout
(`train/warmup.py::place_compile_cache`), and the chip tool copies the
tree as it stands on disk — tier-1 must not fill it with XLA:CPU
executables.

Matmul/conv precision defaults to `highest` for tests: the framework's
bfloat16 compute is a deliberate TPU choice, but golden tests compare
against float64/float32 numpy+torch oracles.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line(
        "markers",
        "pallas_interpret: ops/pallas kernel parity under interpret mode "
        "(tier 1 — runs on CPU without a chip; `-m pallas_interpret` "
        "selects just the kernel gates)",
    )


def pytest_sessionstart(session):
    devs = jax.devices()
    assert devs[0].platform == "cpu", f"tests must run on CPU, got {devs[0]}"
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
