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


# Two tests of tests/perf_yardstick/ each hold ONE statement that is true only
# while BENCHMARK.json holds the detector's cell alone. PR 31 adds a second cell
# as entries at the end of the lists and may edit no file under `perf/` or
# `tests/perf_yardstick/` that is there (they are the benchmark's); the
# `benchmark` PR that may, updates the two tests and takes them off this list
# (PERF.md section 7). Until then each is expected to fail AT THAT STATEMENT and
# nowhere else: another failure is a failure, and a pass is one too (strict), so
# the entry cannot outlive its reason. What the two tests say beside the pinned
# statement is said again in tests/perf_yardstick/test_cells_of_record.py.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PINNED_TO_ONE_CELL = {
    # (file, test) -> (its parameters, the statement, what the failing frame's locals hold, the error it raises)
    ("test_stage_metrics.py", "test_the_stages_manifest_is_sound_and_adds_only_the_ten"): (
        {}, 'assert [p["name"] for p in record["per_layer"]][-10:] == list(NEW_METRICS)', {}, AssertionError,
    ),
    ("test_perf_benchmark.py", "test_manifests_are_sound_and_their_files_exist"): (
        {"path": os.path.join(_ROOT, "BENCHMARK.json")},
        'assert set(cell.config["limits"]) >= limits', {"name": "trinity_ep8.packed8k"}, AssertionError,
    ),
    # PR 33: `test_cells_of_record.py::LIMITS` is keyed by reference module and knows two; the third
    # cell's case stops where it looks its module up. What the case says beside that is said for the
    # third cell in tests/perf_yardstick/hybrid/test_hybrid_cell.py, the limit names read from the
    # reference module itself (PERF.md section 7, "benchmark files", item 6)
    ("test_cells_of_record.py", "test_a_cell_of_record_has_its_files_its_limits_and_the_benchmarks_own_modules"): (
        {"name": "qwen3next_ep16.packed16k"},
        'assert set(cell.config["limits"]) >= LIMITS[cell.config["reference"]]', {"name": "qwen3next_ep16.packed16k"}, KeyError,
    ),
}


def _failed_at(error, statement, local_values):
    """Whether `error` was raised by `statement`, with those values among the
    frame's locals."""
    import linecache

    tb = error.__traceback__
    while tb.tb_next is not None:
        tb = tb.tb_next
    frame = tb.tb_frame
    line = linecache.getline(frame.f_code.co_filename, tb.tb_lineno).strip()
    return line == statement and all(frame.f_locals.get(k) == v for k, v in local_values.items())


def _pinned(item):
    entry = _PINNED_TO_ONE_CELL.get((os.path.basename(str(item.path)), item.originalname))
    if entry is None or entry[0] != (item.callspec.params if hasattr(item, "callspec") else {}):
        return None
    return entry[1:]


import pytest  # noqa: E402


@pytest.hookimpl(wrapper=True)
def pytest_pyfunc_call(pyfuncitem):
    entry = _pinned(pyfuncitem)
    if entry is None:
        return (yield)
    statement, local_values, expected = entry
    try:
        yield
    except expected as error:
        if _failed_at(error, statement, local_values):
            pytest.xfail("pins BENCHMARK.json to one cell; a benchmark PR's to update (PERF.md section 7)")
        raise
    pytest.fail(f"`{statement}` holds again: take this test off tests/conftest.py::_PINNED_TO_ONE_CELL")


@pytest.fixture(autouse=True, scope="module")
def _no_tracer_across_modules():
    """A `Trainer` with a telemetry directory installs its tracer process-wide
    and keeps it installed; each test module starts from no tracer."""
    from replication_faster_rcnn_tpu.telemetry import spans

    yield
    spans.set_tracer(None)
