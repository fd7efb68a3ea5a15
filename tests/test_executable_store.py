"""The executable store (`train/warmup.py::keep_compiled`): a program's
compiled executable kept across processes under a key of everything its
lowering reads, loaded on a later start without tracing or lowering."""

import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import struct

from replication_faster_rcnn_tpu.analysis.strict import StrictHarness, StrictViolation
from replication_faster_rcnn_tpu.telemetry import compiles
from replication_faster_rcnn_tpu.telemetry import spans as tspans
from replication_faster_rcnn_tpu.train import warmup


class State(struct.PyTreeNode):
    step: jax.Array
    params: dict
    opt_state: object


TX = optax.adam(1e-2)


def _state(device=None):
    params = {"w": jnp.linspace(-1.0, 1.0, 16 * 16).reshape(16, 16), "b": jnp.zeros((16,))}
    state = State(step=jnp.zeros((), jnp.int32), params=params, opt_state=TX.init(params))
    return jax.device_put(state, device or jax.devices()[0])


def _batch(rows=8, device=None):
    x = jnp.cos(jnp.arange(rows * 16, dtype=jnp.float32)).reshape(rows, 16)
    return jax.device_put(x, device or jax.devices()[0])


def _make_step():
    """A fresh function each call: a plain jit of it traces anew."""

    def train_step(state, x):
        def loss(p):
            return jnp.mean((jnp.tanh(x @ p["w"] + p["b"]) - 0.5) ** 2)

        value, grads = jax.value_and_grad(loss)(state.params)
        updates, opt_state = TX.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return state.replace(step=state.step + 1, params=params, opt_state=opt_state), {"loss": value}

    return train_step


def _kept(directory, context=("ctx",), step=None):
    jitted = jax.jit(step or _make_step(), donate_argnums=(0,))
    return warmup.keep_compiled(jitted, str(directory), "train_step", context)


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


@pytest.fixture
def tracer():
    compiles.install()
    tr = tspans.SpanTracer()
    prev = tspans.set_tracer(tr)
    try:
        yield tr
    finally:
        tspans.set_tracer(prev)


def _events(tr, name, start=0):
    return [e for e in tr.to_dict()["traceEvents"][start:] if e["name"] == name]


def _outcomes(tr, start=0):
    return [e["args"]["outcome"] for e in _events(tr, "jit/load", start)]


class TestTheStore:
    def test_a_hit_equals_the_plain_jit_to_the_bit_and_donates(self, tracer, tmp_path):
        _kept(tmp_path)(_state(), _batch())  # the miss writes the entry
        want = jax.jit(_make_step(), donate_argnums=(0,))(_state(), _batch())
        n = len(tracer.to_dict()["traceEvents"])
        state = _state()
        got = _kept(tmp_path)(state, _batch())
        assert _outcomes(tracer, n) == ["hit"]
        assert _leaves_equal(got, want)
        assert state.params["w"].is_deleted()  # donation holds in the loaded executable
        again = _kept(tmp_path)(got[0], _batch())  # and it carries on: step two
        assert int(again[0].step) == 2

    def test_a_hit_traces_and_lowers_nothing(self, tracer, tmp_path):
        _kept(tmp_path)(_state(), _batch())
        first = {e["name"] for e in tracer.to_dict()["traceEvents"] if "train_step" in str(e.get("args", {}).get("fun"))}
        assert {"jit/trace", "jit/lower", "jit/compile", "jit/load", "jit/store"} <= first
        jax.clear_caches()
        n = len(tracer.to_dict()["traceEvents"])
        _kept(tmp_path)(_state(), _batch())
        after = tracer.to_dict()["traceEvents"][n:]
        assert [e["args"] for e in after if e["name"] == "jit/load"] == [
            {"fun": "train_step", "outcome": "hit", "bytes": os.path.getsize(_entry(tmp_path))}
        ]
        assert not [e for e in after if e["name"] in ("jit/trace", "jit/lower", "jit/compile", "jit/store")
                    and "train_step" in e["args"]["fun"]]

    def test_one_executable_per_signature_and_a_new_one_for_another(self, tracer, tmp_path):
        kept = _kept(tmp_path)
        state, m = kept(_state(), _batch(8))
        state, m = kept(state, _batch(8))
        assert kept._cache_size() == 1
        state, m = kept(state, _batch(4))
        assert kept._cache_size() == 2 and int(state.step) == 3
        state, m = kept(state, _batch(8))  # back to the first: held, no lookup
        assert kept._cache_size() == 2 and _outcomes(tracer) == ["miss", "miss"]

    def test_a_truncated_entry_reads_bad_is_rewritten_and_the_result_is_right(self, tracer, tmp_path):
        _kept(tmp_path)(_state(), _batch())
        path = _entry(tmp_path)
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size // 2)
        want = jax.jit(_make_step(), donate_argnums=(0,))(_state(), _batch())
        n = len(tracer.to_dict()["traceEvents"])
        got = _kept(tmp_path)(_state(), _batch())
        assert _outcomes(tracer, n) == ["bad"]
        assert _leaves_equal(got, want)
        assert os.path.getsize(path) > size // 2  # written anew, whole
        n = len(tracer.to_dict()["traceEvents"])
        _kept(tmp_path)(_state(), _batch())
        assert _outcomes(tracer, n) == ["hit"]

    def test_without_a_directory_the_jit_itself_comes_back(self):
        jitted = jax.jit(_make_step())
        assert warmup.keep_compiled(jitted, None, "train_step", ("ctx",)) is jitted

    def test_lower_passes_through_to_the_jit(self, tmp_path):
        kept = _kept(tmp_path)
        text = kept.lower(_state(), _batch()).compile().as_text()
        assert "HloModule" in text and kept._cache_size() == 0

    def test_lower_of_a_held_signature_gives_the_loaded_executable(self, tracer, tmp_path):
        """What a traced run's look-up after its window gets: the executable
        that ran, its text with its operations' names, nothing traced."""
        _kept(tmp_path)(_state(), _batch())
        kept = _kept(tmp_path)
        state, _ = kept(_state(), _batch())
        assert _outcomes(tracer)[-1] == "hit"
        n = len(tracer.to_dict()["traceEvents"])
        compiled = kept.lower(state, _batch()).compile()
        assert compiled is kept._held[next(iter(kept._held))]
        assert "HloModule" in compiled.as_text() and "train_step" in compiled.as_text()
        assert not _events(tracer, "jit/trace", n) and not _events(tracer, "jit/lower", n)
        assert "HloModule" in kept.lower(state, _batch(4)).compile().as_text()  # not held: the jit's

    def test_the_strict_gate_still_raises_on_a_warm_recompile(self, tmp_path):
        kept = _kept(tmp_path)
        harness = StrictHarness(warmup_dispatches=1)
        state = _state()
        with harness.session():
            for _ in range(2):
                with harness.dispatch("train_step", kept):
                    state, _ = kept(state, _batch(8))
            with pytest.raises(StrictViolation, match="train_step"):
                with harness.dispatch("train_step", kept):
                    state, _ = kept(state, _batch(4))
        assert "jit cache grew 1->2" in harness.violations[0]

    def test_a_bound_on_the_cache_holds_the_store_to_it(self, tmp_path):
        for rows in (2, 4):
            _kept(tmp_path)(_state(), _batch(rows))
        store = tmp_path / warmup.EXECUTABLES_DIR
        # both entries as an earlier process left them, the first one older
        for age, name in enumerate(sorted(os.listdir(store), key=lambda n: os.path.getmtime(store / n))):
            os.utime(store / name, (warmup._IMPORTED_AT - 20 + 10 * age,) * 2)
        oldest = min(os.listdir(store), key=lambda name: os.path.getmtime(store / name))
        before = jax.config.jax_compilation_cache_max_size
        jax.config.update("jax_compilation_cache_max_size", int(2.5 * os.path.getsize(store / oldest)))
        try:
            _kept(tmp_path)(_state(), _batch(6))
        finally:
            jax.config.update("jax_compilation_cache_max_size", before)
        kept = os.listdir(store)
        assert len(kept) == 2 and oldest not in kept


def _rng_step():
    """`_make_step`'s step on noisy inputs: it traces the nested jits of
    `jax.random`, which a later program shares."""
    plain = _make_step()

    def train_step(state, x):
        key = jax.random.fold_in(jax.random.PRNGKey(0), state.step)
        return plain(state, x + 0.01 * jax.random.normal(key, x.shape))

    return train_step


class TestLaterPrograms:
    @pytest.mark.parametrize("limit, same", [(0, True), (10, False)])
    def test_a_later_programs_lowering_does_not_hang_on_a_load_or_a_compile(self, tmp_path, limit, same):
        """A nested jit keeps the call stack of the program that traced it
        first: with source locations in the metadata, a program lowered
        after a stored one lowers (and keys the persistent cache)
        differently where that one was compiled and where it was loaded.
        `place_compile_cache`'s limit of 0 frames keeps only the names."""

        def later(k):
            return jax.random.normal(jax.random.fold_in(k, 1), (4,))

        before = jax.config.jax_traceback_in_locations_limit
        jax.config.update("jax_traceback_in_locations_limit", limit)
        texts, outcomes = [], []
        tr = tspans.SpanTracer()
        prev = tspans.set_tracer(tr)
        try:
            for _ in range(2):  # a start that compiles and writes, then one that loads
                jax.clear_caches()  # what a new process starts from
                jitted = jax.jit(_rng_step(), donate_argnums=(0,))
                warmup.keep_compiled(jitted, str(tmp_path), "train_step", ("ctx",))(_state(), _batch())
                texts.append(jax.jit(later).lower(jax.random.PRNGKey(0)).as_text(debug_info=True))
        finally:
            tspans.set_tracer(prev)
            jax.config.update("jax_traceback_in_locations_limit", before)
        assert _outcomes(tr) == ["miss", "hit"]
        assert (texts[0] == texts[1]) is same


def _entry(store):
    (name,) = os.listdir(store / warmup.EXECUTABLES_DIR)
    return str(store / warmup.EXECUTABLES_DIR / name)


def _not_source(directory, names):
    return [
        n for n in names
        if n == "__pycache__" or not (n.endswith(".py") or os.path.isdir(os.path.join(directory, n)))
    ]


def _package_copy(tmp_path, name, flip=None):
    """A copy of the package's sources; ``flip`` changes one byte of it."""
    root = tmp_path / name
    shutil.copytree(warmup.PACKAGE_ROOT, root, ignore=_not_source)
    if flip:
        path = root / flip
        data = bytearray(path.read_bytes())
        data[-1] ^= 0x20
        path.write_bytes(bytes(data))
    return str(root)


def _config(**train):
    from replication_faster_rcnn_tpu.config import get_config

    cfg = get_config("trinity_tiny")
    return cfg.replace(train=dataclasses.replace(cfg.train, **train))


def _with_x64(on):
    jax.config.update("jax_enable_x64", on)


KEY_PARTS = {
    # part: what the second start changes (monkeypatch undoes it)
    "source_byte": lambda mp, tmp: mp.setattr(
        warmup, "PACKAGE_ROOT", _package_copy(tmp, "changed", flip=os.path.join("ops", "pallas", "nms_kernel.py"))
    ),
    "config_field": lambda mp, tmp: mp.setattr(
        TestKeyParts, "context", warmup.program_context(_config(lr=3e-4), 10, 1)
    ),
    "steps_per_epoch": lambda mp, tmp: mp.setattr(TestKeyParts, "context", warmup.program_context(_config(), 11, 1)),
    "batch_shape": lambda mp, tmp: mp.setattr(TestKeyParts, "rows", 4),
    "sharding": lambda mp, tmp: mp.setattr(TestKeyParts, "device", jax.devices()[1]),
    "x64": lambda mp, tmp: mp.setattr(TestKeyParts, "x64", True),
}


class TestKeyParts:
    context = None
    rows = 8
    device = None
    x64 = False

    def _run(self, tracer, store):
        n = len(tracer.to_dict()["traceEvents"])
        args = (_state(self.device), _batch(self.rows, self.device))
        _with_x64(self.x64)  # the same arguments, another setting
        try:
            _kept(store, context=self.context)(*args)
        finally:
            _with_x64(False)
        return _outcomes(tracer, n)

    @pytest.mark.parametrize("part", sorted(KEY_PARTS))
    def test_a_change_of_one_part_misses(self, part, tracer, tmp_path, monkeypatch):
        monkeypatch.setattr(warmup, "PACKAGE_ROOT", _package_copy(tmp_path, "base"))
        monkeypatch.setattr(TestKeyParts, "context", warmup.program_context(_config(), 10, 1))
        store = tmp_path / "store"
        assert self._run(tracer, store) == ["miss"]
        assert self._run(tracer, store) == ["hit"]
        KEY_PARTS[part](monkeypatch, tmp_path)
        assert self._run(tracer, store) == ["miss"]
        assert len(os.listdir(store / warmup.EXECUTABLES_DIR)) == 2

    def test_the_seed_is_out_of_the_key_unless_the_step_reads_it(self):
        assert warmup.program_context(_config(seed=1), 10, 1) == warmup.program_context(_config(seed=2), 10, 1)
        from tests.test_stage_scopes import _cfg

        aug = _cfg()
        aug = aug.replace(data=dataclasses.replace(aug.data, augment_device=True, augment_hflip=True))
        other = aug.replace(train=dataclasses.replace(aug.train, seed=5))
        assert warmup.program_context(aug, 10, 1) != warmup.program_context(other, 10, 1)

    @pytest.mark.parametrize("preset", ["detector", "trinity_tiny"])
    def test_the_train_step_lowers_the_same_for_two_seeds(self, preset):
        """What lets `program_context` leave `train.seed` out: the step's
        lowering does not read it (on-device augmentation off)."""
        from replication_faster_rcnn_tpu.train.train_step import make_optimizer, make_train_step

        def lowered(seed):
            if preset == "detector":
                from tests.test_stage_scopes import _batch as det_batch, _cfg

                cfg = _cfg(seed=seed)
                batch = det_batch(cfg)
            else:
                cfg = _config(seed=seed)
                batch = {"tokens": jax.ShapeDtypeStruct((cfg.train.batch_size, cfg.data.seq_len), jnp.int32)}
            from replication_faster_rcnn_tpu.train.train_step import create_train_state

            tx, _ = make_optimizer(cfg, steps_per_epoch=10)
            state = jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(seed), tx)[1])
            model = None if cfg.is_sequence_model else __import__(
                "replication_faster_rcnn_tpu.models.faster_rcnn", fromlist=["FasterRCNN"]
            ).FasterRCNN(cfg)
            return jax.jit(make_train_step(model, cfg, tx)).lower(state, batch).as_text()

        assert lowered(0) == lowered(7)


class TestTwoTrainers:
    def test_the_second_start_loads_what_the_first_compiled(self, tmp_path, monkeypatch):
        """Two trainers of a tiny detector share one store (the
        `JAX_COMPILATION_CACHE_DIR` route): the second's first dispatch loads
        its step, its metrics are the first's, and nothing recompiles."""
        from tests.test_stage_scopes import _cfg

        from replication_faster_rcnn_tpu.data import SyntheticDataset
        from replication_faster_rcnn_tpu.data.loader import collate
        from replication_faster_rcnn_tpu.train.trainer import Trainer

        cfg = _cfg()
        cfg = cfg.replace(debug=dataclasses.replace(cfg.debug, strict=True))
        ds = SyntheticDataset(cfg.data, length=4)
        batch = collate([ds[0], ds[1]])
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
        touched = (
            "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key", "jax_traceback_in_locations_limit",
        )
        before = {k: getattr(jax.config, k) for k in touched}
        runs = []
        try:
            for i in range(2):
                tel = str(tmp_path / f"tel{i}")
                trainer = Trainer(cfg, workdir=str(tmp_path / f"ckpt{i}"), dataset=ds, telemetry_dir=tel)
                try:
                    with trainer.strict_session():
                        rows = [trainer.train_one_batch(batch) for _ in range(3)]
                    trainer.flush_telemetry()
                finally:
                    tspans.set_tracer(None)
                with open(os.path.join(tel, "trace.json")) as f:
                    spans = json.load(f)["traceEvents"]
                runs.append((jax.device_get(rows), spans, trainer.strict.report()))
        finally:
            for k, v in before.items():
                jax.config.update(k, v)
        loads = [{e["args"]["fun"]: e["args"]["outcome"] for e in spans if e["name"] == "jit/load"}
                 for _, spans, _ in runs]
        assert loads == [{"init": "miss", "train_step": "miss"}, {"init": "hit", "train_step": "hit"}]
        second = runs[1][1]
        first_dispatch = min((e for e in second if e["name"] == "step/dispatch"), key=lambda e: e["ts"])
        hit = next(e for e in second if e["name"] == "jit/load" and e["args"]["fun"] == "train_step")
        assert first_dispatch["ts"] <= hit["ts"] <= first_dispatch["ts"] + first_dispatch["dur"]
        assert not [e for e in second if e["name"] in ("jit/trace", "jit/lower")
                    and e["args"]["fun"] in ("jit(train_step)", "jit(init)")]
        assert _leaves_equal(runs[0][0], runs[1][0])
        for _, _, report in runs:
            assert report["programs"]["train_step"]["recompiles_after_warmup"] == 0
        assert sorted(os.listdir(tmp_path / "cache" / warmup.EXECUTABLES_DIR))[0].startswith("init-")
