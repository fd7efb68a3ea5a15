"""The smallest program the harness can drive: what perf/harness.py asks of
`(get_config, Trainer)` and perf/reckon_memory.py of `step_and_state`, for a
bigram language model. Nothing of the detector's package is imported.
"""

import contextlib
import dataclasses
import json
import os
import time
from types import SimpleNamespace

import flax
import jax
import jax.numpy as jnp
import numpy as np
import optax


@dataclasses.dataclass(frozen=True)
class Train:
    batch_size: int = 16
    seed: int = 0
    lr: float = 1e-3


@dataclasses.dataclass(frozen=True)
class Data:
    rows_file: str = ""
    seq_len: int = 64


@dataclasses.dataclass(frozen=True)
class Model:
    vocab: int = 512
    width: int = 128


@dataclasses.dataclass(frozen=True)
class Debug:
    strict: bool = False


@dataclasses.dataclass(frozen=True)
class Compile:
    cache_dir: str = ""


@dataclasses.dataclass(frozen=True)
class Config:
    train: Train = Train()
    data: Data = Data()
    model: Model = Model()
    debug: Debug = Debug()
    compile: Compile = Compile()
    replace = dataclasses.replace


def get_config(preset):
    return Config()


class State(flax.struct.PyTreeNode):
    step: jax.Array
    params: dict
    opt_state: tuple
    rng: jax.Array


def _build(cfg):
    """(the un-jitted step, the function that makes its first state, the optimizer)."""
    tx = optax.adam(cfg.train.lr)

    def loss_fn(params, batch):
        with jax.named_scope("bigram.embed"):
            hidden = params["embed"]["table"][batch["tokens"]]
        with jax.named_scope("bigram.logits"):
            # float32 as the configuration states: a TPU's default is one bfloat16 pass
            logits = jnp.matmul(hidden, params["out"]["kernel"], precision="highest") + params["out"]["bias"]
            picked = jnp.take_along_axis(jax.nn.log_softmax(logits), batch["targets"][..., None], axis=-1)
            return -picked.mean()

    def step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        with jax.named_scope("bigram.update"):
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        return state.replace(step=state.step + 1, params=params, opt_state=opt_state), {"loss": loss, "nll_loss": loss}

    def init():
        m = cfg.model
        params = {
            "embed": {"table": jnp.zeros((m.vocab, m.width), jnp.float32)},
            "out": {"kernel": jnp.zeros((m.width, m.vocab), jnp.float32), "bias": jnp.zeros((m.vocab,), jnp.float32)},
        }
        return State(step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params), rng=jax.random.PRNGKey(0))

    return step, init, tx


def step_and_state(cfg):
    """The un-jitted step `(state, batch) -> (state, metrics)` and the shapes
    of its state: what perf/reckon_memory.py lowers."""
    step, init, _ = _build(cfg)
    return step, jax.eval_shape(init)


class Loader:
    """Batches of rows from the file, shuffled anew every epoch."""

    def __init__(self, rows_file, batch, seed):
        self.rows, self.batch, self.seed, self.epoch = np.load(rows_file), batch, seed, 0

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        order = np.random.RandomState((self.seed + self.epoch) % (2**32)).permutation(len(self.rows))
        for i in range(0, len(order) - self.batch + 1, self.batch):
            rows = self.rows[order[i : i + self.batch]]
            yield {"tokens": rows[:, :-1], "targets": rows[:, 1:]}


class Tracer:
    """Spans as Chrome trace events, mirrored onto the profiler's clock; the
    first event says where the run's files lie. Without a directory, nothing."""

    def __init__(self, directory):
        self.dir, self.t0 = directory, time.perf_counter()
        self.events = [{"name": "telemetry/open", "ph": "i", "ts": 0.0, "args": {"dir": os.path.abspath(directory or "")}}]

    def now_us(self):
        return (time.perf_counter() - self.t0) * 1e6

    @contextlib.contextmanager
    def span(self, name, cat="phase", **args):
        if self.dir is None:
            yield
            return
        t = self.now_us()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.events.append({"name": name, "cat": cat, "ph": "X", "ts": t, "dur": self.now_us() - t})

    def flush(self):
        if self.dir is not None:
            os.makedirs(self.dir, exist_ok=True)
            with open(os.path.join(self.dir, "trace.json"), "w") as f:
                json.dump({"traceEvents": self.events}, f)


class Trainer:
    def __init__(self, cfg, workdir, devices, telemetry_dir=None):
        (device,) = devices
        on = jax.sharding.SingleDeviceSharding(device)
        step, init, self.tx = _build(cfg)
        self._state_shardings = SimpleNamespace(params=on, opt_state=on, rng=on)
        self.state = jax.device_put(jax.jit(init)(), on)
        self.jitted_step = jax.jit(step, donate_argnums=(0,))
        self.loader = Loader(cfg.data.rows_file, cfg.train.batch_size, cfg.train.seed)
        self.tracer, self.strict, self._on = Tracer(telemetry_dir), None, on

    def _stage_batch(self, batch, wait=False):
        with self.tracer.span("data/device_put", cat="data"):
            staged = jax.device_put(batch, self._on)
            return jax.block_until_ready(staged) if wait else staged

    def train_one_batch(self, batch=None, staged=None):
        staged = self._stage_batch(batch) if staged is None else staged
        with self.tracer.span("step/dispatch", cat="step"):
            self.state, metrics = self.jitted_step(self.state, staged)
        return metrics

    def strict_session(self):
        return contextlib.nullcontext()

    def flush_telemetry(self):
        self.tracer.flush()
