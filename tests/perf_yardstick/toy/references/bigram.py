"""The model's side of the toy configuration: the plain reference of one
training step of a bigram language model, and what else a `reference` module
states (perf/harness.py has the list). logits = table[tokens] @ kernel +
bias, the loss the mean cross-entropy against the next token, Adam by hand.
It imports nothing of the program.
"""

import jax
import jax.numpy as jnp

BATCH_KEYS = ("tokens", "targets")
LOSS_PARTS = ("nll_loss",)
LEAF_NUMBERS = {"embed_grad_gap": ("embed/",)}
SCOPE_PREFIX = "bigram."


class Sizes:
    def __init__(self, sizes, batch):
        self.batch = int(batch)
        self.vocab, self.width = int(sizes["model.vocab"]), int(sizes["model.width"])
        self.seq_len, self.lr = int(sizes["data.seq_len"]), float(sizes["train.lr"])


def train_flops_per_image(sizes):
    """FLOPs a sample (a row) needs: the output product forward, and its two
    gradients; the embedding is a gather."""
    return 3 * 2.0 * sizes["data.seq_len"] * sizes["model.width"] * sizes["model.vocab"]


def init_params(sz, key):
    k1, k2 = jax.random.split(key)
    return {
        "embed/table": jax.random.normal(k1, (sz.vocab, sz.width), jnp.float32),
        "out/kernel": jax.random.normal(k2, (sz.width, sz.vocab), jnp.float32) / jnp.sqrt(sz.width),
        "out/bias": jnp.zeros((sz.vocab,), jnp.float32),
    }


def init_adam(params):
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"mu": zeros, "nu": dict(zeros)}


def loss_fn(p, batch):
    hidden = p["embed/table"][batch["tokens"]]
    logits = jnp.einsum("btd,dv->btv", hidden, p["out/kernel"], precision="highest") + p["out/bias"]
    picked = jnp.take_along_axis(jax.nn.log_softmax(logits), batch["targets"][..., None], axis=-1)
    return -picked.mean()


def train_step(params, adam, batch, rng, step, sz, precision="float32"):
    """(params, adam, losses, grad) after one Adam step; `rng` is not used:
    the model samples nothing."""
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    b1, b2, eps = 0.9, 0.999, 1e-8
    t = (step + 1).astype(jnp.float32)
    new_p, mu, nu = {}, {}, {}
    for name, p in params.items():
        mu[name] = b1 * adam["mu"][name] + (1 - b1) * grads[name]
        nu[name] = b2 * adam["nu"][name] + (1 - b2) * grads[name] ** 2
        new_p[name] = p - sz.lr * (mu[name] / (1 - b1**t)) / (jnp.sqrt(nu[name] / (1 - b2**t)) + eps)
    return new_p, {"mu": mu, "nu": nu}, {"loss": loss, "nll_loss": loss}, grads


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}
