"""The model's side of `trinity_mini_ep8`: the plain reference of one training
step of an `afmoe` decoder (arcee-ai Trinity-Mini's `model_type`), and what
else a `reference` module states (perf/harness.py has the list).

Plain `jax.numpy`, float32, every matrix product at `Precision.HIGHEST`, no
kernel, no cache, no sorting by expert; it imports nothing of the program.
To fit beside its own state (16 B a parameter) it computes in blocks, and
that is all its structure: a layer recomputed at a time in the backward pass,
queries a block at a time against all keys, the held experts one after
another over all tokens, the dense feed-forward, the head and the loss a
block of tokens at a time.

The model, from the catalog row's `config` unless marked *assumed* (each
*assumed* is listed in the configuration's file):

  x0 = E[t] * sqrt(hidden)                 (mup_enabled; the value *assumed*)
  a  = x + Attn(RMSNorm(x)); y = a + FFN(RMSNorm(a))     (*assumed* pre-norm)
  Attn: q as H heads, k and v as KV heads each serving H/KV query heads;
        rotary embedding over the whole head, pairs (i, i + d/2), theta
        10,000, positions 0..T-1 along the row; scores q.k / sqrt(d); a key
        is visible if it is not later and, on a `sliding_attention` layer,
        fewer than `sliding_window` positions earlier; softmax; W_o. A packed
        row is one causal stream (*assumed*: no mask between documents).
  FFN, dense layers: W2 (silu(W1 h) * W3 h).
  FFN, expert layers: s = sigmoid(W_r h) over ALL experts; chosen = top-k of
        s + b (b the balance bias, no gradient); weights = chosen s / their
        sum * route_scale; out = shared expert's SwiGLU + sum over the chosen
        experts THAT ARE HELD (first_expert .. first_expert + held - 1) of
        weight * that expert's SwiGLU. What the others would add is left out.
        After the step b += coeff * sign(mean(c) - c), centred on zero, c the
        count of tokens that chose each expert in the step.
  loss = mean cross-entropy of the next token over every position of a row
        but its last, logits over the vocabulary rows held.

Left out because the catalog row gives none of them (*assumed* absent): an
output gate on the attention, a norm on q and k, a second norm after a
sublayer, layers without rotary embedding.

`precision` selects what the control of `correct` needs: "float32" is the
reference; "bfloat16" and "float8" round the operands of every matrix
product (the attention's two among them) to that type's precision.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, jnp.ndarray]
HI = lax.Precision.HIGHEST

BATCH_KEYS = ("tokens",)
# compared at step 1: the loss, and the token-expert pairs computed here
# (summed over the expert layers): a flipped top-k choice moves it by one
LOSS_PARTS = ("nll_loss", "expert_assignments")
# the first gradient's norms upstream of every top-k choice of the forward
# pass: the embedding's rows and the first layer's attention
LEAF_NUMBERS = {"embed_grad_gap": ("embed/",), "attn0_grad_gap": ("layers_0/attn/",)}
SCOPE_PREFIX = "frcnn."  # of the step program's stage scopes (`telemetry/stages.py`)
QUERY_BLOCK = 128
TOKEN_BLOCK = 2048


class Sizes:
    """The configuration's sizes, read from its JSON `sizes` block."""

    def __init__(self, sizes: Dict[str, Any], batch: int) -> None:
        g = sizes.__getitem__
        self.batch = int(batch)
        self.seq_len = int(g("data.seq_len"))
        self.vocab = int(g("lm.vocab_rows"))
        self.hidden = int(g("lm.hidden_size"))
        self.heads, self.kv_heads, self.head = int(g("lm.num_heads")), int(g("lm.num_kv_heads")), int(g("lm.head_size"))
        self.window = int(g("lm.sliding_window"))
        self.layer_types = tuple(g("lm.layer_types"))
        self.dense_layers = int(g("lm.num_dense_layers"))
        self.dense_width, self.expert_width = int(g("lm.dense_width")), int(g("lm.expert_width"))
        self.experts, self.top_k = int(g("lm.num_experts")), int(g("lm.experts_per_token"))
        self.route_scale, self.balance = float(g("lm.route_scale")), float(g("lm.load_balance_coeff"))
        self.theta, self.eps = float(g("lm.rope_theta")), float(g("lm.rms_norm_eps"))
        self.held, self.first = int(g("lm.experts_held")), int(g("lm.first_expert"))
        self.lr, self.weight_decay = float(g("train.lr")), float(g("train.weight_decay"))

    def expert_layers(self):
        return [i for i in range(len(self.layer_types)) if i >= self.dense_layers]


# ----------------------------------------------------------- precision


def _straight_through(x, qx):
    return x + lax.stop_gradient(qx - x)


def make_rounding(precision: str):
    """Operand rounding for matrix products, with `lax.reduce_precision` (the
    TPU compiler removes a plain `astype` round trip)."""
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: _straight_through(x, lax.reduce_precision(x, 8, 7))
    if precision == "float8":

        def q(x):
            # e4m3 with a per-tensor scale that puts the largest magnitude at 240
            scale = jnp.maximum(jnp.max(jnp.abs(lax.stop_gradient(x))), 1e-30) / 240.0
            return _straight_through(x, lax.reduce_precision(x / scale, 4, 3) * scale)

        return q
    raise ValueError(f"unknown precision {precision!r}")


# ------------------------------------------------------------- weights


def param_shapes(sz: Sizes) -> Dict[str, tuple]:
    """Leaf name -> shape: the program's gradient leaves, "/"-joined."""
    d, hd = sz.hidden, sz.head
    shapes = {"embed/table": (sz.vocab, d)}
    for i in range(len(sz.layer_types)):
        at = f"layers_{i}/"
        shapes.update({
            at + "attn_norm/scale": (d,), at + "ffn_norm/scale": (d,),
            at + "attn/wq": (d, sz.heads * hd), at + "attn/wk": (d, sz.kv_heads * hd),
            at + "attn/wv": (d, sz.kv_heads * hd), at + "attn/wo": (sz.heads * hd, d),
        })
        if i < sz.dense_layers:
            f = sz.dense_width
            shapes.update({at + "ffn/w1": (d, f), at + "ffn/w3": (d, f), at + "ffn/w2": (f, d)})
        else:
            f, e = sz.expert_width, sz.held
            shapes.update({
                at + "router/kernel": (d, sz.experts),
                at + "experts/w1": (e, d, f), at + "experts/w3": (e, d, f), at + "experts/w2": (e, f, d),
                at + "shared/w1": (d, f), at + "shared/w3": (d, f), at + "shared/w2": (f, d),
            })
    shapes["final_norm/scale"] = (d,)
    shapes["head/kernel"] = (d, sz.vocab)
    return shapes


def init_params(sz: Sizes, key) -> Params:
    """Norms at one; every matrix N(0, 1 / its fan-in), so that activations
    and logits are of order one at every width (the embedding's multiplier
    sqrt(hidden) brings its rows to order one)."""
    shapes = param_shapes(sz)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if len(shape) == 1:
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            fan_in = sz.hidden if name == "embed/table" else shape[-2]
            out[name] = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
    return out


def init_adam(params: Params) -> Dict[str, Any]:
    """Adam's moments, and beside them the balance bias of every expert
    layer, which the harness hands back to each step unopened."""
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    bias = {k.split("/")[0]: jnp.zeros((v.shape[1],), jnp.float32) for k, v in params.items() if k.endswith("router/kernel")}
    return {"mu": zeros, "nu": dict(zeros), "router_bias": bias}


# -------------------------------------------------------------- layers


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x: [B, T, heads, d]."""
    t, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mm(x, w, q):
    return jnp.dot(q(x), q(w), precision=HI)


def swiglu(h, w1, w3, w2, q):
    return _mm(jax.nn.silu(_mm(h, w1, q)) * _mm(h, w3, q), w2, q)


def by_token_blocks(fn, args):
    """`fn` over blocks of TOKEN_BLOCK tokens (the leading axis of every
    array in `args`), each block recomputed in the backward pass; the blocks'
    results stacked. What `fn` computes is a token's own."""
    n = args[0].shape[0]
    block = TOKEN_BLOCK if n % TOKEN_BLOCK == 0 else n
    blocks = tuple(a.reshape((n // block, block) + a.shape[1:]) for a in args)
    return lax.map(jax.checkpoint(fn), blocks)


def attention(qh, kh, vh, window, q):
    """Masked softmax attention, a block of queries at a time against all
    keys. qh: [B, T, H, d]; kh, vh: [B, T, KV, d]; window None on a full layer."""
    b, t, h, d = qh.shape
    kv = kh.shape[2]
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)
    qg = jnp.transpose(qh.reshape(b, t, kv, h // kv, d), (0, 2, 3, 1, 4))  # [B, KV, G, T, d]
    kt, vt = jnp.transpose(kh, (0, 2, 1, 3)), jnp.transpose(vh, (0, 2, 1, 3))  # [B, KV, T, d]
    keys = jnp.arange(t)

    @jax.checkpoint
    def one_block(args):
        qb, start = args  # [B, KV, G, block, d]
        scores = jnp.einsum("bkgqd,bktd->bkgqt", q(qb), q(kt), precision=HI) / jnp.sqrt(float(d))
        rows = start + jnp.arange(block)
        seen = keys[None, :] <= rows[:, None]
        if window is not None:
            seen = seen & (rows[:, None] - keys[None, :] < window)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqt,bktd->bkgqd", q(p), q(vt), precision=HI)

    blocks = jnp.moveaxis(qg.reshape(b, kv, h // kv, t // block, block, d), 3, 0)
    out = lax.map(one_block, (blocks, jnp.arange(0, t, block)))  # [T/block, B, KV, G, block, d]
    out = jnp.moveaxis(out, 0, 3).reshape(b, kv, h // kv, t, d)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, t, h * d)


def route(h, kernel, bias, sz: Sizes):
    """h: [N, D]. (chosen [N, k], weights [N, k], counts [E])."""
    scores = jax.nn.sigmoid(jnp.dot(h, kernel, precision=HI))
    _, chosen = lax.top_k(scores + bias, sz.top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    weights = picked / jnp.sum(picked, axis=1, keepdims=True) * sz.route_scale
    counts = jnp.sum(jax.nn.one_hot(chosen, sz.experts, dtype=jnp.float32), axis=(0, 1))
    return chosen, weights, counts


def held_experts(h, chosen, weights, w1, w3, w2, sz: Sizes, q):
    """The weighted sum over the chosen experts that are held: each held
    expert's SwiGLU over all tokens, times the weight the token gave it
    (nought where it did not choose it)."""

    @jax.checkpoint
    def part(e, a, b, c):
        weight = jnp.sum(jnp.where(chosen == sz.first + e, weights, 0.0), axis=1)
        return weight[:, None] * swiglu(h, a, b, c, q)

    def one_expert(y, args):
        # the sum is linear in what it carries: only an expert's part is recomputed
        return y + part(*args), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(h), (jnp.arange(sz.held), w1, w3, w2))
    return y


def layer(p: Params, i: int, bias, x, sz: Sizes, q):
    at = f"layers_{i}/"
    b, t, d = x.shape
    h = rms_norm(x, p[at + "attn_norm/scale"], sz.eps)
    heads = lambda y, n: y.reshape(b, t, n, sz.head)
    qh = rotary(heads(_mm(h, p[at + "attn/wq"], q), sz.heads), sz.theta)
    kh = rotary(heads(_mm(h, p[at + "attn/wk"], q), sz.kv_heads), sz.theta)
    vh = heads(_mm(h, p[at + "attn/wv"], q), sz.kv_heads)
    window = sz.window if sz.layer_types[i] == "sliding_attention" else None
    x = x + _mm(attention(qh, kh, vh, window, q), p[at + "attn/wo"], q)
    h = rms_norm(x, p[at + "ffn_norm/scale"], sz.eps)
    flat = h.reshape(b * t, d)
    if i < sz.dense_layers:
        ffn = by_token_blocks(lambda a: swiglu(a[0], p[at + "ffn/w1"], p[at + "ffn/w3"], p[at + "ffn/w2"], q), (flat,))
        return x + ffn.reshape(b, t, d), None
    chosen, weights, counts = route(flat, p[at + "router/kernel"], bias, sz)
    routed = held_experts(flat, chosen, weights, p[at + "experts/w1"], p[at + "experts/w3"], p[at + "experts/w2"], sz, q)
    shared = swiglu(h, p[at + "shared/w1"], p[at + "shared/w3"], p[at + "shared/w2"], q)
    here = (chosen >= sz.first) & (chosen < sz.first + sz.held)
    return x + shared + routed.reshape(b, t, d), (counts, jnp.sum(here).astype(jnp.float32))


def head_loss(p: Params, x, tokens, sz: Sizes, q):
    """Mean cross-entropy of the next token over every position of a row but
    its last, the logits a block of tokens at a time."""
    b, t, d = x.shape
    x = rms_norm(x, p["final_norm/scale"], sz.eps).reshape(b * t, d)
    target = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1).reshape(b * t)
    counted = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t)).reshape(b * t)

    def nll(args):
        xs, ys, ws = args
        logp = jax.nn.log_softmax(_mm(xs, p["head/kernel"], q), axis=-1)
        picked = jnp.take_along_axis(logp, ys[:, None], axis=1)[:, 0]
        return -jnp.sum(jnp.where(ws, picked, 0.0))

    return jnp.sum(by_token_blocks(nll, (x, target, counted))) / (b * (t - 1))


def loss_fn(p: Params, bias: Dict[str, jnp.ndarray], tokens, sz: Sizes, q):
    x = p["embed/table"][tokens] * jnp.sqrt(float(sz.hidden))
    counts, pairs = {}, 0.0
    for i in range(len(sz.layer_types)):
        name = f"layers_{i}"
        # a layer is given its own leaves: the whole tree would come back as a gradient of zeros a layer
        own = {k: v for k, v in p.items() if k.startswith(name + "/")}
        x, routed = jax.checkpoint(lambda p, b, x, i=i: layer(p, i, b, x, sz, q))(own, bias.get(name), x)
        if routed is not None:
            counts[name], pairs = routed[0], pairs + routed[1]
    return head_loss(p, x, tokens, sz, q), (counts, pairs)


def train_step(params: Params, adam, batch, rng, step, sz: Sizes, precision: str = "float32"):
    """One step: (params, adam, losses, grad) after the update; `grad` is the
    gradient as Adam gets it, the L2 term added. `rng` is not used: the model
    samples nothing. `adam` carries the balance bias beside the moments."""
    q = make_rounding(precision)
    bias = adam.get("router_bias") or {f"layers_{i}": jnp.zeros((sz.experts,), jnp.float32) for i in sz.expert_layers()}
    (loss, (counts, pairs)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, bias, batch["tokens"], sz, q)
    b1, b2, eps = 0.9, 0.999, 1e-8
    t = (step + 1).astype(jnp.float32)
    new_p, mu, nu, seen = {}, {}, {}, {}
    for name, p in params.items():
        g = grads[name] + sz.weight_decay * p if sz.weight_decay else grads[name]
        m = b1 * adam["mu"][name] + (1 - b1) * g
        v = b2 * adam["nu"][name] + (1 - b2) * g * g
        # the schedule is a cosine over epochs: constant lr inside epoch 0
        new_p[name] = p - sz.lr * (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + eps)
        mu[name], nu[name], seen[name] = m, v, g
    new_bias = {}
    for name, c in counts.items():
        moved = bias[name] + sz.balance * jnp.sign(jnp.mean(c) - c)
        new_bias[name] = moved - jnp.mean(moved)
    parts = {"loss": loss, "nll_loss": loss, "expert_assignments": pairs}
    return new_p, {"mu": mu, "nu": nu, "router_bias": new_bias}, parts, seen


def leaf_norms(tree: Params) -> Dict[str, jnp.ndarray]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


# ----------------------------------------- what the cell's readers call


def visible_pairs(t: int, window) -> int:
    """(query, key) pairs of a row of t tokens that the mask lets through."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _row_pairs(sz: Sizes) -> int:
    return sum(visible_pairs(sz.seq_len, sz.window if kind == "sliding_attention" else None) for kind in sz.layer_types)


def train_flops_per_image(sizes: Dict[str, Any]) -> float:
    """FLOPs a sample (one packed row) NEEDS, forward and backward, from
    shapes alone: 6 a token for every matrix-product parameter a token meets
    (the routed experts at their expected share here, top_k * held / experts
    assignments a token and layer), and 12 * head size a visible (query,
    key) pair and head for the attention's two products. No recomputation,
    never what the program executes; the embedding is a gather."""
    sz = Sizes(sizes, 1)
    d = sz.hidden
    attn = 2 * d * sz.heads * sz.head + 2 * d * sz.kv_heads * sz.head
    expert = 3 * d * sz.expert_width
    share = sz.top_k * sz.held / sz.experts
    per_token = 0.0
    for i in range(len(sz.layer_types)):
        per_token += attn + (3 * d * sz.dense_width if i < sz.dense_layers else d * sz.experts + expert * (1 + share))
    per_token += d * sz.vocab
    return 6.0 * per_token * sz.seq_len + 12.0 * sz.head * sz.heads * _row_pairs(sz)


def attention_roofline_seconds(sizes: Dict[str, Any], batch: int, flops_per_s: float, bytes_per_s: float) -> float:
    """The least time a chip needs for the attention function of one step
    (the projections apart): per layer and pass the larger of FLOPs over peak
    (forward 4 * head size a visible pair and head, backward 8) and bytes
    over bandwidth (q, k, v and the output once, bfloat16; backward their
    cotangents too)."""
    sz = Sizes(sizes, batch)
    tokens = batch * sz.seq_len
    moved = 2.0 * tokens * sz.head * (2 * sz.heads + 2 * sz.kv_heads)  # bytes of q, o, k, v
    least = 0.0
    for kind in sz.layer_types:
        pairs = batch * visible_pairs(sz.seq_len, sz.window if kind == "sliding_attention" else None) * sz.heads
        least += max(4.0 * sz.head * pairs / flops_per_s, moved / bytes_per_s)
        least += max(8.0 * sz.head * pairs / flops_per_s, 2.0 * moved / bytes_per_s)
    return least


def expert_mm_roofline_seconds(sizes: Dict[str, Any], assignments: float, flops_per_s: float, bytes_per_s: float) -> float:
    """The least time for the grouped products of one step that computes
    `assignments` token-expert pairs (all expert layers together): forward
    2 * 3 * hidden * expert width FLOPs a pair, backward twice that; bytes a
    pass: the held experts' weights once and the pairs' rows in, between the
    products and out (bfloat16), the backward reading and writing twice that."""
    sz = Sizes(sizes, 1)
    weights = 2.0 * len(sz.expert_layers()) * sz.held * 3 * sz.hidden * sz.expert_width
    rows = 2.0 * assignments * (2 * sz.hidden + 3 * sz.expert_width)
    flops = 2.0 * 3 * sz.hidden * sz.expert_width * assignments
    return max(flops / flops_per_s, (weights + rows) / bytes_per_s) + max(2 * flops / flops_per_s, 2 * (weights + rows) / bytes_per_s)
