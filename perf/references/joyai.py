"""The model's side of `joyai_flash_ep16`: the plain reference of one training
step of a `joyai_llm_flash` decoder (JoyAI-LLM-Flash's `model_type`, the
DeepSeek-V3 layer: multi-head latent attention, a sigmoid router with a
balance bias and a shared expert, one multi-token-prediction module), and what
else a `reference` module states (perf/harness.py has the list).

Plain `jax.numpy`, float32, every matrix product at `Precision.HIGHEST`, no
kernel, no cache, no sorting by expert; it imports nothing of the program.
The equations are DeepSeek-V2's (arXiv:2405.04434 section 2.1) and
DeepSeek-V3's (arXiv:2412.19437 sections 2.1-2.2) at the catalog row's sizes:

  N(x) = x * rsqrt(mean(x^2) + eps) * w              (plain weight)
  x0 = E[t]                                          (no multiplier)
  a = x + MLA(N(x));  y = a + FFN(N(a))              (pre-norm, no biases)

  MLA:  c_q = N_qa(x W_qa)                                    [q_lora_rank]
        q = c_q W_qb, a head's [q_nope (nope) | q_pe (rope)]
        [c_kv (kv_lora_rank) | k_pe (rope)] = x W_kva;  c_kv = N_kva(c_kv)
        [k_nope (nope) | v (v_head)] a head = c_kv W_kvb
        q_pe, k_pe turned by the rotary embedding, pairs (2i, 2i + 1)
        (rope_interleave), theta rope_theta, positions 0..T-1 along the row;
        ONE k_pe for all heads
        q_h = [q_nope_h | q_pe_h];  k_h = [k_nope_h | k_pe]
        o_h = softmax(q_h k_h^T / sqrt(nope + rope) + causal) v_h
        y = concat_h(o_h) W_o
  FFN, the leading dense layers: W2 (silu(W1 h) * W3 h).
  FFN, expert layers: s = sigmoid(W_r h) over ALL experts; chosen = top-k of
        s + b (b the balance bias, no gradient); weights = chosen s / their
        sum * route_scale; out = shared expert's SwiGLU + sum over the chosen
        experts THAT ARE HELD (first_expert .. first_expert + held - 1) of
        weight * that expert's SwiGLU. What the others would add is left out.
        After the step b += coeff * sign(mean(c) - c), centred on zero, c the
        count of tokens that chose each expert in the step.
  loss_main = mean cross-entropy of the next token over every position of a
        row but its last, logits N(x_L) W_head over the vocabulary rows held.
  MTP (depth 1): h'_i = [N_e(E[t_{i+1}]) | N_h(x_L,i)] W_eh, x_L the trunk's
        last output before its final norm (the row's last position takes
        E[0]: its outputs enter no loss); h^1 = one expert layer over h'
        (an MLA mixer, its own router, balance bias and held experts);
        loss_mtp = mean CE(N_m(h^1_i) W_head, t_{i+2}) over i = 0 .. T-3, the
        embedding and head shared with the trunk.
  loss = loss_main + mtp_weight * loss_mtp.

*Assumed* (each listed in the configuration's file): mtp_weight 0.1
(DeepSeek-V3's later weight), the balance bias's speed 0.001 and no
sequence-wise auxiliary loss, no mask and no state reset between documents,
`eh_proj`'s input order [embedding | hidden], the MTP layer an expert layer.

Departures from plainness, each to fit beside the reference's own state (16 B
a parameter) and none a change of the arithmetic: a layer is recomputed at a
time in the backward pass, its mixer apart from its feed-forward part; the
attention a block of queries at a time against all keys; the held experts
one after another over all tokens; the dense feed-forward, the head and the
loss a block of tokens at a time.

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
# compared at step 1: the loss, the token-expert pairs computed here (summed
# over the expert layers and the MTP layer: a flipped top-k choice moves it by
# one) and the multi-token-prediction module's loss
LOSS_PARTS = ("nll_loss", "expert_assignments", "mtp_loss")
# the first gradient's norms upstream of every top-k choice of the forward
# pass: the embedding's rows and the first layer's keys' and values' latent
# up-projection
LEAF_NUMBERS = {"embed_grad_gap": ("embed/",), "latent0_grad_gap": ("layers_0/attn/wkv_b",)}
SCOPE_PREFIX = "frcnn."  # of the step program's stage scopes (`telemetry/stages.py`)
MTP = "mtp/"
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
        self.heads = int(g("lm.num_heads"))
        self.q_rank, self.kv_rank = int(g("lm.q_lora_rank")), int(g("lm.kv_lora_rank"))
        self.nope, self.rope, self.v_head = int(g("lm.qk_nope_head_dim")), int(g("lm.qk_rope_head_dim")), int(g("lm.v_head_dim"))
        self.layer_types = tuple(g("lm.layer_types"))
        self.dense_layers = int(g("lm.num_dense_layers"))
        self.dense_width, self.expert_width = int(g("lm.dense_width")), int(g("lm.expert_width"))
        self.experts, self.top_k = int(g("lm.num_experts")), int(g("lm.experts_per_token"))
        self.route_scale, self.balance = float(g("lm.route_scale")), float(g("lm.load_balance_coeff"))
        self.theta, self.eps = float(g("lm.rope_theta")), float(g("lm.rms_norm_eps"))
        self.held, self.first = int(g("lm.experts_held")), int(g("lm.first_expert"))
        self.mtp_weight = float(g("lm.mtp_weight"))
        self.lr, self.weight_decay = float(g("train.lr")), float(g("train.weight_decay"))
        # what this reference is: a size file that says otherwise is another model's
        told = {
            "lm.router_score": "sigmoid", "lm.norm_zero_centred": False, "lm.embed_scale": False,
            "lm.shared_expert_gate": False, "lm.rope_interleave": True, "lm.mtp_layers": 1,
        }
        odd = {k: sizes.get(k) for k, v in told.items() if sizes.get(k) != v}
        if odd or set(self.layer_types) != {"latent_attention"} or self.dense_layers >= len(self.layer_types):
            raise ValueError(f"not a joyai_llm_flash configuration: {odd or self.layer_types}")

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


def layer_shapes(sz: Sizes, at: str, dense: bool) -> Dict[str, tuple]:
    d, h = sz.hidden, sz.heads
    shapes = {
        at + "attn_norm/scale": (d,), at + "ffn_norm/scale": (d,),
        at + "attn/wq_a": (d, sz.q_rank), at + "attn/q_a_norm": (sz.q_rank,),
        at + "attn/wq_b": (sz.q_rank, h * (sz.nope + sz.rope)),
        at + "attn/wkv_a": (d, sz.kv_rank + sz.rope), at + "attn/kv_a_norm": (sz.kv_rank,),
        at + "attn/wkv_b": (sz.kv_rank, h * (sz.nope + sz.v_head)), at + "attn/wo": (h * sz.v_head, d),
    }
    if dense:
        f = sz.dense_width
        shapes.update({at + "ffn/w1": (d, f), at + "ffn/w3": (d, f), at + "ffn/w2": (f, d)})
    else:
        f, e = sz.expert_width, sz.held
        shapes.update({
            at + "router/kernel": (d, sz.experts),
            at + "experts/w1": (e, d, f), at + "experts/w3": (e, d, f), at + "experts/w2": (e, f, d),
            at + "shared/w1": (d, f), at + "shared/w3": (d, f), at + "shared/w2": (f, d),
        })
    return shapes


def param_shapes(sz: Sizes) -> Dict[str, tuple]:
    """Leaf name -> shape: the program's gradient leaves, "/"-joined."""
    d = sz.hidden
    shapes = {"embed/table": (sz.vocab, d)}
    for i in range(len(sz.layer_types)):
        shapes.update(layer_shapes(sz, f"layers_{i}/", i < sz.dense_layers))
    shapes["final_norm/scale"] = (d,)
    shapes["head/kernel"] = (d, sz.vocab)
    shapes.update({MTP + "enorm/scale": (d,), MTP + "hnorm/scale": (d,), MTP + "eh_proj/kernel": (2 * d, d)})
    shapes.update(layer_shapes(sz, MTP + "block/", False))
    shapes[MTP + "final_norm/scale"] = (d,)
    return shapes


def init_params(sz: Sizes, key) -> Params:
    """Norms at one; every matrix N(0, 1 / its fan-in), so that activations
    and logits are of order one at every width; the embedding's rows N(0, 1)
    (there is no multiplier to bring them there)."""
    shapes = param_shapes(sz)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if len(shape) == 1:
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            fan_in = 1 if name == "embed/table" else shape[-2]
            out[name] = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
    return out


def init_adam(params: Params) -> Dict[str, Any]:
    """Adam's moments, and beside them the balance bias of every expert
    layer (`layers_<i>`, and `mtp` for the MTP layer), which the harness
    hands back to each step unopened."""
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    bias = {k.split("/")[0]: jnp.zeros((v.shape[1],), jnp.float32) for k, v in params.items() if k.endswith("router/kernel")}
    return {"mu": zeros, "nu": dict(zeros), "router_bias": bias}


# -------------------------------------------------------------- layers


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary_interleaved(x, theta):
    """x: [B, T, heads, r]; pairs (2i, 2i + 1) turned by position * theta^(-2i/r)."""
    t, r = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


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


def attention(qh, kh, vh, q):
    """Causal softmax attention, a block of queries at a time against all
    keys, scores q.k / sqrt(q's width). qh, kh: [B, T, H, dq]; vh: [B, T, H,
    dv]. Returns [B, T, H * dv]."""
    b, t, h, dq = qh.shape
    dv = vh.shape[-1]
    block = min(QUERY_BLOCK, t)
    assert t % block == 0, (t, block)
    kt, vt = jnp.transpose(kh, (0, 2, 1, 3)), jnp.transpose(vh, (0, 2, 1, 3))  # [B, H, T, d]
    keys = jnp.arange(t)

    @jax.checkpoint
    def one_block(args):
        qb, start = args  # [B, H, block, dq]
        scores = jnp.einsum("bhqd,bhtd->bhqt", q(qb), q(kt), precision=HI) / jnp.sqrt(float(dq))
        seen = keys[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqt,bhtd->bhqd", q(p), q(vt), precision=HI)

    blocks = jnp.moveaxis(jnp.transpose(qh, (0, 2, 1, 3)).reshape(b, h, t // block, block, dq), 2, 0)
    out = lax.map(one_block, (blocks, jnp.arange(0, t, block)))  # [T/block, B, H, block, dv]
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, t, dv)
    return jnp.transpose(out, (0, 2, 1, 3)).reshape(b, t, h * dv)


def latent_attention(p: Params, at: str, x, sz: Sizes, q):
    """The mixer of a layer over its normed input x [B, T, D]."""
    b, t, _ = x.shape
    h, nope, rope = sz.heads, sz.nope, sz.rope
    c_q = rms_norm(_mm(x, p[at + "attn/wq_a"], q), p[at + "attn/q_a_norm"], sz.eps)
    qh = _mm(c_q, p[at + "attn/wq_b"], q).reshape(b, t, h, nope + rope)
    kva = _mm(x, p[at + "attn/wkv_a"], q)
    c_kv, k_pe = kva[..., : sz.kv_rank], kva[..., sz.kv_rank :]
    kv = _mm(rms_norm(c_kv, p[at + "attn/kv_a_norm"], sz.eps), p[at + "attn/wkv_b"], q).reshape(b, t, h, nope + sz.v_head)
    q_pe = rotary_interleaved(qh[..., nope:], sz.theta)
    k_pe = jnp.broadcast_to(rotary_interleaved(k_pe.reshape(b, t, 1, rope), sz.theta), (b, t, h, rope))
    qh = jnp.concatenate([qh[..., :nope], q_pe], axis=-1)
    kh = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    return _mm(attention(qh, kh, kv[..., nope:], q), p[at + "attn/wo"], q)


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


def layer(p: Params, at: str, dense: bool, bias, x, sz: Sizes, q):
    """One layer (`at` its leaves' prefix); with its counts by expert and
    its pairs held here where it is an expert layer."""
    b, t, d = x.shape

    @jax.checkpoint  # the mixer's own: built again only once the feed-forward half of the backward pass is gone
    def mixer(p, x):
        return latent_attention(p, at, rms_norm(x, p[at + "attn_norm/scale"], sz.eps), sz, q)

    x = x + mixer(p, x)
    h = rms_norm(x, p[at + "ffn_norm/scale"], sz.eps)
    flat = h.reshape(b * t, d)
    if dense:
        ffn = by_token_blocks(lambda a: swiglu(a[0], p[at + "ffn/w1"], p[at + "ffn/w3"], p[at + "ffn/w2"], q), (flat,))
        return x + ffn.reshape(b, t, d), None
    chosen, weights, counts = route(flat, p[at + "router/kernel"], bias, sz)
    routed = held_experts(flat, chosen, weights, p[at + "experts/w1"], p[at + "experts/w3"], p[at + "experts/w2"], sz, q)
    shared = swiglu(h, p[at + "shared/w1"], p[at + "shared/w3"], p[at + "shared/w2"], q)
    here = (chosen >= sz.first) & (chosen < sz.first + sz.held)
    return x + shared + routed.reshape(b, t, d), (counts, jnp.sum(here).astype(jnp.float32))


def head_loss(p: Params, norm: str, x, tokens, ahead: int, sz: Sizes, q):
    """Mean cross-entropy of the token `ahead` positions on over every
    position of a row that has one, the logits N(x) W_head (`norm` the
    leaf of N's weight) a block of tokens at a time."""
    b, t, d = x.shape
    x = rms_norm(x, p[norm], sz.eps).reshape(b * t, d)
    target = jnp.concatenate([tokens[:, ahead:], jnp.zeros((b, ahead), tokens.dtype)], axis=1).reshape(b * t)
    counted = jnp.broadcast_to(jnp.arange(t) < t - ahead, (b, t)).reshape(b * t)

    def nll(args):
        xs, ys, ws = args
        logp = jax.nn.log_softmax(_mm(xs, p["head/kernel"], q), axis=-1)
        picked = jnp.take_along_axis(logp, ys[:, None], axis=1)[:, 0]
        return -jnp.sum(jnp.where(ws, picked, 0.0))

    return jnp.sum(by_token_blocks(nll, (x, target, counted))) / (b * (t - ahead))


def _own(p: Params, prefix: str) -> Params:
    # a layer is given its own leaves: the whole tree would come back as a gradient of zeros a layer
    return {k: v for k, v in p.items() if k.startswith(prefix)}


def loss_fn(p: Params, bias: Dict[str, jnp.ndarray], tokens, sz: Sizes, q):
    x = p["embed/table"][tokens]
    counts, pairs = {}, 0.0
    for i in range(len(sz.layer_types)):
        name = f"layers_{i}"
        step = jax.checkpoint(lambda p, b, x, i=i: layer(p, f"layers_{i}/", i < sz.dense_layers, b, x, sz, q))
        x, routed = step(_own(p, name + "/"), bias.get(name), x)
        if routed is not None:
            counts[name], pairs = routed[0], pairs + routed[1]
    main = head_loss({k: p[k] for k in ("final_norm/scale", "head/kernel")}, "final_norm/scale", x, tokens, 1, sz, q)
    # the multi-token-prediction module over the trunk's last output
    b = tokens.shape[0]
    following = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    e = rms_norm(p["embed/table"][following], p[MTP + "enorm/scale"], sz.eps)
    h = _mm(jnp.concatenate([e, rms_norm(x, p[MTP + "hnorm/scale"], sz.eps)], axis=-1), p[MTP + "eh_proj/kernel"], q)
    step = jax.checkpoint(lambda p, b, x: layer(p, MTP + "block/", False, b, x, sz, q))
    h, routed = step(_own(p, MTP + "block/"), bias["mtp"], h)
    counts["mtp"], pairs = routed[0], pairs + routed[1]
    further = head_loss({k: p[k] for k in (MTP + "final_norm/scale", "head/kernel")}, MTP + "final_norm/scale", h, tokens, 2, sz, q)
    return main + sz.mtp_weight * further, (main, further, counts, pairs)


def train_step(params: Params, adam, batch, rng, step, sz: Sizes, precision: str = "float32"):
    """One step: (params, adam, losses, grad) after the update; `grad` is the
    gradient as Adam gets it, the L2 term added. `rng` is not used: the model
    samples nothing. `adam` carries the balance bias beside the moments."""
    q = make_rounding(precision)
    bias = adam.get("router_bias") or {
        name: jnp.zeros((sz.experts,), jnp.float32) for name in [f"layers_{i}" for i in sz.expert_layers()] + ["mtp"]
    }
    (loss, (main, further, counts, pairs)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, bias, batch["tokens"], sz, q
    )
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
    parts = {"loss": loss, "nll_loss": main, "mtp_loss": further, "expert_assignments": pairs}
    return new_p, {"mu": mu, "nu": nu, "router_bias": new_bias}, parts, seen


def leaf_norms(tree: Params) -> Dict[str, jnp.ndarray]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


# ----------------------------------------- what the cell's readers call


def visible_pairs(t: int) -> int:
    """(query, key) pairs of a causal row of t tokens."""
    return t * (t + 1) // 2


def attention_layers(sz: Sizes) -> int:
    """Layers that run the attention function: the trunk's and the MTP layer."""
    return len(sz.layer_types) + 1


def train_flops_per_image(sizes: Dict[str, Any]) -> float:
    """FLOPs a sample (one packed row) NEEDS, forward and backward, from
    shapes alone: 6 a token for every matrix-product parameter a token meets
    (the latent projections, the dense feed-forward, the router, the shared
    expert, the routed experts at their expected share here, top_k * held /
    experts assignments a token and layer, the MTP's projection and layer,
    both passes through the head), and 6 * (2 (nope + rope) + 2 v_head) a
    visible (query, key) pair and head for the attention's two products. No
    recomputation, never what the program executes; the embedding is a gather."""
    sz = Sizes(sizes, 1)
    d, h = sz.hidden, sz.heads
    mla = d * sz.q_rank + sz.q_rank * h * (sz.nope + sz.rope) + d * (sz.kv_rank + sz.rope)
    mla += sz.kv_rank * h * (sz.nope + sz.v_head) + h * sz.v_head * d
    expert = 3 * d * sz.expert_width
    moe = d * sz.experts + expert * (1 + sz.top_k * sz.held / sz.experts)
    per_token = sum(mla + (3 * d * sz.dense_width if i < sz.dense_layers else moe) for i in range(len(sz.layer_types)))
    per_token += 2 * d * d + mla + moe  # the MTP module's projection and layer
    per_token += 2 * d * sz.vocab  # the head, for the next token and for the one after
    core = 2 * (sz.nope + sz.rope) + 2 * sz.v_head
    return 6.0 * per_token * sz.seq_len + 3.0 * core * h * visible_pairs(sz.seq_len) * attention_layers(sz)


def attention_roofline_seconds(sizes: Dict[str, Any], batch: int, flops_per_s: float, bytes_per_s: float) -> float:
    """The least time a chip needs for the attention function of one step
    (the projections apart), the trunk's layers' and the MTP layer's: per
    layer and pass the larger of FLOPs over peak (forward 2 (nope + rope) + 2
    v_head a visible pair and head, backward twice that) and bytes over
    bandwidth (q and k at nope + rope, v and the output at v_head, once,
    bfloat16; backward their cotangents too)."""
    sz = Sizes(sizes, batch)
    tokens = batch * sz.seq_len
    moved = 2.0 * tokens * sz.heads * (2 * (sz.nope + sz.rope) + 2 * sz.v_head)
    flops = (2 * (sz.nope + sz.rope) + 2 * sz.v_head) * batch * visible_pairs(sz.seq_len) * sz.heads
    a_layer = max(flops / flops_per_s, moved / bytes_per_s) + max(2 * flops / flops_per_s, 2 * moved / bytes_per_s)
    return a_layer * attention_layers(sz)


def expert_mm_roofline_seconds(sizes: Dict[str, Any], assignments: float, flops_per_s: float, bytes_per_s: float) -> float:
    """The least time for the grouped products of one step that computes
    `assignments` token-expert pairs (all expert layers and the MTP layer
    together): forward 2 * 3 * hidden * expert width FLOPs a pair, backward
    twice that; bytes a pass: the held experts' weights once and the pairs'
    rows in, between the products and out (bfloat16), the backward reading
    and writing twice that."""
    sz = Sizes(sizes, 1)
    weights = 2.0 * (len(sz.expert_layers()) + 1) * sz.held * 3 * sz.hidden * sz.expert_width
    rows = 2.0 * assignments * (2 * sz.hidden + 3 * sz.expert_width)
    flops = 2.0 * 3 * sz.hidden * sz.expert_width * assignments
    return max(flops / flops_per_s, (weights + rows) / bytes_per_s) + max(2 * flops / flops_per_s, 2 * (weights + rows) / bytes_per_s)
