"""The model's side of `qwen3_next_ep16`: the plain reference of one training
step of a `qwen3_next` decoder (Qwen3-Next-80B-A3B's `model_type`), and what
else a `reference` module states (perf/harness.py has the list).

Plain `jax.numpy`, float32, every matrix product at `Precision.HIGHEST`, no
kernel, no cache, no chunks, no sorting by expert; it imports nothing of the
program. The equations are those of the public `modeling_qwen3_next.py`
(`torch_recurrent_gated_delta_rule` for the linear layers):

  N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)        (zero-centred weight)
  x0 = E[t]                                          (no multiplier)
  a = x + Mixer(N(x));  y = a + Experts(N(a))        (pre-norm, no biases)

  Mixer, `linear_attention` (Gated DeltaNet): W_qkvz gives, a key head's
        group together, its q and k (dk each), its value heads' v and z (dv
        each; value head j reads key head j // (H / KH)); W_ba gives b and a,
        one each a value head. (q', k', v') = silu(conv(q | k | v)), a
        depthwise causal convolution of `conv` taps, no bias. beta =
        sigmoid(b); g = -exp(A_log) * softplus(a + dt_bias). q = q' / |q'| /
        sqrt(dk), k = k' / |k'| (|x| = sqrt(sum x^2 + 1e-6)). A state S [dk,
        dv] a value head, nought at the row's start, TOKEN BY TOKEN:
            S- = exp(g_t) S;  S = S- + k_t (x) beta_t (v_t - S-^T k_t);  o_t = S^T q_t
        y = (o * rsqrt(mean(o^2) + eps) * w_n * silu(z)) W_o   (the norm a
        head over its dv, w_n plain).
  Mixer, `full_attention`: W_q gives a head's q, then its gate (head size
        each); q and k pass a norm N a head (weights of their own), then the
        rotary embedding over the leading `rotary_fraction` of the head,
        pairs (i, i + r/2), positions 0..T-1 along the row; causal softmax
        attention, scores q.k / sqrt(d), each KV head serving H / KV query
        heads; y = (o * sigmoid(gate)) W_o.
  Experts: p = softmax(W_r h) over ALL experts; chosen = top-k of p; weights
        = chosen p / their sum; out = sigmoid(h w_g) * shared SwiGLU + sum
        over the chosen experts THAT ARE HELD (first_expert .. first_expert +
        held - 1) of weight * that expert's SwiGLU. What the others would add
        is left out. No balance bias, no auxiliary loss (*assumed*).
  loss = mean cross-entropy of the next token over every position of a row
        but its last, logits N(x_L) W_head over the vocabulary rows held.

A packed row is one causal stream with one state (*assumed*: no mask and no
state reset between documents). The checkpoint's multi-token-prediction
module is left out (no key of the catalog row describes it).

Departures from plainness, each to fit beside the reference's own state (16 B
a parameter) and none a change of the arithmetic: a layer is recomputed at a
time in the backward pass, its mixer apart from its experts; the recurrence
runs in blocks of `STATE_BLOCK` tokens inside blocks of as many of those,
each recomputed there (a step's states for a whole row of 16,384 tokens would
be 34 GB a layer); the projections and the convolution before it run a key
head's group at a time, the gated norm and output product after it a block
of tokens at a time; the full layer's mixer a KV head's group at a time,
queries a block at a time against all keys; the held experts one after
another over all tokens; the head and the loss a block of tokens at a time.

`precision` selects what the controls of `correct` need: "float32" is the
reference; "bfloat16" and "float8" round the operands of every matrix
product to that type's precision (the attention's two and the recurrence's
three among them); "bfloat16_state" is float32 but for the recurrence's
state, rounded to bfloat16 after every token (a probe: it reads what the
program's own bfloat16 operands read, and passes).
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
# (summed over the layers): a flipped top-k choice moves it by one
LOSS_PARTS = ("nll_loss", "expert_assignments")
# the first gradient's norms by mechanism: the embedding's rows and layer 0's
# delta-rule mixer (upstream of every top-k choice of the forward pass), and
# the full-attention layer's mixer
LEAF_NUMBERS = {
    "embed_grad_gap": ("embed/",), "delta0_grad_gap": ("layers_0/linear/",), "attn3_grad_gap": ("layers_3/attn/",),
}
SCOPE_PREFIX = "frcnn."  # of the step program's stage scopes (`telemetry/stages.py`)
# values of `train_step`'s `precision` beside the controls', read with the limits and not judged:
# the recurrence's state rounded to bfloat16 after every token reads what the program reads
# (PERF.md section 6, PR 33), so no limit can be held against it
PROBE_PRECISIONS = ("bfloat16_state",)
QUERY_BLOCK = 128
TOKEN_BLOCK = 2048
STATE_BLOCK = 32  # tokens of the recurrence between two kept states, and such blocks between two more
DELTA_CHUNK = 64  # the chunk of the chunked form whose work `delta_rule_roofline_seconds` counts


class Sizes:
    """The configuration's sizes, read from its JSON `sizes` block."""

    def __init__(self, sizes: Dict[str, Any], batch: int) -> None:
        g = sizes.__getitem__
        self.batch = int(batch)
        self.seq_len = int(g("data.seq_len"))
        self.vocab = int(g("lm.vocab_rows"))
        self.hidden = int(g("lm.hidden_size"))
        self.heads, self.kv_heads, self.head = int(g("lm.num_heads")), int(g("lm.num_kv_heads")), int(g("lm.head_size"))
        self.rotary = int(self.head * float(g("lm.rotary_fraction")))
        self.layer_types = tuple(g("lm.layer_types"))
        self.key_heads, self.value_heads = int(g("lm.linear_num_key_heads")), int(g("lm.linear_num_value_heads"))
        self.dk, self.dv = int(g("lm.linear_key_head_dim")), int(g("lm.linear_value_head_dim"))
        self.conv = int(g("lm.linear_conv_kernel"))
        self.expert_width = int(g("lm.expert_width"))
        self.experts, self.top_k = int(g("lm.num_experts")), int(g("lm.experts_per_token"))
        self.theta, self.eps = float(g("lm.rope_theta")), float(g("lm.rms_norm_eps"))
        self.held, self.first = int(g("lm.experts_held")), int(g("lm.first_expert"))
        self.lr, self.weight_decay = float(g("train.lr")), float(g("train.weight_decay"))
        # what this reference is: a size file that says otherwise is another model's
        told = {
            "lm.router_score": "softmax", "lm.qk_norm": True, "lm.attention_gate": True, "lm.norm_zero_centred": True,
            "lm.shared_expert_gate": True, "lm.embed_scale": False, "lm.num_dense_layers": 0,
        }
        odd = {k: sizes.get(k) for k, v in told.items() if sizes.get(k) != v}
        if odd:
            raise ValueError(f"not a qwen3_next configuration: {odd}")

    def linear_layers(self):
        return [i for i, kind in enumerate(self.layer_types) if kind == "linear_attention"]


# ----------------------------------------------------------- precision


def _straight_through(x, qx):
    return x + lax.stop_gradient(qx - x)


def make_rounding(precision: str):
    """Operand rounding for matrix products, with `lax.reduce_precision` (the
    TPU compiler removes a plain `astype` round trip)."""
    if precision in ("float32", "bfloat16_state"):
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
    d, hd, f, e = sz.hidden, sz.head, sz.expert_width, sz.held
    keys, values = sz.key_heads * sz.dk, sz.value_heads * sz.dv
    shapes = {"embed/table": (sz.vocab, d)}
    for i, kind in enumerate(sz.layer_types):
        at = f"layers_{i}/"
        shapes.update({at + "attn_norm/scale": (d,), at + "ffn_norm/scale": (d,)})
        if kind == "linear_attention":
            shapes.update({
                at + "linear/in_qkvz": (d, 2 * keys + 2 * values), at + "linear/in_ba": (d, 2 * sz.value_heads),
                at + "linear/conv": (2 * keys + values, sz.conv), at + "linear/a_log": (sz.value_heads,),
                at + "linear/dt_bias": (sz.value_heads,), at + "linear/norm": (sz.dv,), at + "linear/out": (values, d),
            })
        else:
            shapes.update({
                at + "attn/wq": (d, sz.heads * 2 * hd), at + "attn/wk": (d, sz.kv_heads * hd),
                at + "attn/wv": (d, sz.kv_heads * hd), at + "attn/wo": (sz.heads * hd, d),
                at + "attn/q_norm": (hd,), at + "attn/k_norm": (hd,),
            })
        shapes.update({
            at + "router/kernel": (d, sz.experts), at + "shared_gate/kernel": (d, 1),
            at + "experts/w1": (e, d, f), at + "experts/w3": (e, d, f), at + "experts/w2": (e, f, d),
            at + "shared/w1": (d, f), at + "shared/w3": (d, f), at + "shared/w2": (f, d),
        })
    shapes["final_norm/scale"] = (d,)
    shapes["head/kernel"] = (d, sz.vocab)
    return shapes


def init_params(sz: Sizes, key) -> Params:
    """Every matrix N(0, 1 / its fan-in), so that activations and logits are
    of order one at every width; the embedding's rows N(0, 1) (there is no
    multiplier to bring them there); the convolution's taps N(0, 1 / taps).
    Zero-centred norm weights nought, the gated norm's at one. `A_log = log
    U(1, 16)`; `dt_bias` the inverse softplus of a step drawn log-uniformly
    from 0.001 to 0.1 (the published layer's own trainer draws it so): a head
    then keeps from a fifth to all but a thousandth of its state a token, and
    the comparison sees the state carried over thousands of tokens. (At
    `dt_bias` = 1 the mean `exp(g)` is 0.003: every state is forgotten
    within two tokens and a wrong carry would pass.)"""
    shapes = param_shapes(sz)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        leaf = name.split("/")[-1]
        if leaf == "a_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif leaf == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif len(shape) == 1:
            out[name] = jnp.ones(shape, jnp.float32) if name.endswith("linear/norm") else jnp.zeros(shape, jnp.float32)
        else:
            fan_in = 1 if name == "embed/table" else shape[-1] if leaf == "conv" else shape[-2]
            out[name] = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(float(fan_in))
    return out


def init_adam(params: Params) -> Dict[str, Any]:
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"mu": zeros, "nu": dict(zeros)}


# -------------------------------------------------------------- layers


def rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rotary(x, theta, r):
    """x: [B, T, heads, d]; the leading r of a head turned, the rest as it is."""
    t = x.shape[1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b, rest = x[..., : r // 2], x[..., r // 2 : r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


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
    keys. qh: [B, T, H, d]; kh, vh: [B, T, KV, d]."""
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
        seen = keys[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqt,bktd->bkgqd", q(p), q(vt), precision=HI)

    blocks = jnp.moveaxis(qg.reshape(b, kv, h // kv, t // block, block, d), 3, 0)
    out = lax.map(one_block, (blocks, jnp.arange(0, t, block)))  # [T/block, B, KV, G, block, d]
    out = jnp.moveaxis(out, 0, 3).reshape(b, kv, h // kv, t, d)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, t, h, d)


def full_attention_mixer(p: Params, at: str, h, sz: Sizes, q):
    """A KV head's group of query heads at a time (its own columns of W_q,
    W_k, W_v, its own rows of W_o), each group recomputed in the backward
    pass; the groups' products with W_o add up."""
    b, t, d = h.shape
    group = sz.heads // sz.kv_heads
    by_kv = lambda w, width: jnp.moveaxis(w.reshape(d, sz.kv_heads, width), 1, 0)
    wo = p[at + "attn/wo"].reshape(sz.kv_heads, group * sz.head, d)

    @jax.checkpoint
    def one_group(y, w):
        wq, wk, wv, wo = w
        qg = _mm(h, wq, q).reshape(b, t, group, 2 * sz.head)
        qh, gate = qg[..., : sz.head], qg[..., sz.head :]
        kh, vh = _mm(h, wk, q).reshape(b, t, 1, sz.head), _mm(h, wv, q).reshape(b, t, 1, sz.head)
        qh = rotary(rms_norm(qh, 1.0 + p[at + "attn/q_norm"], sz.eps), sz.theta, sz.rotary)
        kh = rotary(rms_norm(kh, 1.0 + p[at + "attn/k_norm"], sz.eps), sz.theta, sz.rotary)
        o = attention(qh, kh, vh, q) * jax.nn.sigmoid(gate)
        return y + _mm(o.reshape(b, t, group * sz.head), wo, q), None

    weights = (by_kv(p[at + "attn/wq"], group * 2 * sz.head), by_kv(p[at + "attn/wk"], sz.head), by_kv(p[at + "attn/wv"], sz.head), wo)
    return lax.scan(one_group, jnp.zeros_like(h), weights)[0]


def gated_delta_recurrence(qh, kh, vh, g, beta, q=lambda x: x, round_state=False):
    """The recurrence, token by token. qh, kh: [B, T, KH, dk]; vh: [B, T, H,
    dv] (value head j reads key head j // (H / KH)); g, beta: [B, T, H].
    Returns o [B, T, H, dv]."""
    b, t, _, dk = qh.shape
    h = vh.shape[2]
    rep = h // qh.shape[2]

    def token(s, x):
        qt, kt, vt, gt, bt = x
        qt, kt = jnp.repeat(qt, rep, axis=1), jnp.repeat(kt, rep, axis=1)
        s = jnp.exp(gt)[..., None, None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhde,bhd->bhe", q(s), q(kt), precision=HI))
        s = s + jnp.einsum("bhd,bhe->bhde", q(kt), q(u), precision=HI)
        if round_state:
            s = lax.reduce_precision(s, 8, 7)
        return s, jnp.einsum("bhde,bhd->bhe", q(s), q(qt), precision=HI)

    # blocks of STATE_BLOCK tokens inside blocks of STATE_BLOCK times as many, each recomputed
    # in the backward pass from the state at its start
    inner = STATE_BLOCK if t % STATE_BLOCK == 0 else t
    outer = inner * STATE_BLOCK if t % (inner * STATE_BLOCK) == 0 else inner
    by_block = lambda x: jnp.moveaxis(x, 1, 0).reshape((t // outer, outer // inner, inner) + x.shape[:1] + x.shape[2:])
    first = jnp.zeros((b, h, dk, vh.shape[-1]), jnp.float32)
    tokens = jax.checkpoint(lambda s, xs: lax.scan(token, s, xs))
    blocks = jax.checkpoint(lambda s, xs: lax.scan(tokens, s, xs))
    _, o = lax.scan(blocks, first, tuple(map(by_block, (qh, kh, vh, g, beta))))
    return jnp.moveaxis(o.reshape((t,) + o.shape[3:]), 0, 1)


def unit_length(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def linear_attention_mixer(p: Params, at: str, h, sz: Sizes, q, round_state=False):
    b, t, d = h.shape
    kh, vh, dk, dv = sz.key_heads, sz.value_heads, sz.dk, sz.dv
    rep = vh // kh
    # the projection's columns, a key head's group together: q, k, its value heads' v, their z
    w = jnp.moveaxis(p[at + "linear/in_qkvz"].reshape(d, kh, 2 * dk + 2 * rep * dv), 1, 0)
    wqkv, wz = w[..., : 2 * dk + rep * dv], jnp.moveaxis(w[..., 2 * dk + rep * dv :], 0, 1).reshape(d, vh * dv)
    wba = jnp.moveaxis(p[at + "linear/in_ba"].reshape(d, kh, 2 * rep), 1, 0)
    # the convolution's channels are all q, then all k, then all v: a group's own, in the group's order
    taps = p[at + "linear/conv"]
    keys = kh * dk
    taps = jnp.concatenate([
        taps[:keys].reshape(kh, dk, -1), taps[keys : 2 * keys].reshape(kh, dk, -1), taps[2 * keys :].reshape(kh, rep * dv, -1),
    ], axis=1)

    @jax.checkpoint
    def one_group(w):
        """What precedes the recurrence, for one key head and its value heads
        (the convolution is depthwise and the norms a head's own)."""
        wqkv, wba, taps, a_log, dt_bias = w
        mixed = jax.nn.silu(lax.conv_general_dilated(
            _mm(h, wqkv, q), taps.T[:, None, :], (1,), [(sz.conv - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=taps.shape[0], precision=HI,
        ))
        qh, kh_, vh_ = jnp.split(mixed, [dk, 2 * dk], axis=-1)
        ba = _mm(h, wba, q)
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., rep:] + dt_bias)
        return unit_length(qh) / jnp.sqrt(float(dk)), unit_length(kh_), vh_.reshape(b, t, rep, dv), g, jax.nn.sigmoid(ba[..., :rep])

    by_group = lambda x: x.reshape(kh, rep)
    qh, kh_, vh_, g, beta = lax.map(one_group, (wqkv, wba, taps, by_group(p[at + "linear/a_log"]), by_group(p[at + "linear/dt_bias"])))
    heads = lambda x: jnp.moveaxis(x, 0, 2)  # [groups, B, T, ...] -> [B, T, groups, ...]
    qh, kh_ = heads(qh), heads(kh_)
    vh_, g, beta = heads(vh_).reshape(b, t, vh, dv), heads(g).reshape(b, t, vh), heads(beta).reshape(b, t, vh)
    o = gated_delta_recurrence(qh, kh_, vh_, g, beta, q, round_state)

    def after(args):
        o, h = args  # a block of tokens: [n, H, dv], [n, D]
        z = _mm(h, wz, q).reshape(-1, vh, dv)
        gated = rms_norm(o, p[at + "linear/norm"], sz.eps) * jax.nn.silu(z)
        return _mm(gated.reshape(-1, vh * dv), p[at + "linear/out"], q)

    return by_token_blocks(after, (o.reshape(b * t, vh, dv), h.reshape(b * t, d))).reshape(b, t, d)


def route(h, kernel, sz: Sizes):
    """h: [N, D]. (chosen [N, k], weights [N, k])."""
    probs = jax.nn.softmax(jnp.dot(h, kernel, precision=HI), axis=-1)
    _, chosen = lax.top_k(probs, sz.top_k)
    picked = jnp.take_along_axis(probs, chosen, axis=1)
    return chosen, picked / jnp.sum(picked, axis=1, keepdims=True)


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


def layer(p: Params, i: int, x, sz: Sizes, q, round_state=False):
    at = f"layers_{i}/"
    b, t, d = x.shape

    @jax.checkpoint  # the mixer's own: built again only once the experts' half of the backward pass is gone
    def mixer(p, x):
        h = rms_norm(x, 1.0 + p[at + "attn_norm/scale"], sz.eps)
        if sz.layer_types[i] == "linear_attention":
            return linear_attention_mixer(p, at, h, sz, q, round_state)
        return full_attention_mixer(p, at, h, sz, q)

    x = x + mixer(p, x)
    h = rms_norm(x, 1.0 + p[at + "ffn_norm/scale"], sz.eps)
    flat = h.reshape(b * t, d)
    chosen, weights = route(flat, p[at + "router/kernel"], sz)
    routed = held_experts(flat, chosen, weights, p[at + "experts/w1"], p[at + "experts/w3"], p[at + "experts/w2"], sz, q)
    shared = swiglu(h, p[at + "shared/w1"], p[at + "shared/w3"], p[at + "shared/w2"], q)
    shared = jax.nn.sigmoid(_mm(h, p[at + "shared_gate/kernel"], q)) * shared
    here = (chosen >= sz.first) & (chosen < sz.first + sz.held)
    return x + shared + routed.reshape(b, t, d), jnp.sum(here).astype(jnp.float32)


def head_loss(p: Params, x, tokens, sz: Sizes, q):
    """Mean cross-entropy of the next token over every position of a row but
    its last, the logits a block of tokens at a time."""
    b, t, d = x.shape
    x = rms_norm(x, 1.0 + p["final_norm/scale"], sz.eps).reshape(b * t, d)
    target = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1).reshape(b * t)
    counted = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t)).reshape(b * t)

    def nll(args):
        xs, ys, ws = args
        logp = jax.nn.log_softmax(_mm(xs, p["head/kernel"], q), axis=-1)
        picked = jnp.take_along_axis(logp, ys[:, None], axis=1)[:, 0]
        return -jnp.sum(jnp.where(ws, picked, 0.0))

    return jnp.sum(by_token_blocks(nll, (x, target, counted))) / (b * (t - 1))


def loss_fn(p: Params, tokens, sz: Sizes, q, round_state=False):
    x = p["embed/table"][tokens]
    pairs = 0.0
    for i in range(len(sz.layer_types)):
        name = f"layers_{i}"
        # a layer is given its own leaves: the whole tree would come back as a gradient of zeros a layer
        own = {k: v for k, v in p.items() if k.startswith(name + "/")}
        x, here = jax.checkpoint(lambda p, x, i=i: layer(p, i, x, sz, q, round_state))(own, x)
        pairs = pairs + here
    return head_loss(p, x, tokens, sz, q), pairs


def train_step(params: Params, adam, batch, rng, step, sz: Sizes, precision: str = "float32"):
    """One step: (params, adam, losses, grad) after the update; `grad` is the
    gradient as Adam gets it, the L2 term added. `rng` is not used: the model
    samples nothing."""
    q = make_rounding(precision)
    (loss, pairs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch["tokens"], sz, q, precision == "bfloat16_state"
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
    parts = {"loss": loss, "nll_loss": loss, "expert_assignments": pairs}
    return new_p, {"mu": mu, "nu": nu}, parts, seen


def leaf_norms(tree: Params) -> Dict[str, jnp.ndarray]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


# ----------------------------------------- what the cell's readers call


def visible_pairs(t: int) -> int:
    """(query, key) pairs of a causal row of t tokens."""
    return t * (t + 1) // 2


def delta_rule_flops_per_token(sz: Sizes) -> float:
    """Forward FLOPs a token and value head of the chunked gated delta rule
    at chunks of `DELTA_CHUNK`: the state's three products (W S, Q S, K^T
    V_new: 2 dk dv each), K K^T and Q K^T (2 C dk each), the masked product
    with V_new (2 C dv), and the unit triangular system for U and W by
    substitution (C (dk + dv))."""
    c = DELTA_CHUNK
    return 6.0 * sz.dk * sz.dv + c * (5.0 * sz.dk + 3.0 * sz.dv)


def train_flops_per_image(sizes: Dict[str, Any]) -> float:
    """FLOPs a sample (one packed row) NEEDS, forward and backward, from
    shapes alone: 6 a token for every matrix-product parameter a token meets
    (the routed experts at their expected share here, top_k * held / experts
    assignments a token and layer; the convolution's taps among them), 12 *
    head size a visible (query, key) pair and head for the attention's two
    products, and three times the chunked delta rule's forward work. No
    recomputation, never what the program executes; the embedding is a gather."""
    sz = Sizes(sizes, 1)
    d = sz.hidden
    keys, values = sz.key_heads * sz.dk, sz.value_heads * sz.dv
    linear = d * (2 * keys + 2 * values) + d * 2 * sz.value_heads + (2 * keys + values) * sz.conv + values * d
    full = d * sz.heads * 2 * sz.head + 2 * d * sz.kv_heads * sz.head + sz.heads * sz.head * d
    expert = 3 * d * sz.expert_width
    every = d * sz.experts + d + expert * (1 + sz.top_k * sz.held / sz.experts)
    n_linear = len(sz.linear_layers())
    n_full = len(sz.layer_types) - n_linear
    per_token = n_linear * linear + n_full * full + len(sz.layer_types) * every + d * sz.vocab
    return (
        6.0 * per_token * sz.seq_len
        + 12.0 * sz.head * sz.heads * visible_pairs(sz.seq_len) * n_full
        + 3.0 * delta_rule_flops_per_token(sz) * sz.value_heads * sz.seq_len * n_linear
    )


def attention_roofline_seconds(sizes: Dict[str, Any], batch: int, flops_per_s: float, bytes_per_s: float) -> float:
    """The least time a chip needs for the attention function of one step
    (the projections apart), the full-attention layers': per layer and pass
    the larger of FLOPs over peak (forward 4 * head size a visible pair and
    head, backward 8) and bytes over bandwidth (q, k, v and the output once,
    bfloat16; backward their cotangents too)."""
    sz = Sizes(sizes, batch)
    moved = 2.0 * batch * sz.seq_len * sz.head * (2 * sz.heads + 2 * sz.kv_heads)  # bytes of q, o, k, v
    pairs = batch * visible_pairs(sz.seq_len) * sz.heads
    a_layer = max(4.0 * sz.head * pairs / flops_per_s, moved / bytes_per_s) + max(
        8.0 * sz.head * pairs / flops_per_s, 2.0 * moved / bytes_per_s
    )
    return a_layer * (len(sz.layer_types) - len(sz.linear_layers()))


def delta_rule_roofline_seconds(sizes: Dict[str, Any], batch: int, flops_per_s: float, bytes_per_s: float) -> float:
    """The least time for the gated delta rule of one step (projections,
    convolution and norms apart), the linear-attention layers': per layer and
    pass the larger of the chunked form's FLOPs over peak (backward twice the
    forward) and of bytes over bandwidth: q, k, v and the output once in
    bfloat16, g and beta in float32; backward their cotangents too. It counts
    the work whatever implements it."""
    sz = Sizes(sizes, batch)
    tokens = batch * sz.seq_len
    flops = delta_rule_flops_per_token(sz) * sz.value_heads * tokens
    moved = tokens * (2.0 * (2 * sz.key_heads * sz.dk + 2 * sz.value_heads * sz.dv) + 4.0 * 2 * sz.value_heads)
    a_layer = max(flops / flops_per_s, moved / bytes_per_s) + max(2 * flops / flops_per_s, 2 * moved / bytes_per_s)
    return a_layer * len(sz.linear_layers())


def expert_mm_roofline_seconds(sizes: Dict[str, Any], assignments: float, flops_per_s: float, bytes_per_s: float) -> float:
    """The least time for the grouped products of one step that computes
    `assignments` token-expert pairs (all layers together): forward 2 * 3 *
    hidden * expert width FLOPs a pair, backward twice that; bytes a pass:
    the held experts' weights once and the pairs' rows in, between the
    products and out (bfloat16), the backward reading and writing twice that."""
    sz = Sizes(sizes, 1)
    weights = 2.0 * len(sz.layer_types) * sz.held * 3 * sz.hidden * sz.expert_width
    rows = 2.0 * assignments * (2 * sz.hidden + 3 * sz.expert_width)
    flops = 2.0 * 3 * sz.hidden * sz.expert_width * assignments
    return max(flops / flops_per_s, (weights + rows) / bytes_per_s) + max(2 * flops / flops_per_s, 2 * (weights + rows) / bytes_per_s)
