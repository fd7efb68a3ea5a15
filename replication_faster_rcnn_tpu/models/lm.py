"""The sequence model: a decoder of pre-norm layers whose mixer is, layer by
layer, rotary grouped-query attention (windowed or full), a gated delta-rule
recurrence over a matrix state (`linear_attention`) or multi-head latent
attention (`latent_attention`), with a dense SwiGLU in
the leading layers and a routed expert layer in the others, told which
experts it holds. What else a layer computes (norms on q and k, a rotary
embedding over part of a head, a sigmoid gate on the attention's output, a
norm's weight as `1 + w`, the router's score, a gate on the shared expert,
the embedding's multiplier) is a field of `LMConfig`, each read at one site.

The model is plain functions of a parameter tree (`init`, `losses`): what
`train/train_step.py` asks of a kind of model. Parameters are float32 and
are cast to `model.compute_dtype` where a layer reads them; the router's
scores, every softmax, the norms' statistics, the combine and the loss are
float32. Every layer is under a `jax.checkpoint` that keeps, beside the
layer's inputs, the attention function's own residuals
(`ops.attention.RESIDUAL_NAMES`: the tiles of q, k and v, the output and the
log-sum-exp): the backward pass recomputes the rest of the layer, but runs no
attention forward a second time and builds none of its operands again. That
costs `4 * (num_heads + num_kv_heads) * head_size + 4 * num_heads` bytes a
token and layer whatever the preset (18,560 at the published widths: 1.52 GB
for five layers of 16,384 tokens; a latent layer's q and k are 192 wide and
its v and output 128, 40,960 bytes a token). A delta-rule layer keeps the function's
output and the state at each segment's start (`ops.delta_rule`): its backward
pass runs the recurrence backward only. The head and the loss run over blocks
of tokens, so that no `[tokens, vocabulary]` array outlives its block.

A `latent_attention` layer (`latent_block`, DeepSeek-V2's attention): the
query through a latent, ``c_q = N(h W_qa)``, ``q = c_q W_qb``, a head's q its
part without rotary embedding (`qk_nope_head_dim`) then its part with it
(`qk_rope_head_dim`); keys and values through another, ``[c_kv | k_pe] = h
W_kva``, ``[k_nope | v] = N(c_kv) W_kvb`` a head, and one ``k_pe`` turned by the
rotary embedding and shared by every head's key. The attention runs over the
decompressed heads, scores ``q.k / sqrt(nope + rope)``; what the latents and
their norms cost runs under `frcnn.lm_latent`, nested in `frcnn.lm_attention`.

The multi-token-prediction module (`mtp_loss`, `lm.mtp_layers` 1: DeepSeek-V3's
depth 1): ``h'_i = [N_e(E[t_{i+1}]) | N_h(h_i)] W_eh`` with ``h_i`` the trunk's
last output before its final norm, one layer of the last layer's kind over
``h'`` (its own router, balance bias and held experts), then its own final
norm, the shared head and the cross-entropy of the token after next. The
step's loss is the next token's plus `mtp_weight` times that one; the
gradient flows into the trunk and the shared embedding and head.

A `linear_attention` layer (`delta_block`): one projection gives q, k (key
heads), v and an output gate z (value heads), laid out by key-head group; a
second gives a write strength and a decay a value head. q, k and v pass a
depthwise causal convolution and SiLU; q and k are scaled to unit length (q
also by `1 / sqrt(key size)`); `beta = sigmoid(b)`, `g = -exp(A_log) *
softplus(a + dt_bias)` in float32; `ops.delta_rule.gated_delta_rule` runs the
recurrence from a state of nought at the row's start; the output is normed a
head, gated by `silu(z)` and projected.

The expert layer (`expert_layer`). The router scores ALL `lm.num_experts`
in float32; the chosen are the top `experts_per_token` of score + balance
bias, their weights the chosen scores over their sum times `route_scale`.
This chip holds experts `first_expert .. first_expert + experts_held - 1`:
the token-expert pairs whose expert is held are sorted by expert (one stable
sort), their rows gathered, three grouped products run over the contiguous
groups (`ops/grouped_mm.py`), and the results are added by token, weighted,
in float32. What the experts held elsewhere would add is left out, and that
partial result goes on: on one chip the layer runs without its exchange.
No pair is dropped whatever the routing: the row buffer holds twice the
pairs that even routing sends here, a step whose pairs pass it goes on through
further buffers of that size, up to `tokens * min(experts_per_token,
experts_held)` rows in all, the most the shapes allow. The grouped products
visit only the row tiles that the pairs fill, so the step's time follows the
pairs really routed here. With `experts_fixed_rows` the buffer holds that
many times what even routing sends here and the products visit every row tile
of it (the last held expert's group runs on over the rows past the pairs,
which weigh 0), so a step's work is the same whatever the routing.

With `router_score="softmax"` the chosen are the top of the probabilities
over all experts, their weights the chosen probabilities over their sum, and
there is no bias and no scale.

The balance bias is no parameter: it lives in `TrainState.batch_stats`
(`{"router_bias": {layer: [num_experts]}}`) and moves inside the step by
`load_balance_coeff * sign(mean(c) - c)`, `c` the step's count of tokens
that chose each expert, then is centred on zero. There is no auxiliary loss.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from replication_faster_rcnn_tpu.config import FasterRCNNConfig, LMConfig
from replication_faster_rcnn_tpu.ops.attention import RESIDUAL_NAMES, attention
from replication_faster_rcnn_tpu.ops.grouped_mm import grouped_matmul
from replication_faster_rcnn_tpu.telemetry import stages

Array = jnp.ndarray

BATCH_KEYS = ("tokens",)
# counters of the step's metrics that the trainer writes to its tracer
# (those a configuration's step has: `router_bias_absmax` where there is a balance bias,
# the two of the delta rule where a layer runs it, the multi-token-prediction module's loss where there is one)
COUNTERS = (
    "expert_assignments", "expert_load_max_over_mean", "tokens_dropped", "router_bias_absmax",
    "delta_state_absmax", "delta_decay_mean", "mtp_loss",
)
MTP = "mtp"  # the multi-token-prediction module's subtree, and its balance bias's key
HEAD_BLOCK = 2048  # tokens whose logits are alive at once
USUAL_ROWS = 2  # the experts' usual buffer, in pairs that even routing sends here
# Up to as many buffers the overflow is one `lax.switch` over how many the pairs reach, beyond a loop on
# the device. The switch holds n (n - 1) / 2 copies of a buffer's program: 6 at four buffers, 28 at eight
# (335 s of compiling against 110, an executable too large for a 192 MiB cache). At four buffers the loop
# was read too (PERF.md section 6, PR 33): a step of 0.490-0.500 s against the switch's 0.512, 1.03 GB less
# reserved, a minute less compiling, and another program for that step: a change of its own, not this rule's.
SWITCH_BUFFERS = 4
INIT_STD = 0.02
# what a layer's `jax.checkpoint` keeps for the backward pass, beside the layer's inputs
KEPT = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)
# of `ops.delta_rule.RESIDUAL_NAMES`, what a delta-rule layer keeps: the state at each segment's
# start, the output, the decay and the write strength. Not q, k and v (0.27 GB a layer at the
# published sizes): the convolution's and SiLU's own backward pass builds the projection again
# whether they are kept or not, and what lies between, two norms and a transpose, is cheap
DELTA_KEPT = ("delta_g", "delta_beta", "delta_state", "delta_out")


def layer_name(i: int) -> str:
    return f"layers_{i}"


def is_dense(lm: LMConfig, i: int) -> bool:
    return i < lm.num_dense_layers


# ------------------------------------------------------------ parameters


def _swiglu_shapes(d: int, f: int, lead: Tuple[int, ...] = ()) -> Dict[str, Tuple[int, ...]]:
    return {"w1": lead + (d, f), "w3": lead + (d, f), "w2": lead + (f, d)}


def is_linear(lm: LMConfig, i: int) -> bool:
    return lm.layer_types[i] == "linear_attention"


def mtp_like(lm: LMConfig) -> int:
    """The layer whose kind the multi-token-prediction module's layer has:
    the last (DeepSeek-V3's module is a layer as its last layer is)."""
    return len(lm.layer_types) - 1


def kept(lm: LMConfig):
    """The policy of a layer's `jax.checkpoint`: `KEPT`, and where a layer
    runs the delta rule, `DELTA_KEPT` of that function's residuals too."""
    if KEPT is None or "linear_attention" not in lm.layer_types:
        return KEPT
    return jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES, *DELTA_KEPT)


def _attention_shapes(lm: LMConfig) -> Dict[str, Tuple[int, ...]]:
    d, hd = lm.hidden_size, lm.head_size
    shapes = {
        # with the output gate a head's part of wq is its q, then its gate
        "wq": (d, lm.num_heads * hd * (2 if lm.attention_gate else 1)), "wk": (d, lm.num_kv_heads * hd),
        "wv": (d, lm.num_kv_heads * hd), "wo": (lm.num_heads * hd, d),
    }
    if lm.qk_norm:
        shapes.update(q_norm=(hd,), k_norm=(hd,))
    return shapes


def _latent_shapes(lm: LMConfig) -> Dict[str, Tuple[int, ...]]:
    d, h = lm.hidden_size, lm.num_heads
    return {
        "wq_a": (d, lm.q_lora_rank), "q_a_norm": (lm.q_lora_rank,),
        "wq_b": (lm.q_lora_rank, h * (lm.qk_nope_head_dim + lm.qk_rope_head_dim)),
        "wkv_a": (d, lm.kv_lora_rank + lm.qk_rope_head_dim), "kv_a_norm": (lm.kv_lora_rank,),
        "wkv_b": (lm.kv_lora_rank, h * (lm.qk_nope_head_dim + lm.v_head_dim)), "wo": (h * lm.v_head_dim, d),
    }


def _linear_shapes(lm: LMConfig) -> Dict[str, Tuple[int, ...]]:
    d = lm.hidden_size
    keys = lm.linear_num_key_heads * lm.linear_key_head_dim
    values = lm.linear_num_value_heads * lm.linear_value_head_dim
    return {
        "in_qkvz": (d, 2 * keys + 2 * values), "in_ba": (d, 2 * lm.linear_num_value_heads),
        "conv": (2 * keys + values, lm.linear_conv_kernel),
        "a_log": (lm.linear_num_value_heads,), "dt_bias": (lm.linear_num_value_heads,),
        "norm": (lm.linear_value_head_dim,), "out": (values, d),
    }


def _layer_shapes(lm: LMConfig, i: int) -> Dict[str, Any]:
    d = lm.hidden_size
    layer: Dict[str, Any] = {"attn_norm": {"scale": (d,)}, "ffn_norm": {"scale": (d,)}}
    if is_linear(lm, i):
        layer["linear"] = _linear_shapes(lm)
    elif lm.layer_types[i] == "latent_attention":
        layer["attn"] = _latent_shapes(lm)
    else:
        layer["attn"] = _attention_shapes(lm)
    if is_dense(lm, i):
        layer["ffn"] = _swiglu_shapes(d, lm.dense_width)
    else:
        layer["router"] = {"kernel": (d, lm.num_experts)}
        layer["experts"] = _swiglu_shapes(d, lm.expert_width, (lm.experts_held,))
        layer["shared"] = _swiglu_shapes(d, lm.expert_width)
        if lm.shared_expert_gate:
            layer["shared_gate"] = {"kernel": (d, 1)}
    return layer


def param_shapes(lm: LMConfig) -> Dict[str, Any]:
    """The parameter tree as shapes: the gradient leaves, and no other."""
    d = lm.hidden_size
    tree: Dict[str, Any] = {"embed": {"table": (lm.vocab_rows, d)}}
    for i in range(len(lm.layer_types)):
        tree[layer_name(i)] = _layer_shapes(lm, i)
    tree["final_norm"] = {"scale": (d,)}
    tree["head"] = {"kernel": (d, lm.vocab_rows)}
    if lm.mtp_layers:
        tree[MTP] = {
            "enorm": {"scale": (d,)}, "hnorm": {"scale": (d,)}, "eh_proj": {"kernel": (2 * d, d)},
            "block": _layer_shapes(lm, mtp_like(lm)), "final_norm": {"scale": (d,)},
        }
    return tree


def has_balance_bias(lm: LMConfig) -> bool:
    return lm.router_score == "sigmoid"


def _init_vector(lm: LMConfig, name: str, shape: Tuple[int, ...], key: Array) -> Array:
    """A one-dimensional leaf: a norm's weight at one (at nought where the
    norm reads `1 + w`; the delta-rule layer's gated norm is plain), the
    decay's `A_log = log U(1, 16)`, and its `dt_bias` the inverse softplus of
    a step drawn log-uniformly from 0.001 to 0.1, so that a head keeps from a
    fifth to all but a thousandth of its state a token."""
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if lm.norm_zero_centred and name in ("scale", "q_norm", "k_norm", "q_a_norm", "kv_a_norm"):
        return jnp.zeros(shape, jnp.float32)
    return jnp.ones(shape, jnp.float32)


def init(config: FasterRCNNConfig, rng: Array) -> Tuple[Any, Any]:
    """(params, batch_stats): matrices N(0, 0.02), vectors by `_init_vector`,
    the balance bias of every expert layer (the multi-token-prediction
    module's among them) at zero where there is one."""
    lm = config.lm
    shapes = param_shapes(lm)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(rng, len(leaves))
    made = [
        _init_vector(lm, path[-1].key, s, k) if len(s) == 1 else INIT_STD * jax.random.normal(k, s, jnp.float32)
        for (path, s), k in zip(leaves, keys)
    ]
    if not has_balance_bias(lm):
        return jax.tree_util.tree_unflatten(treedef, made), {}
    bias = {
        layer_name(i): jnp.zeros((lm.num_experts,), jnp.float32)
        for i in range(len(lm.layer_types)) if not is_dense(lm, i)
    }
    if lm.mtp_layers and not is_dense(lm, mtp_like(lm)):
        bias[MTP] = jnp.zeros((lm.num_experts,), jnp.float32)
    return jax.tree_util.tree_unflatten(treedef, made), {"router_bias": bias}


# ---------------------------------------------------------------- layers


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * scale).astype(x.dtype)


def rotary(x: Array, theta: float, fraction: float = 1.0, interleave: bool = False) -> Array:
    """Rotary embedding over the leading ``r = fraction * d`` of each head of
    ``[B, T, heads, d]``, pairs ``(i, i + r/2)`` (``interleave``: pairs ``(2i,
    2i + 1)``, the partner of lane ``i`` lane ``i xor 1``), positions 0..T-1
    along the row; the rest of the head is left as it is (its angle is
    nought). ``x cos + swap(x) sin`` with ``swap`` each lane's partner. The
    exchange is a product with a 0/1 matrix (exact), so that every array
    keeps the head's whole width in its lanes; slices of half a head cost
    layout copies on the TPU."""
    t, d = x.shape[1], x.shape[-1]
    r = int(d * fraction)
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    still = [jnp.zeros((d - r,), jnp.float32)] if r < d else []
    turned = [jnp.repeat(freq, 2)] if interleave else [freq, freq]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.concatenate(turned + still)[None, :]
    # the lane of a pair that takes minus the sine
    first = jnp.arange(d) % 2 == 0 if interleave else jnp.arange(d) < r // 2
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.where(first, -jnp.sin(angle), jnp.sin(angle))[None, :, None, :]
    at = jnp.arange(d)[:, None]
    if interleave:
        partner = jnp.bitwise_xor(jnp.arange(d)[None, :], 1)
    else:
        partner = (jnp.arange(d)[None, :] + r // 2) % r
    if r < d:
        partner = jnp.where(jnp.arange(d)[None, :] < r, partner, jnp.arange(d)[None, :])
    swap = (at == partner).astype(x.dtype)
    swapped = jnp.einsum("bthd,de->bthe", x, swap)
    return (x.astype(jnp.float32) * cos + swapped.astype(jnp.float32) * sin).astype(x.dtype)


def _mm(x: Array, w: Array) -> Array:
    return jnp.dot(x, w.astype(x.dtype))


def swiglu(h: Array, p: Dict[str, Array]) -> Array:
    return _mm(jax.nn.silu(_mm(h, p["w1"])) * _mm(h, p["w3"]), p["w2"])


def norm_weight(lm: LMConfig, w: Array) -> Array:
    """The weight a norm multiplies by, from its parameter."""
    return 1.0 + w if lm.norm_zero_centred else w


def attention_block(lm: LMConfig, p: Dict[str, Any], x: Array, windowed: bool) -> Array:
    b, t, _ = x.shape
    h = rms_norm(x, norm_weight(lm, p["attn_norm"]["scale"]), lm.rms_norm_eps)
    heads = lambda y, n: y.reshape(b, t, n, -1)

    def turned(y: Array, name: str) -> Array:
        if lm.qk_norm:
            y = rms_norm(y, norm_weight(lm, p["attn"][name]), lm.rms_norm_eps)
        return rotary(y, lm.rope_theta, lm.rotary_fraction)

    q = heads(_mm(h, p["attn"]["wq"]), lm.num_heads)
    if lm.attention_gate:
        q, gate = q[..., : lm.head_size], q[..., lm.head_size :]
    q = turned(q, "q_norm")
    k = turned(heads(_mm(h, p["attn"]["wk"]), lm.num_kv_heads), "k_norm")
    v = heads(_mm(h, p["attn"]["wv"]), lm.num_kv_heads)
    with jax.named_scope(stages.LM_ATTN_CORE):
        o = attention(q, k, v, lm.sliding_window if windowed else None)
    if lm.attention_gate:
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)
    return _mm(o.reshape(b, t, lm.num_heads * lm.head_size), p["attn"]["wo"])


def latent_block(lm: LMConfig, p: Dict[str, Any], x: Array) -> Array:
    """Multi-head latent attention over ``x`` ``[B, T, D]``: the latents,
    their norms and the rotary parts under `frcnn.lm_latent`, the attention
    over the decompressed heads under `frcnn.lm_attn_core`."""
    b, t, _ = x.shape
    w = p["attn"]
    n, nope, rope, dv = lm.num_heads, lm.qk_nope_head_dim, lm.qk_rope_head_dim, lm.v_head_dim
    h = rms_norm(x, norm_weight(lm, p["attn_norm"]["scale"]), lm.rms_norm_eps)
    with jax.named_scope(stages.LM_LATENT):
        c_q = rms_norm(_mm(h, w["wq_a"]), norm_weight(lm, w["q_a_norm"]), lm.rms_norm_eps)
        q = _mm(c_q, w["wq_b"]).reshape(b, t, n, nope + rope)
        c_kv, k_pe = jnp.split(_mm(h, w["wkv_a"]), [lm.kv_lora_rank], axis=-1)
        kv = _mm(rms_norm(c_kv, norm_weight(lm, w["kv_a_norm"]), lm.rms_norm_eps), w["wkv_b"]).reshape(b, t, n, nope + dv)
        turned = lambda y: rotary(y, lm.rope_theta, 1.0, lm.rope_interleave)
        q = jnp.concatenate([q[..., :nope], turned(q[..., nope:])], axis=-1)
        # one rotary part of the key, shared by every head
        k_pe = jnp.broadcast_to(turned(k_pe.reshape(b, t, 1, rope)), (b, t, n, rope))
        k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
        v = kv[..., nope:]
    with jax.named_scope(stages.LM_ATTN_CORE):
        o = attention(q, k, v, None, (nope + rope) ** -0.5)
    return _mm(o.reshape(b, t, n * dv), w["wo"])


def causal_conv(x: Array, taps: Array) -> Array:
    """Depthwise causal convolution along the row: ``y_t = sum_j taps[c, j]
    x_{t - (n - 1) + j}`` over ``[B, T, C]``, noughts before the row's first
    token, no bias; float32 sums."""
    n, t = taps.shape[1], x.shape[1]
    padded = jnp.pad(x, [(0, 0), (n - 1, 0), (0, 0)])
    return sum(padded[:, j : j + t].astype(jnp.float32) * taps[:, j] for j in range(n)).astype(x.dtype)


def unit_length(x: Array) -> Array:
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + 1e-6)


def delta_block(lm: LMConfig, p: Dict[str, Any], x: Array):
    """The delta-rule mixer of ``x`` ``[B, T, D]``, and its two counters: the
    largest magnitude in a state at a row's end, the mean of a token's
    ``exp(g)``."""
    from replication_faster_rcnn_tpu.ops.delta_rule import gated_delta_rule

    b, t, _ = x.shape
    kh, vh, dk, dv = lm.linear_num_key_heads, lm.linear_num_value_heads, lm.linear_key_head_dim, lm.linear_value_head_dim
    rep = vh // kh
    w = p["linear"]
    h = rms_norm(x, norm_weight(lm, p["attn_norm"]["scale"]), lm.rms_norm_eps)
    # a key head's group lies together: its q, its k, its value heads' v, their z
    qkvz = _mm(h, w["in_qkvz"]).reshape(b, t, kh, 2 * dk + 2 * rep * dv)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + rep * dv], axis=-1)
    ba = _mm(h, w["in_ba"]).reshape(b, t, kh, 2 * rep).astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :rep].reshape(b, t, vh))
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(ba[..., rep:].reshape(b, t, vh) + w["dt_bias"])
    flat = lambda y: y.reshape(b, t, -1)
    mixed = jax.nn.silu(causal_conv(jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1), w["conv"]))
    q, k, v = jnp.split(mixed, [kh * dk, 2 * kh * dk], axis=-1)
    q = (unit_length(q.reshape(b, t, kh, dk)) * dk ** -0.5).astype(x.dtype)
    k = unit_length(k.reshape(b, t, kh, dk)).astype(x.dtype)
    with jax.named_scope(stages.LM_DELTA_CORE):
        o, state = gated_delta_rule(q, k, v.reshape(b, t, vh, dv), g, beta)
    gate = jax.nn.silu(z.reshape(b, t, vh, dv).astype(jnp.float32))
    gated = rms_norm(o, w["norm"], lm.rms_norm_eps).astype(jnp.float32) * gate
    stats = {"state_absmax": jnp.max(jnp.abs(state)), "decay_mean": jnp.mean(jnp.exp(g))}
    return _mm(gated.astype(x.dtype).reshape(b, t, vh * dv), w["out"]), jax.lax.stop_gradient(stats)


def route(lm: LMConfig, kernel: Array, bias: Array, h: Array):
    """The router over all experts. ``h``: ``[N, D]``. Returns the chosen
    experts ``[N, k]`` int32, their weights ``[N, k]`` float32, and the count
    of tokens that chose each expert ``[E]`` float32."""
    logits = jnp.dot(h.astype(jnp.float32), kernel, precision=jax.lax.Precision.HIGHEST)
    if has_balance_bias(lm):
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + bias, lm.experts_per_token)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, lm.experts_per_token)
    # the chosen scores by compare-and-select, not by a gather (PERF.md, PR 28)
    hit = chosen[:, :, None] == jnp.arange(lm.num_experts, dtype=chosen.dtype)
    picked = jnp.sum(jnp.where(hit, scores[:, None, :], 0.0), axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    if has_balance_bias(lm):
        weights = weights * lm.route_scale
    counts = jnp.sum(hit, axis=(0, 1)).astype(jnp.float32)
    return chosen, weights, counts


def buffer_rows(lm: LMConfig, n_tokens: int) -> Tuple[int, int]:
    """(rows of the experts' buffer, buffers that hold every pair the shapes
    allow to land here). A buffer is `USUAL_ROWS` (or `lm.experts_fixed_rows`)
    times what even routing sends here, up to the grouped product's row tile;
    a step whose pairs pass the first buffer goes through as many more as its
    pairs fill."""
    most = n_tokens * min(lm.experts_per_token, lm.experts_held)
    even = n_tokens * lm.experts_per_token * lm.experts_held / lm.num_experts
    up = lambda rows: -(-int(rows) // 128) * 128
    rows = min(up((lm.experts_fixed_rows or USUAL_ROWS) * even), up(most))
    return rows, -(-most // rows)


def sort_by_expert(lm: LMConfig, chosen: Array):
    """The pairs (token, chosen expert) sorted by the held expert they chose,
    pairs of one expert in token order, the pairs whose expert is held
    elsewhere behind them all. Returns each sorted pair's held expert
    (``experts_held`` for none), its index ``token * k + slot``, and the held
    experts' group sizes."""
    n, k = chosen.shape
    local = chosen.reshape(n * k) - lm.first_expert
    key = jnp.where((local >= 0) & (local < lm.experts_held), local, lm.experts_held)
    key, pair = jax.lax.sort((key, jnp.arange(n * k, dtype=jnp.int32)), num_keys=1, is_stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(lm.experts_held, dtype=key.dtype), axis=0).astype(jnp.int32)
    return key, pair, sizes


def experts_on_buffer(lm: LMConfig, e: Dict[str, Array], h: Array, key: Array, pair: Array,
                      weights: Array, sizes: Array, rows: int, at: int) -> Array:
    """The held experts over the ``at``-th buffer of ``rows`` sorted pairs:
    the pairs' rows gathered, three grouped products over the part of each
    expert's group that lies in this buffer, the results added by token,
    weighted, in float32."""
    k = weights.shape[1]
    lo = at * rows
    if isinstance(at, int):
        fit = lambda x, fill: jnp.pad(x, (0, max(0, lo + rows - x.shape[0])), constant_values=fill)[lo : lo + rows]
    else:  # a buffer chosen on the device (`through_buffers`): any of those the shapes allow
        whole = rows * buffer_rows(lm, h.shape[0])[1]
        fit = lambda x, fill: jax.lax.dynamic_slice(
            jnp.pad(x, (0, max(0, whole - x.shape[0])), constant_values=fill), (lo,), (rows,)
        )
    key, pair = fit(key, lm.experts_held), fit(pair, 0)
    ends = jnp.cumsum(sizes)
    here = jnp.clip(jnp.minimum(ends, lo + rows) - jnp.maximum(ends - sizes, lo), 0, None)
    if lm.experts_fixed_rows:
        # the last held expert's group runs on to the buffer's end: its rows past the
        # pairs weigh 0, so every row tile is visited and nothing they give is kept
        here = here.at[-1].add(rows - jnp.sum(here))
    token = pair // k
    weight = jnp.where(key < lm.experts_held, weights.reshape(-1)[pair], 0.0)
    # gathered through float32, so that the rows' gradient adds up by token in float32
    taken = h.astype(jnp.float32)[token].astype(h.dtype)
    with jax.named_scope(stages.LM_EXPERT_MM):
        act = jax.nn.silu(grouped_matmul(taken, e["w1"], here)) * grouped_matmul(taken, e["w3"], here)
        out = grouped_matmul(act, e["w2"], here)
    return jnp.zeros(h.shape, jnp.float32).at[token].add(out.astype(jnp.float32) * weight[:, None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def through_buffers(lm: LMConfig, rows: int, e, h, weights, key, pair, sizes, reached):
    """The held experts over buffers 1 .. ``reached`` of the sorted pairs,
    added up: one loop on the device over as many buffers as the step's pairs
    reach, and one more in the backward pass, which computes a buffer again
    and adds its gradients to those of the buffers before it. The program
    holds a buffer's work twice whatever the number of buffers."""
    one = lambda at, y: y + experts_on_buffer(lm, e, h, key, pair, weights, sizes, rows, at)
    return jax.lax.fori_loop(1, reached + 1, one, jnp.zeros(h.shape, jnp.float32))


def _through_buffers_fwd(lm, rows, e, h, weights, key, pair, sizes, reached):
    return through_buffers(lm, rows, e, h, weights, key, pair, sizes, reached), (e, h, weights, key, pair, sizes, reached)


def _through_buffers_bwd(lm, rows, res, dy):
    e, h, weights, key, pair, sizes, reached = res

    def one(at, so_far):
        on = lambda e, h, weights: experts_on_buffer(lm, e, h, key, pair, weights, sizes, rows, at)
        return jax.tree_util.tree_map(jnp.add, so_far, jax.vjp(on, e, h, weights)[1](dy))

    nought = jax.tree_util.tree_map(jnp.zeros_like, (e, h, weights))
    return jax.lax.fori_loop(1, reached + 1, one, nought) + (None, None, None, None)


through_buffers.defvjp(_through_buffers_fwd, _through_buffers_bwd)


def expert_layer(lm: LMConfig, p: Dict[str, Any], bias: Array, h: Array):
    """The routed experts' part of the layer for ``h`` ``[N, D]``: the held
    experts' weighted sum ``[N, D]`` float32, and what the router counted."""
    with jax.named_scope(stages.LM_ROUTER):
        chosen, weights, counts = route(lm, p["router"]["kernel"], bias, h)
        key, pair, sizes = sort_by_expert(lm, chosen)
        pairs = jnp.sum(sizes)
    rows, buffers = buffer_rows(lm, h.shape[0])
    with jax.named_scope(stages.LM_EXPERTS):
        on = lambda at: experts_on_buffer(lm, p["experts"], h, key, pair, weights, sizes, rows, at)
        # no pair is dropped: a step whose pairs pass the first buffer goes on through
        # as many more as they reach, one after another and each recomputed in the
        # backward pass, so that the layer never holds more than one. ONE switch
        # over how many: each `cond` of its own, taken or not, writes zeros for every
        # operand's gradient (1.5 ms a layer and buffer: PERF.md section 6, PR 31)
        first = on(0)
        reached = jnp.clip((pairs - 1) // rows, 0, buffers - 1)
        if buffers <= SWITCH_BUFFERS:
            through = lambda n: lambda: sum(
                (jax.checkpoint(lambda at=at: on(at))() for at in range(1, n + 1)), jnp.zeros(h.shape, jnp.float32)
            )
            y = first + jax.lax.switch(reached, [through(n) for n in range(buffers)])
        else:
            # the switch holds n (n - 1) / 2 copies of a buffer's program (28 at eight buffers:
            # five minutes of compiling); the loop holds two
            y = first + through_buffers(lm, rows, p["experts"], h, weights, key, pair, sizes, reached)
    with jax.named_scope(stages.LM_ROUTER):
        held = sizes.astype(jnp.float32)
        stats = {
            "counts": counts,
            "assignments": pairs.astype(jnp.float32),
            "load_max_over_mean": jnp.max(held) / jnp.maximum(jnp.mean(held), 1.0),
            # pairs whose expert is held and that found no row: none, by the buffers' count
            "dropped": jnp.maximum(pairs - rows * buffers, 0).astype(jnp.float32),
        }
    return y, stats


def layer(lm: LMConfig, i: int, p: Dict[str, Any], bias, x: Array):
    """One layer: ``a = x + Mixer(norm(x))``, ``y = a + FFN(norm(a))``, and
    the layer's counters (None where it has none)."""
    b, t, d = x.shape
    mixer = {}
    if is_linear(lm, i):
        with jax.named_scope(stages.LM_LINEAR_ATTENTION):
            mixed, mixer = delta_block(lm, p, x)
            x = x + mixed
    elif lm.layer_types[i] == "latent_attention":
        with jax.named_scope(stages.LM_ATTENTION):
            x = x + latent_block(lm, p, x)
    else:
        with jax.named_scope(stages.LM_ATTENTION):
            x = x + attention_block(lm, p, x, lm.layer_types[i] == "sliding_attention")
    with jax.named_scope(stages.LM_FFN):
        h = rms_norm(x, norm_weight(lm, p["ffn_norm"]["scale"]), lm.rms_norm_eps)
        ffn = swiglu(h, p["ffn"] if is_dense(lm, i) else p["shared"])
        if is_dense(lm, i):
            return x + ffn, mixer or None
        if lm.shared_expert_gate:
            ffn = (jax.nn.sigmoid(_mm(h, p["shared_gate"]["kernel"]).astype(jnp.float32)) * ffn).astype(ffn.dtype)
    routed, stats = expert_layer(lm, p, bias, h.reshape(b * t, d))
    with jax.named_scope(stages.LM_EXPERTS):
        return x + (ffn.astype(jnp.float32) + routed.reshape(b, t, d)).astype(x.dtype), {**stats, **mixer}


def next_bias(lm: LMConfig, bias: Array, counts: Array) -> Array:
    moved = bias + lm.load_balance_coeff * jnp.sign(jnp.mean(counts) - counts)
    return moved - jnp.mean(moved)


def head_loss(lm: LMConfig, params: Dict[str, Any], x: Array, tokens: Array, norm=None, ahead: int = 1) -> Array:
    """Mean cross-entropy of the token ``ahead`` positions on (the next one
    unless told) over every position of a row that has one, the logits a
    block of tokens at a time; ``norm`` the final norm's weight, the trunk's
    unless told."""
    b, t, d = x.shape
    norm = params["final_norm"]["scale"] if norm is None else norm
    x = rms_norm(x, norm_weight(lm, norm), lm.rms_norm_eps)
    target = jnp.concatenate([tokens[:, ahead:], jnp.zeros((b, ahead), tokens.dtype)], axis=1)
    counted = jnp.broadcast_to(jnp.arange(t) < t - ahead, (b, t))
    n = b * t
    block = HEAD_BLOCK if n % HEAD_BLOCK == 0 else n
    kernel = params["head"]["kernel"]

    @jax.checkpoint
    def nll(xs, ys, ws):
        logits = jnp.dot(xs, kernel.astype(xs.dtype), preferred_element_type=jnp.float32)
        hit = ys[:, None] == jnp.arange(logits.shape[-1], dtype=ys.dtype)
        picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        return jnp.sum(jnp.where(ws, jax.nn.logsumexp(logits, axis=-1) - picked, 0.0))

    blocks = lambda a: a.reshape((n // block, block) + a.shape[2:])
    sums = jax.lax.map(lambda xyw: nll(*xyw), (blocks(x), blocks(target), blocks(counted)))
    return jnp.sum(sums) / (b * (t - ahead))


def embed(lm: LMConfig, params: Dict[str, Any], tokens: Array, dtype) -> Array:
    x = params["embed"]["table"][tokens]
    return (x * (lm.hidden_size ** 0.5) if lm.embed_scale else x).astype(dtype)


def checkpointed_layer(lm: LMConfig, i: int):
    """Layer ``i`` under the `jax.checkpoint` that keeps what `kept` names."""
    return jax.checkpoint(lambda p, b, x: layer(lm, i, p, b, x), policy=kept(lm))


def mtp_loss(lm: LMConfig, params: Dict[str, Any], bias, x: Array, tokens: Array):
    """The multi-token-prediction module's loss over the trunk's output ``x``
    (before its final norm), and its layer's counters: ``h'_i = [N_e(E[t_{i +
    1}]) | N_h(x_i)] W_eh``, a layer of the last layer's kind over ``h'``, then
    its final norm, the shared head and the cross-entropy of ``t_{i + 2}``. The
    row's last position is given id 0 for its next token: its outputs enter
    no loss (the attention is causal)."""
    p = params[MTP]
    b = tokens.shape[0]
    following = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    e = rms_norm(embed(lm, params, following, x.dtype), norm_weight(lm, p["enorm"]["scale"]), lm.rms_norm_eps)
    h = rms_norm(x, norm_weight(lm, p["hnorm"]["scale"]), lm.rms_norm_eps)
    h = _mm(jnp.concatenate([e, h], axis=-1), p["eh_proj"]["kernel"])
    y, stats = checkpointed_layer(lm, mtp_like(lm))(p["block"], bias.get(MTP), h)
    return head_loss(lm, params, y, tokens, p["final_norm"]["scale"], ahead=2), stats


def losses(
    model: Any,
    config: FasterRCNNConfig,
    params: Any,
    batch_stats: Any,
    batch: Dict[str, Array],
    rng: Array,
    train: bool = True,
    train_resolution=None,
) -> Tuple[Array, Tuple[Dict[str, Array], Any]]:
    """Forward + loss. Returns (total, (metrics, new_batch_stats)), as the
    detector's `compute_losses` does; `rng` is not used: the model samples
    nothing. Each stage runs under its scope of `telemetry/stages.py`; the
    multi-token-prediction module's own work under `frcnn.lm_mtp`, its layer
    under the layers' scopes."""
    del model, rng, train, train_resolution
    lm = config.lm
    dtype = jnp.dtype(config.model.compute_dtype)
    tokens = batch["tokens"]
    with jax.named_scope(stages.LM_EMBED):
        x = embed(lm, params, tokens, dtype)
    bias = batch_stats.get("router_bias", {})
    new_bias, per_layer, mixers = {}, [], []

    def counted(name, stats):
        if stats is not None:
            stats = jax.lax.stop_gradient(stats)
            if "decay_mean" in stats:
                mixers.append({k: stats.pop(k) for k in ("state_absmax", "decay_mean")})
        if stats:
            counts = stats.pop("counts")
            if has_balance_bias(lm):
                with jax.named_scope(stages.LM_ROUTER):
                    new_bias[name] = next_bias(lm, bias[name], counts)
            per_layer.append(stats)

    for i in range(len(lm.layer_types)):
        name = layer_name(i)
        x, stats = checkpointed_layer(lm, i)(params[name], bias.get(name), x)
        counted(name, stats)
    with jax.named_scope(stages.LM_HEAD):
        loss = head_loss(lm, params, x, tokens)
    metrics = {"loss": loss, "nll_loss": loss}
    if lm.mtp_layers:
        with jax.named_scope(stages.LM_MTP):
            further, stats = mtp_loss(lm, params, bias, x, tokens)
        counted(MTP, stats)
        metrics.update(loss=loss + lm.mtp_weight * further, mtp_loss=further)
    if per_layer:
        with jax.named_scope(stages.LM_ROUTER):
            stacked = {k: jnp.stack([s[k] for s in per_layer]) for k in per_layer[0]}
            metrics.update(
                expert_assignments=jnp.sum(stacked["assignments"]),
                expert_load_max_over_mean=jnp.max(stacked["load_max_over_mean"]),
                tokens_dropped=jnp.sum(stacked["dropped"]),
            )
            if new_bias:
                metrics["router_bias_absmax"] = jnp.max(jnp.stack([jnp.max(jnp.abs(v)) for v in new_bias.values()]))
    if mixers:
        with jax.named_scope(stages.LM_LINEAR_ATTENTION):
            metrics.update(
                delta_state_absmax=jnp.max(jnp.stack([m["state_absmax"] for m in mixers])),
                delta_decay_mean=jnp.mean(jnp.stack([m["decay_mean"] for m in mixers])),
            )
    return metrics["loss"], (metrics, {"router_bias": new_bias} if has_balance_bias(lm) else {})
