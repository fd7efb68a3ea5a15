"""The sequence model: a decoder of pre-norm layers with rotary grouped-query
attention (windowed or full, layer by layer), a dense SwiGLU in the leading
layers and a routed expert layer in the others, told which experts it holds.

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
for five layers of 16,384 tokens). The head and the loss run over blocks of
tokens, so that no `[tokens, vocabulary]` array outlives its block.

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
pairs really routed here.

The balance bias is no parameter: it lives in `TrainState.batch_stats`
(`{"router_bias": {layer: [num_experts]}}`) and moves inside the step by
`load_balance_coeff * sign(mean(c) - c)`, `c` the step's count of tokens
that chose each expert, then is centred on zero. There is no auxiliary loss.
"""

from __future__ import annotations

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
COUNTERS = ("expert_assignments", "expert_load_max_over_mean", "tokens_dropped", "router_bias_absmax")
HEAD_BLOCK = 2048  # tokens whose logits are alive at once
USUAL_ROWS = 2  # the experts' usual buffer, in pairs that even routing sends here
INIT_STD = 0.02
# what a layer's `jax.checkpoint` keeps for the backward pass, beside the layer's inputs
KEPT = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)


def layer_name(i: int) -> str:
    return f"layers_{i}"


def is_dense(lm: LMConfig, i: int) -> bool:
    return i < lm.num_dense_layers


# ------------------------------------------------------------ parameters


def _swiglu_shapes(d: int, f: int, lead: Tuple[int, ...] = ()) -> Dict[str, Tuple[int, ...]]:
    return {"w1": lead + (d, f), "w3": lead + (d, f), "w2": lead + (f, d)}


def param_shapes(lm: LMConfig) -> Dict[str, Any]:
    """The parameter tree as shapes: the gradient leaves, and no other."""
    d, hd = lm.hidden_size, lm.head_size
    tree: Dict[str, Any] = {"embed": {"table": (lm.vocab_rows, d)}}
    for i in range(len(lm.layer_types)):
        layer: Dict[str, Any] = {
            "attn_norm": {"scale": (d,)},
            "attn": {
                "wq": (d, lm.num_heads * hd), "wk": (d, lm.num_kv_heads * hd),
                "wv": (d, lm.num_kv_heads * hd), "wo": (lm.num_heads * hd, d),
            },
            "ffn_norm": {"scale": (d,)},
        }
        if is_dense(lm, i):
            layer["ffn"] = _swiglu_shapes(d, lm.dense_width)
        else:
            layer["router"] = {"kernel": (d, lm.num_experts)}
            layer["experts"] = _swiglu_shapes(d, lm.expert_width, (lm.experts_held,))
            layer["shared"] = _swiglu_shapes(d, lm.expert_width)
        tree[layer_name(i)] = layer
    tree["final_norm"] = {"scale": (d,)}
    tree["head"] = {"kernel": (d, lm.vocab_rows)}
    return tree


def init(config: FasterRCNNConfig, rng: Array) -> Tuple[Any, Any]:
    """(params, batch_stats): matrices N(0, 0.02), norms at one, the balance
    bias of every expert layer at zero."""
    lm = config.lm
    shapes = param_shapes(lm)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(rng, len(leaves))
    made = [
        jnp.ones(s, jnp.float32) if len(s) == 1 else INIT_STD * jax.random.normal(k, s, jnp.float32)
        for s, k in zip(leaves, keys)
    ]
    bias = {
        layer_name(i): jnp.zeros((lm.num_experts,), jnp.float32)
        for i in range(len(lm.layer_types)) if not is_dense(lm, i)
    }
    return jax.tree_util.tree_unflatten(treedef, made), {"router_bias": bias}


# ---------------------------------------------------------------- layers


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * scale).astype(x.dtype)


def rotary(x: Array, theta: float) -> Array:
    """Rotary embedding over the whole head of ``[B, T, heads, d]``, pairs
    ``(i, i + d/2)``, positions 0..T-1 along the row: ``x cos + swap(x) sin``
    with ``swap`` the head's two halves exchanged. The exchange is a product
    with a 0/1 matrix (exact), so that every array keeps the head's whole
    width in its lanes; slices of half a head cost layout copies on the TPU."""
    t, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.concatenate([freq, freq])[None, :]
    first = jnp.arange(d) < d // 2
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.where(first, -jnp.sin(angle), jnp.sin(angle))[None, :, None, :]
    swap = (jnp.arange(d)[:, None] == (jnp.arange(d)[None, :] + d // 2) % d).astype(x.dtype)
    swapped = jnp.einsum("bthd,de->bthe", x, swap)
    return (x.astype(jnp.float32) * cos + swapped.astype(jnp.float32) * sin).astype(x.dtype)


def _mm(x: Array, w: Array) -> Array:
    return jnp.dot(x, w.astype(x.dtype))


def swiglu(h: Array, p: Dict[str, Array]) -> Array:
    return _mm(jax.nn.silu(_mm(h, p["w1"])) * _mm(h, p["w3"]), p["w2"])


def attention_block(lm: LMConfig, p: Dict[str, Any], x: Array, windowed: bool) -> Array:
    b, t, _ = x.shape
    h = rms_norm(x, p["attn_norm"]["scale"], lm.rms_norm_eps)
    heads = lambda y, n: y.reshape(b, t, n, lm.head_size)
    q = rotary(heads(_mm(h, p["attn"]["wq"]), lm.num_heads), lm.rope_theta)
    k = rotary(heads(_mm(h, p["attn"]["wk"]), lm.num_kv_heads), lm.rope_theta)
    v = heads(_mm(h, p["attn"]["wv"]), lm.num_kv_heads)
    with jax.named_scope(stages.LM_ATTN_CORE):
        o = attention(q, k, v, lm.sliding_window if windowed else None)
    return _mm(o.reshape(b, t, lm.num_heads * lm.head_size), p["attn"]["wo"])


def route(lm: LMConfig, kernel: Array, bias: Array, h: Array):
    """The router over all experts. ``h``: ``[N, D]``. Returns the chosen
    experts ``[N, k]`` int32, their weights ``[N, k]`` float32, and the count
    of tokens that chose each expert ``[E]`` float32."""
    scores = jax.nn.sigmoid(
        jnp.dot(h.astype(jnp.float32), kernel, precision=jax.lax.Precision.HIGHEST)
    )
    _, chosen = jax.lax.top_k(scores + bias, lm.experts_per_token)
    # the chosen scores by compare-and-select, not by a gather (PERF.md, PR 28)
    hit = chosen[:, :, None] == jnp.arange(lm.num_experts, dtype=chosen.dtype)
    picked = jnp.sum(jnp.where(hit, scores[:, None, :], 0.0), axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * lm.route_scale
    counts = jnp.sum(hit, axis=(0, 1)).astype(jnp.float32)
    return chosen, weights, counts


def buffer_rows(lm: LMConfig, n_tokens: int) -> Tuple[int, int]:
    """(rows of the experts' buffer, buffers that hold every pair the shapes
    allow to land here). A buffer is `USUAL_ROWS` times what even routing
    sends here, up to the grouped product's row tile; a step whose pairs pass
    the first buffer goes through as many more as its pairs fill."""
    most = n_tokens * min(lm.experts_per_token, lm.experts_held)
    even = n_tokens * lm.experts_per_token * lm.experts_held / lm.num_experts
    up = lambda rows: -(-int(rows) // 128) * 128
    rows = min(up(USUAL_ROWS * even), up(most))
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
    fit = lambda x, fill: jnp.pad(x, (0, max(0, lo + rows - x.shape[0])), constant_values=fill)[lo : lo + rows]
    key, pair = fit(key, lm.experts_held), fit(pair, 0)
    ends = jnp.cumsum(sizes)
    here = jnp.clip(jnp.minimum(ends, lo + rows) - jnp.maximum(ends - sizes, lo), 0, None)
    token = pair // k
    weight = jnp.where(key < lm.experts_held, weights.reshape(-1)[pair], 0.0)
    # gathered through float32, so that the rows' gradient adds up by token in float32
    taken = h.astype(jnp.float32)[token].astype(h.dtype)
    with jax.named_scope(stages.LM_EXPERT_MM):
        act = jax.nn.silu(grouped_matmul(taken, e["w1"], here)) * grouped_matmul(taken, e["w3"], here)
        out = grouped_matmul(act, e["w2"], here)
    return jnp.zeros(h.shape, jnp.float32).at[token].add(out.astype(jnp.float32) * weight[:, None])


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
        through = lambda n: lambda: sum(
            (jax.checkpoint(lambda at=at: on(at))() for at in range(1, n + 1)), jnp.zeros(h.shape, jnp.float32)
        )
        y = on(0) + jax.lax.switch(jnp.clip((pairs - 1) // rows, 0, buffers - 1), [through(n) for n in range(buffers)])
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
    """One layer: ``a = x + Attn(norm(x))``, ``y = a + FFN(norm(a))``."""
    b, t, d = x.shape
    with jax.named_scope(stages.LM_ATTENTION):
        x = x + attention_block(lm, p, x, lm.layer_types[i] == "sliding_attention")
    with jax.named_scope(stages.LM_FFN):
        h = rms_norm(x, p["ffn_norm"]["scale"], lm.rms_norm_eps)
        ffn = swiglu(h, p["ffn"] if is_dense(lm, i) else p["shared"])
        if is_dense(lm, i):
            return x + ffn, None
    routed, stats = expert_layer(lm, p, bias, h.reshape(b * t, d))
    with jax.named_scope(stages.LM_EXPERTS):
        return x + (ffn.astype(jnp.float32) + routed.reshape(b, t, d)).astype(x.dtype), stats


def next_bias(lm: LMConfig, bias: Array, counts: Array) -> Array:
    moved = bias + lm.load_balance_coeff * jnp.sign(jnp.mean(counts) - counts)
    return moved - jnp.mean(moved)


def head_loss(lm: LMConfig, params: Dict[str, Any], x: Array, tokens: Array) -> Array:
    """Mean cross-entropy of the next token over every position of a row
    but its last, the logits a block of tokens at a time."""
    b, t, d = x.shape
    x = rms_norm(x, params["final_norm"]["scale"], lm.rms_norm_eps)
    target = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    counted = jnp.broadcast_to(jnp.arange(t) < t - 1, (b, t))
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
    return jnp.sum(sums) / (b * (t - 1))


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
    nothing. Each stage runs under its scope of `telemetry/stages.py`."""
    del model, rng, train, train_resolution
    lm = config.lm
    dtype = jnp.dtype(config.model.compute_dtype)
    tokens = batch["tokens"]
    with jax.named_scope(stages.LM_EMBED):
        x = (params["embed"]["table"][tokens] * (lm.hidden_size ** 0.5)).astype(dtype)
    bias = batch_stats["router_bias"]
    new_bias, per_layer = {}, []
    for i in range(len(lm.layer_types)):
        name = layer_name(i)
        x, stats = jax.checkpoint(lambda p, b, x, i=i: layer(lm, i, p, b, x), policy=KEPT)(
            params[name], bias.get(name), x
        )
        if stats is not None:
            stats = jax.lax.stop_gradient(stats)
            with jax.named_scope(stages.LM_ROUTER):
                new_bias[name] = next_bias(lm, bias[name], stats.pop("counts"))
            per_layer.append(stats)
    with jax.named_scope(stages.LM_HEAD):
        loss = head_loss(lm, params, x, tokens)
    metrics = {"loss": loss, "nll_loss": loss}
    if per_layer:
        with jax.named_scope(stages.LM_ROUTER):
            stacked = {k: jnp.stack([s[k] for s in per_layer]) for k in per_layer[0]}
            metrics.update(
                expert_assignments=jnp.sum(stacked["assignments"]),
                expert_load_max_over_mean=jnp.max(stacked["load_max_over_mean"]),
                tokens_dropped=jnp.sum(stacked["dropped"]),
                router_bias_absmax=jnp.max(jnp.stack([jnp.max(jnp.abs(v)) for v in new_bias.values()])),
            )
    return loss, (metrics, {"router_bias": new_bias})
