"""Detection losses — masked, fixed-shape, batch-global semantics.

Capability parity with reference `train.py:29-57` (``_fast_rcnn_loc_loss``)
and the CE calls at `train.py:83,121`:

  * smooth-L1 with sigma: 0.5*s^2*d^2 below 1/s^2, |d| - 0.5/s^2 above
    (`train.py:43-52`), summed over positives and normalized by the
    batch-global positive count, floored at 1 (`train.py:55-57`).
  * softmax cross-entropy with ignore_index=-1 semantics: mean over
    non-ignored entries across the whole batch (`train.py:83,121`).

Under `jax.jit` auto-partitioning these global reductions become XLA
cross-replica collectives on a sharded batch, so data-parallel training is
bit-for-bit the same objective as single-device — the psum'd allreduce of
the BASELINE north star falls out of the sharding, not hand-written comms.

Under the explicit `shard_map` backend (`parallel/spmd.py`) each shard sees
only its local batch slice, so the batch-global normalizers must be summed
across shards by hand: pass ``axis_name`` and the positive/valid counts are
`lax.psum`'d over that mesh axis before dividing, keeping the objective
identical to the auto-partitioned path.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jnp.ndarray


def _global_sum(x: Array, axis_name: Optional[str]) -> Array:
    return jax.lax.psum(x, axis_name) if axis_name else x


def smooth_l1(pred: Array, target: Array, sigma: float = 1.0) -> Array:
    """Elementwise smooth-L1 (Huber with the sigma^2 knee of `train.py:43-52`)."""
    s2 = sigma * sigma
    diff = jnp.abs(pred - target)
    return jnp.where(diff < 1.0 / s2, 0.5 * s2 * diff * diff, diff - 0.5 / s2)


def loc_loss(
    pred: Array,
    target: Array,
    labels: Array,
    sigma: float = 1.0,
    axis_name: Optional[str] = None,
) -> Array:
    """Localization loss on positive samples only (labels > 0), summed and
    normalized by max(#pos, 1) over the whole batch (`train.py:40-57`).

    pred/target: [..., 4]; labels: [...] with >0 = positive. With
    ``axis_name``, #pos is the global count across that mesh axis (the
    local sum/global count quotient psums to the global quotient).
    """
    pos = (labels > 0).astype(pred.dtype)
    per = smooth_l1(pred, target, sigma).sum(-1)  # [...]
    n_pos = jnp.maximum(_global_sum(pos.sum(), axis_name), 1.0)
    return (per * pos).sum() / n_pos


def ignore_cross_entropy(
    logits: Array, labels: Array, axis_name: Optional[str] = None
) -> Array:
    """Softmax CE averaged over entries with label >= 0 (torch
    ``ignore_index=-1`` semantics, `train.py:83,121`).

    logits: [..., C]; labels: [...] int with -1 = ignore. With
    ``axis_name``, the mean is over the global valid count.

    The label's logit is picked by compare-and-select over the class axis,
    not by ``take_along_axis`` (what
    ``optax.softmax_cross_entropy_with_integer_labels`` does): the chip
    serves an XLA gather at 9-13 ns an index whatever the row's width, so
    the RPN's pick over a class axis of TWO (32 x 12,996 anchors) was the
    step's longest device operation, 4.96 ms of 91.0 (PERF.md, PR 28), and
    its backward a scatter-add. A ``where`` and not a product with a
    one-hot, so an infinite logit of another class cannot make a NaN. The
    values are optax's to the bit (the sum adds zeros; same ``logsumexp``);
    `tests/oracles.py::ignore_cross_entropy_optax` keeps the old writing.
    """
    valid = labels >= 0
    classes = jnp.arange(logits.shape[-1], dtype=jnp.int32)
    picked = labels.astype(jnp.int32)[..., None] == classes
    label_logits = jnp.where(picked, logits, 0.0).sum(-1)
    ce = jax.nn.logsumexp(logits, axis=-1) - label_logits
    n = jnp.maximum(_global_sum(valid.sum(), axis_name), 1)
    return jnp.where(valid, ce, 0.0).sum() / n
