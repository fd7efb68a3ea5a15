"""Fixed-shape greedy NMS — the TPU-native replacement for
``torchvision.ops.nms`` (reference `nets/rpn.py:75`; SURVEY.md §2.3).

The reference's NMS returns a data-dependent number of boxes, which cannot
live inside a jit-compiled graph. Here NMS is a `lax.fori_loop` with exactly
``max_out`` iterations: each iteration selects the highest-scoring surviving
candidate and suppresses everything with IoU above the threshold against it.
The result is the same set, in the same score order, as sort-then-greedy NMS,
but as padded ``[max_out]`` indices plus a validity mask — a fixed shape XLA
can compile once and the batch dimension can vmap over.

Cost: ``max_out`` sequential steps of O(N) vector work. At the reference's
budgets (600 selections over <=12k candidates) this is latency- not
FLOP-bound — it measured ~35% of the v5e train step in round 1, which is
why the shipped default is the tiled exact algorithm (`ops/nms_tiled.py`,
bit-identical selections, ~25-75 sequential steps instead of 600; see
``nms_fixed_auto`` below). The loop stays as the oracle-simple fallback
(`FRCNN_NMS=loop`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from replication_faster_rcnn_tpu.ops import boxes as box_ops

Array = jnp.ndarray

_NEG = -jnp.inf


@partial(jax.jit, static_argnames=("max_out",))
def nms_fixed(
    boxes: Array,
    scores: Array,
    iou_thresh: float,
    max_out: int,
    mask: Array | None = None,
) -> tuple[Array, Array]:
    """Greedy NMS with a fixed output size.

    Args:
      boxes: [N, 4] candidate boxes ([r1, c1, r2, c2]).
      scores: [N] scores; higher is better.
      iou_thresh: suppress candidates with IoU strictly greater than this
        against a kept box (torchvision semantics).
      max_out: number of output slots (e.g. post_nms budget).
      mask: optional [N] bool; False entries are never selected.

    Returns:
      (idx, valid): [max_out] int32 indices into ``boxes`` in descending
      score order, and a [max_out] bool mask of which slots hold real
      selections. Invalid slots point at index 0.
    """
    n = boxes.shape[0]
    live_scores = scores.astype(jnp.float32)
    # Non-finite scores (NaN from a diverging score head) must never win
    # argmax — a NaN selection would mark the slot invalid without
    # suppressing anything, stalling every remaining iteration.
    live_scores = jnp.where(jnp.isfinite(live_scores), live_scores, _NEG)
    if mask is not None:
        live_scores = jnp.where(mask, live_scores, _NEG)

    def body(i, state):
        live, idx, valid = state
        best = jnp.argmax(live)
        best_score = live[best]
        is_valid = best_score > _NEG
        idx = idx.at[i].set(jnp.where(is_valid, best, 0).astype(jnp.int32))
        valid = valid.at[i].set(is_valid)
        ious = box_ops.iou(boxes[best][None, :], boxes)[0]  # [N]
        # The selected box suppresses itself (IoU 1) and all overlaps.
        suppress = (ious > iou_thresh) | (jnp.arange(n, dtype=jnp.int32) == best)
        live = jnp.where(is_valid & suppress, _NEG, live)
        return live, idx, valid

    idx0 = jnp.zeros((max_out,), jnp.int32)
    valid0 = jnp.zeros((max_out,), bool)
    _, idx, valid = jax.lax.fori_loop(0, max_out, body, (live_scores, idx0, valid0))
    return idx, valid


def nms_fixed_auto(
    boxes: Array,
    scores: Array,
    iou_thresh: float,
    max_out: int,
    mask: Array | None = None,
    assume_sorted: bool = False,
) -> tuple[Array, Array]:
    """Backend dispatch for the proposal path.

    ``assume_sorted`` (candidates already in descending-score order) is a
    pure optimization hint: the tiled backend skips its internal sort;
    the loop backend ignores it (it is order-independent).

    Default on every backend (TPU included): the tiled exact algorithm
    (`ops/nms_tiled.py`; ~25-75 sequential matrix steps instead of one per
    selection). It is bit-identical to the selection loop (parity-tested in
    tests/test_nms_tiled.py) and plain XLA ops; the loop's one sequential
    step per selection is why it is no backend's default.

    Overrides via FRCNN_NMS: ``loop`` (the selection loop above),
    ``tiled`` (explicit default), or ``pallas`` (the `ops/pallas/` kernel
    — same tile/fixpoint recurrence as tiled, bit-identical selections;
    ``FRCNN_PALLAS_NMS=1`` is the legacy spelling of the same choice).
    With no explicit FRCNN_NMS choice the `ops.backend` axis decides
    (`ops.want_pallas`): backend=pallas routes here too, backend=xla
    keeps the tiled default. Choosing pallas and not getting it raises.
    """
    import os

    choice = os.environ.get("FRCNN_NMS", "").strip().lower()
    if not choice and os.environ.get("FRCNN_PALLAS_NMS") == "1":
        # the legacy opt-in spelling for the round-5 kernel — same signal
        # as FRCNN_NMS=pallas below, resolving to the rebuilt backend
        choice = "pallas"
    if choice and choice not in ("loop", "tiled", "pallas"):
        import warnings

        warnings.warn(
            f"unknown FRCNN_NMS={choice!r} (choices: loop, tiled, pallas); "
            "using the tiled default"
        )
        choice = ""
    if not choice:
        from replication_faster_rcnn_tpu import ops as ops_pkg

        choice = "pallas" if ops_pkg.want_pallas("nms") else "tiled"
    if choice == "pallas":
        from replication_faster_rcnn_tpu import ops as ops_pkg

        return ops_pkg.require_pallas("nms").nms_fixed_pallas(
            boxes, scores, iou_thresh, max_out, mask=mask,
            tile=_tile_from_env(), assume_sorted=assume_sorted,
            interpret=ops_pkg.interpret_mode(),
        )
    if choice == "tiled":
        from replication_faster_rcnn_tpu.ops.nms_tiled import nms_fixed_tiled

        return nms_fixed_tiled(
            boxes, scores, iou_thresh, max_out, mask=mask,
            tile=_tile_from_env(), assume_sorted=assume_sorted,
        )
    return nms_fixed(boxes, scores, iou_thresh, max_out, mask=mask)


def _tile_from_env() -> int:
    """FRCNN_NMS_TILE: candidates-per-sequential-step tile (default 512),
    honored by the tiled and pallas backends alike. Larger tiles mean
    fewer sequential steps but a bigger in-tile fixpoint matrix; the
    optimum is hardware- and budget-dependent. Bad values warn and fall
    back — a typo in a sweep must not crash a training run at trace
    time."""
    import os

    try:
        tile = int(os.environ.get("FRCNN_NMS_TILE", "512"))
        if tile < 1:
            raise ValueError(tile)
        return tile
    except ValueError:
        import warnings

        warnings.warn(
            f"invalid FRCNN_NMS_TILE={os.environ['FRCNN_NMS_TILE']!r} "
            "(want a positive int); using 512"
        )
        return 512


def batched_nms_fixed(
    boxes: Array,
    scores: Array,
    class_ids: Array,
    iou_thresh: float,
    max_out: int,
    mask: Array | None = None,
) -> tuple[Array, Array]:
    """Per-class NMS in one pass (for inference postprocessing).

    Boxes of different classes never suppress each other: each class's boxes
    are shifted into a disjoint coordinate region (the standard trick), then
    a single fixed-shape NMS runs over all of them (backend chosen by
    `nms_fixed_auto` — same dispatch as the proposal path).
    """
    extent = jnp.max(boxes) + 1.0
    offsets = class_ids.astype(boxes.dtype)[:, None] * extent
    shifted = boxes + offsets
    return nms_fixed_auto(shifted, scores, iou_thresh, max_out, mask=mask)
