"""The comparison that decides `correct` for a training cell.

The timed path's first steps against the plain reference on the same
batches: each step's loss, the norm of the first gradient as the optimizer
got it, and the norm of the parameters' change after the steps. Norms are
compared by the worst leaf: the gap between the program's norm and the
reference's (not the norm of their difference: the two sample different
anchors and ROIs once a rounding flips one selection), over the reference's
norm of that leaf or of the median leaf, whichever is larger.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple


LOSS_PARTS = ("rpn_cls_loss", "rpn_reg_loss", "head_cls_loss", "head_reg_loss")


def leaf_gaps(
    program: Dict[str, float], reference: Dict[str, float], leaves: Sequence[str]
) -> Dict[str, float]:
    """Each leaf's gap of norms, over the reference's norm of that leaf or of
    the median leaf of the whole tree, whichever is larger."""
    ref_sorted = sorted(reference.values())
    median = ref_sorted[len(ref_sorted) // 2]
    return {k: abs(program[k] - reference[k]) / max(reference[k], median, 1e-30) for k in leaves}


def worst_leaf_gap(
    program: Dict[str, float], reference: Dict[str, float], leaves: Sequence[str]
) -> Tuple[float, str]:
    worst, where = 0.0, ""
    for k, gap in leaf_gaps(program, reference, leaves).items():
        if not gap <= worst:  # also lets a NaN through
            worst, where = gap, k
    return worst, where


def median_leaf_gap(
    program: Dict[str, float], reference: Dict[str, float], leaves: Sequence[str]
) -> float:
    """The median leaf's gap: steady where the worst leaf is the noise of one
    small tensor."""
    gaps = sorted(leaf_gaps(program, reference, leaves).values())
    return gaps[len(gaps) // 2]


def moving_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least a
    thousandth of the median leaf's. The others move under Adam by round-off
    alone and are left out of the change."""
    ordered = sorted(ref_grad.values())
    floor = 1e-3 * ordered[len(ordered) // 2]
    return [k for k, v in ref_grad.items() if v >= floor]


def numbers(program: Dict[str, Any], reference: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """{name: {"value": gap, "at": leaf}} for every number compared.
    `program` and `reference` hold `losses` (one per step), `parts` (the
    first step's four losses), `grad_norms` and `change_norms` (per leaf)."""
    out: Dict[str, Dict[str, Any]] = {}
    for i, (lp, lr) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss{i + 1}_gap"] = {"value": abs(lp - lr) / max(abs(lr), 1e-30)}
    for part in LOSS_PARTS:
        lp, lr = program["parts"][part], reference["parts"][part]
        out[f"{part[:-5]}1_gap"] = {"value": abs(lp - lr) / max(abs(lr), 1e-30)}
    leaves = sorted(reference["grad_norms"])
    gap, at = worst_leaf_gap(program["grad_norms"], reference["grad_norms"], leaves)
    out["grad_norm_gap"] = {"value": gap, "at": at}
    # the RPN heads' own leaves: their gradient comes from the two RPN losses
    # alone, upstream of every proposal, so no flipped selection reaches it.
    # The objectness kernel's is the steadiest (256 sampled anchors an image).
    rpn = [k for k in leaves if k.startswith("rpn/cls/") or k.startswith("rpn/reg/")]
    gap, at = worst_leaf_gap(program["grad_norms"], reference["grad_norms"], rpn)
    out["rpn_grad_norm_gap"] = {"value": gap, "at": at}
    gap, _ = worst_leaf_gap(program["grad_norms"], reference["grad_norms"], ["rpn/cls/kernel"])
    out["rpn_cls_grad_gap"] = {"value": gap}
    out["grad_norm_median_gap"] = {
        "value": median_leaf_gap(program["grad_norms"], reference["grad_norms"], leaves)
    }
    moving = moving_leaves(reference["grad_norms"])
    gap, at = worst_leaf_gap(program["change_norms"], reference["change_norms"], moving)
    out["change_norm_gap"] = {"value": gap, "at": at}
    out["change_norm_median_gap"] = {
        "value": median_leaf_gap(program["change_norms"], reference["change_norms"], moving)
    }
    return out


def judge(nums: Dict[str, Dict[str, Any]], limits: Dict[str, float]) -> bool:
    """Attach each limit; correct when every number with a limit is finite
    and within it. A number without a limit is printed and not judged."""
    ok = True
    for name, entry in nums.items():
        limit = limits.get(name)
        entry["limit"] = limit
        if limit is not None and not (math.isfinite(entry["value"]) and entry["value"] <= limit):
            ok = False
    return ok
