"""The comparison that decides `correct` for a training cell.

The timed path's first steps against the plain reference on the same
batches: each step's loss, the norm of the first gradient as the optimizer
got it, and the norm of the parameters' change after the steps. Norms are
compared by the worst leaf: the gap between the program's norm and the
reference's (not the norm of their difference: where a model samples, the
two sample differently once a rounding flips one selection), over the
reference's norm of that leaf or of the median leaf, whichever is larger.
Which loss parts and which named leaves are compared besides is the
model's: the configuration's reference module states them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple


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
    first step's loss parts), `grad_norms` and `change_norms` (per leaf);
    `reference` also `named_leaves`, the model's own numbers as
    {name: leaf prefixes}: each the worst gap of the first gradient's norms
    over the leaves under its prefixes."""
    out: Dict[str, Dict[str, Any]] = {}
    for i, (lp, lr) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss{i + 1}_gap"] = {"value": abs(lp - lr) / max(abs(lr), 1e-30)}
    for part, lr in reference["parts"].items():
        lp = program["parts"][part]
        out[f"{part.removesuffix('_loss')}1_gap"] = {"value": abs(lp - lr) / max(abs(lr), 1e-30)}
    leaves = sorted(reference["grad_norms"])
    gap, at = worst_leaf_gap(program["grad_norms"], reference["grad_norms"], leaves)
    out["grad_norm_gap"] = {"value": gap, "at": at}
    for name, prefixes in reference["named_leaves"].items():
        picked = [k for k in leaves if k.startswith(tuple(prefixes))]
        if not picked:
            raise KeyError(f"{name}: no leaf of the reference lies under {prefixes!r}")
        gap, at = worst_leaf_gap(program["grad_norms"], reference["grad_norms"], picked)
        out[name] = {"value": gap, "at": at}
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
