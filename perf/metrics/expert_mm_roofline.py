"""The grouped expert products' share of their roofline: the least time a
chip needs for the token-expert pairs the step really computed (the program's
counter `lm/expert_assignments`, mean over the window; FLOPs and bytes by the
configuration's reference module) over the device time per step inside
`frcnn.lm_expert_mm`, forward and backward (perf/stagecut.py)."""

from perf import stagecut


def counter_mean(ctx, name):
    """Mean of a program counter's events, None where there is none."""
    values = [e["args"]["value"] for e in ctx["spans"] if e.get("ph") == "C" and e.get("name") == name]
    return sum(values) / len(values) if values else None


def read(ctx):
    count = getattr(ctx["flops"], "expert_mm_roofline_seconds", None)
    pairs = counter_mean(ctx, "lm/expert_assignments")
    took = stagecut.stage_ms(ctx, ("frcnn.lm_expert_mm",))
    if count is None or pairs is None or not took:
        return None
    least = count(ctx["sizes"], pairs / ctx["chips"], ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * 1e3 / took
