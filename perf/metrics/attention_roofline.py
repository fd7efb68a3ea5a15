"""The attention function's share of its roofline: the least time a chip
needs for the visible (query, key) pairs of one step (the configuration's
reference module counts them: per layer and pass the larger of FLOPs over
peak and bytes over bandwidth) over the device time per step inside
`frcnn.lm_attn_core`, forward and backward (perf/stagecut.py). A program that
recomputes the forward in its backward pass reads lower for it: the needed
work is counted once."""

from perf import stagecut


def read(ctx):
    count = getattr(ctx["flops"], "attention_roofline_seconds", None)
    took = stagecut.stage_ms(ctx, ("frcnn.lm_attn_core",))
    if count is None or not took:
        return None
    least = count(
        ctx["sizes"], ctx["batch"] // ctx["chips"], ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"]
    )
    return 100.0 * least * 1e3 / took
