"""The gated delta rule's share of its roofline: the least time a chip needs
for the recurrence of one step's tokens (the configuration's reference module
counts it: per layer and pass the larger of the chunked form's FLOPs over
peak and of q, k, v, g, beta, the output and their cotangents once over
bandwidth) over the device time per step inside `frcnn.lm_delta_core`,
forward and backward (perf/stagecut.py). It counts the work whatever
implements it; a program that recomputes part of the forward in its backward
pass reads lower for it."""

from perf import stagecut


def read(ctx):
    count = getattr(ctx["flops"], "delta_rule_roofline_seconds", None)
    took = stagecut.stage_ms(ctx, ("frcnn.lm_delta_core",))
    if count is None or not took:
        return None
    least = count(
        ctx["sizes"], ctx["batch"] // ctx["chips"], ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"]
    )
    return 100.0 * least * 1e3 / took
