"""Device time per completed traced step and chip inside `frcnn.lm_linear_attention` and, nested in it, `frcnn.lm_delta_core`: a delta-rule layer's norm, projections, convolution, gates, the gated delta rule itself,
the gated norm and the output projection, forward and backward (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.lm_linear_attention", "frcnn.lm_delta_core"))
