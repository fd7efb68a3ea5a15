"""Device time per completed traced step and chip inside `frcnn.lm_attention` and, nested in it, `frcnn.lm_attn_core`: the norm, q/k/v, the rotary embedding, the attention function, the output projection, forward and
backward (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.lm_attention", "frcnn.lm_attn_core"))
