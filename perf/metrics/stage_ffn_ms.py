"""Device time per completed traced step and chip inside `frcnn.lm_ffn`: the norm, the dense SwiGLU or the shared expert, forward and
backward (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.lm_ffn",))
