"""Device time per completed traced step and chip inside `frcnn.lm_embed` and `frcnn.lm_head`: the embedding rows; the last norm, the output head, the cross-entropy, forward and
backward (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.lm_embed", "frcnn.lm_head"))
