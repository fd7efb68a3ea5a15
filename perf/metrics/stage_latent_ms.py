"""Device time per completed traced step and chip inside `frcnn.lm_latent`: a latent-attention layer's query and key-value
latents, their norms, the up-projections and the rotary parts, forward and backward (perf/stagecut.py; an operation counts
in the last stage scope of its path, so this time is not in `stage_attention_ms`)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.lm_latent",))
