"""Device time per completed traced step and chip inside `frcnn.lm_mtp`: the multi-token-prediction module's own work (the
next token's embedding, its two norms, the projection to the hidden width, the module's final norm, its pass through the
head and its cross-entropy), forward and backward (perf/stagecut.py). The module's layer runs under the layers' own scopes
and counts in their stages."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.lm_mtp",))
