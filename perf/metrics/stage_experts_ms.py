"""Device time per completed traced step and chip inside `frcnn.lm_router`, `frcnn.lm_experts` and, nested in it, `frcnn.lm_expert_mm`: scores, top-k, the sort by expert, the balance bias; the rows gathered, the grouped products, the combine by token, forward and
backward (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.lm_router", "frcnn.lm_experts", "frcnn.lm_expert_mm"))
