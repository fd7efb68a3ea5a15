"""Device time per completed traced step and chip inside `frcnn.rpn`,
forward and backward: the RPN head and its two losses (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.rpn",))
