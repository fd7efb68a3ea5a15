"""Device time per completed traced step and chip inside `frcnn.box_head`
and under no scope nested in it, forward and backward: the tail, the two
heads, the class-delta gather and the two head losses (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.box_head",))
