"""Device time per completed traced step and chip inside `frcnn.update`:
gradient rounding or exchange, the non-finite guard, the optimizer and the
health norms (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.update",))
