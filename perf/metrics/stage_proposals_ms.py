"""Device time per completed traced step and chip inside `frcnn.proposals`:
box decode, clip, top-k and NMS (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.proposals",))
