"""Device time per completed traced step and chip inside `frcnn.roi_pool`,
forward and backward: ROIPool / ROIAlign / multilevel align (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.roi_pool",))
