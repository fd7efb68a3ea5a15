"""Device time per completed traced step and chip inside
`frcnn.anchor_targets` and `frcnn.roi_targets`: both target creators, IoU
matching and sampling (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.anchor_targets", "frcnn.roi_targets"))
