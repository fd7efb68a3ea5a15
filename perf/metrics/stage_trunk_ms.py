"""Device time per completed traced step and chip inside `frcnn.input` and
`frcnn.trunk`, forward and backward: augmentation, preprocess, the trunk and
the FPN neck where there is one (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("frcnn.input", "frcnn.trunk"))
