"""Share of the device time inside any `frcnn.*` scope whose `op_name` lies
under `transpose(`: the backward pass (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.backward_pct(ctx)
