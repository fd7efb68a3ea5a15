"""Share of the chips' busy time in operations whose `op_name` holds no
`frcnn.*` scope: the health of the cut itself, which a refactor that drops
a scope moves (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.unscoped_pct(ctx)
