"""Share of the first chip's idle time inside the traced window during
which no program span (the tracer's, mirrored onto the profiler's clock) was
open on any thread (perf/stagecut.py)."""

from perf import stagecut


def read(ctx):
    return stagecut.idle_unattributed_pct(ctx)
