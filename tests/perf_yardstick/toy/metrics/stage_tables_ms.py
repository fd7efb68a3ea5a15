"""Device time per completed traced step inside the toy's two stage scopes,
`bigram.embed` and `bigram.logits`, forward and backward: a reader of its
own, over perf/stagecut.py and the scope prefix its reference module states."""

from perf import stagecut


def read(ctx):
    return stagecut.stage_ms(ctx, ("bigram.embed", "bigram.logits"))
