"""Per step, time inside all-reduce operations on one chip during which no
other operation runs on it. Nothing to read on one chip."""


def read(ctx):
    steps = ctx["window"].get("traced_steps", 0)
    if ctx["chips"] < 2 or steps <= 0:
        return None
    return 1e3 * ctx["trace"]["allreduce_exposed_s"] / steps
