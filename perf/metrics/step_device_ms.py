"""Device-busy time per completed step: the union of the intervals in which
an operation ran on a chip during the traced slice (mean over the chips),
over the steps dispatched and drained inside it."""


def read(ctx):
    steps = ctx["window"].get("traced_steps", 0)
    if steps <= 0:
        return None
    return 1e3 * ctx["trace"]["busy_s"] / steps
