"""1 - busy / traced window, mean over the chips."""


def read(ctx):
    t = ctx["trace"]
    if t["trace_window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["trace_window_s"])
