"""Share of the window the loop spent waiting for data: the `data/fetch`
span the window loop writes around `next(feed)` plus the trainer's own
`data/device_put` span, summed, over the window. Program spans, host clock."""


def read(ctx):
    if ctx["mix"]["feed"] != "loader":
        return None
    lo, hi = ctx["window"]["span_window_us"]
    waited = sum(
        e["dur"] for e in ctx["spans"]
        if e.get("ph") == "X" and e["name"] in ("data/fetch", "data/device_put") and lo <= e["ts"] <= hi
    )
    return 100.0 * waited / (hi - lo)
