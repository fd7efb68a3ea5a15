"""Mean duration of the trainer's `step/dispatch` spans inside the window:
what one call of the jitted step costs the host."""


def read(ctx):
    lo, hi = ctx["window"]["span_window_us"]
    durs = [
        e["dur"] for e in ctx["spans"]
        if e.get("ph") == "X" and e["name"] == "step/dispatch" and lo <= e["ts"] <= hi
    ]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3
