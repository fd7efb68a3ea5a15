"""`memory_stats()["peak_bytes_in_use"]` of the fullest chip, after the
window and before the reference runs."""


def read(ctx):
    if not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 2**30
