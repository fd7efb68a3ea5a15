"""The fullest held expert's pairs over the mean of the held experts', in the
expert layer where that is worst: the program's counter
`lm/expert_load_max_over_mean`, mean over the window's events."""


def read(ctx):
    values = [
        e["args"]["value"] for e in ctx["spans"] if e.get("ph") == "C" and e.get("name") == "lm/expert_load_max_over_mean"
    ]
    return sum(values) / len(values) if values else None
