"""The whole step's share of the chips' peak: FLOPs the forward and backward
passes NEED per image (perf/flops.py, from shapes; no recompute counted)
times the image rate of this run's untraced part, over chips x peak."""


def read(ctx):
    pre = ctx["window"].get("pre")
    if not pre or pre["seconds"] <= 0:
        return None
    need = ctx["flops"].train_flops_per_image(ctx["sizes"])
    rate = pre["images"] / pre["seconds"]
    return 100.0 * need * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
