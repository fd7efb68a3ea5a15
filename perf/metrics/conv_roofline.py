"""The convolutions' share of their roofline: the least time a chip could
take for the step's convolution FLOPs and bytes (perf/flops.py: per pass the
larger of FLOPs over peak and bytes over bandwidth) over the summed device
time of the trace's convolution operations, per step and chip."""


def read(ctx):
    steps = ctx["window"].get("traced_steps", 0)
    conv_s = ctx["trace"]["conv_s"]
    if steps <= 0 or conv_s <= 0:
        return None
    least = ctx["flops"].conv_roofline_seconds(
        ctx["sizes"], ctx["batch"] // ctx["chips"],
        ctx["peaks"]["bf16_flops_per_s"], ctx["peaks"]["hbm_bytes_per_s"],
    )["least_s"]
    return 100.0 * least * steps / conv_s
