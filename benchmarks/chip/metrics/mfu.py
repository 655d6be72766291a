"""Layer: the whole step on the device. Model FLOPs per step over the
traced run's time per step times the chip's peak bf16 FLOP/s (each chip's
share of the step's model FLOPs, on several chips)."""


def read(device, ctx):
    if not ctx.steps or not device.ops:
        return None
    step_s = ctx.trace.window_ns / 1e9 / ctx.steps
    flops = ctx.flops_per_step / ctx.chips
    return 100.0 * flops / (step_s * ctx.peaks["bf16_flops_per_s"])
