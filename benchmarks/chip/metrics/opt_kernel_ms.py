"""Layer: optimizer kernels. Summed device time of the Mosaic kernel
events per step (every Pallas kernel of these cells is an optimizer
kernel)."""


def read(device, ctx):
    ks = [o for o in device.ops if ctx.is_kernel(o)]
    if not ks or not ctx.steps:
        return None
    return sum(o.dur for o in ks) / ctx.steps / 1e6
