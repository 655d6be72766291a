"""Layer: optimizer kernels. Summed device time per step of the Mosaic
kernel events of the compiled step that lie under the program's ``opt``
scope (a kernel of the model's, under ``model``, does not count); nothing
where the step has no such kernel."""
from chipbench.marks import opt_kernels


def read(device, ctx):
    ks = opt_kernels(device, ctx)
    if not ks or not ctx.steps:
        return None
    return sum(o.dur for o in ks) / ctx.steps / 1e6
