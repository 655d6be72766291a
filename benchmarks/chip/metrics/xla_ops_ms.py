"""Layer: model and XLA glue. Device busy time per step during which no
Pallas kernel and no collective runs (the union of all op intervals less
the union of the kernels' and collectives'; a control-flow op's event
spans the kernels it runs)."""
from chipbench.trace import is_collective, union_ns


def read(device, ctx):
    if not device.ops or not ctx.steps:
        return None
    busy = union_ns((o.start, o.end) for o in device.ops)
    other = union_ns((o.start, o.end) for o in device.ops
                     if ctx.is_kernel(o) or is_collective(o))
    return (busy - other) / ctx.steps / 1e6
