"""Layer: trainer host loop. The share of the traced window in which no
operation ran on the chip: 1 - (union of op intervals) / window."""
from chipbench.trace import union_ns


def read(device, ctx):
    if not device.ops:
        return None
    busy = union_ns((o.start, o.end) for o in device.ops)
    return 100.0 * (1.0 - busy / ctx.trace.window_ns)
