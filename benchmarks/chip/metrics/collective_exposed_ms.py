"""Layer: cross-chip gossip. Per step, the time in collective events
during which no other operation computes on that chip (control-flow ops,
whose events span their bodies, do not count as computing)."""
from chipbench.trace import CONTROL_FLOW, is_collective, union_ns


def read(device, ctx):
    coll = [(o.start, o.end) for o in device.ops if is_collective(o)]
    if not coll or not ctx.steps:
        return None
    other = [(o.start, o.end) for o in device.ops
             if not is_collective(o) and o.kind not in CONTROL_FLOW]
    exposed = union_ns(coll + other) - union_ns(other)
    return exposed / ctx.steps / 1e6
