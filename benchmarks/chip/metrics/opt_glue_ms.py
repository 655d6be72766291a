"""Layer: optimizer, outside kernels. Self time per step of the compiled
step's ops under the program's ``opt`` scope that are neither a Mosaic
kernel nor a collective, nor a packed view: the copies into the kernels'
operand layout, the period ``cond`` and the round bookkeeping."""
from chipbench.marks import PACKED_VIEW, scoped_ms
from chipbench.trace import is_collective


def read(device, ctx):
    return scoped_ms(device, ctx, "opt", lambda op, names, bwd:
                     "opt" in names and not names & PACKED_VIEW
                     and not ctx.is_kernel(op) and not is_collective(op))
