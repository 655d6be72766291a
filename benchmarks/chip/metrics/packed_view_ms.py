"""Layer: packed embedding/parameter view. Self time per step of the
compiled step's ops under the program's ``unpack`` or ``pack`` scope, the
per-leaf views of the packed (K, rows, 128) buffer: forward, and their
transposes in the backward pass, which deposit the gradient packed."""
from chipbench.marks import PACKED_VIEW, scoped_ms


def read(device, ctx):
    return scoped_ms(device, ctx, "unpack",
                     lambda op, names, bwd: bool(names & PACKED_VIEW))
