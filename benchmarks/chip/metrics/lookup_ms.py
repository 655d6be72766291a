"""Layer: packed embedding/parameter view. Self time per step of the
compiled step's ops under the program's ``lookup`` scope: the lane-dense
lookup of a narrow packed leaf (an embedding table read straight from the
buffer's (rows, 128) block), forward and backward. ``fwd_ms`` and
``bwd_ms`` count these ops too, as the model's."""
from chipbench.marks import scoped_ms


def read(device, ctx):
    return scoped_ms(device, ctx, "lookup",
                     lambda op, names, bwd: "lookup" in names)
