"""Layer: model. Self time per step of the compiled step's backward ops
under the program's ``model`` scope (``transpose(jvp(model))``), leaving
out the packed views' transposes, which ``packed_view_ms`` reads."""
from chipbench.marks import PACKED_VIEW, scoped_ms


def read(device, ctx):
    return scoped_ms(device, ctx, "model", lambda op, names, bwd:
                     "model" in names and bwd and not names & PACKED_VIEW)
