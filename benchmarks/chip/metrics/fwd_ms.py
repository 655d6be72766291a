"""Layer: model. Self time per step of the compiled step's forward ops
under the program's ``model`` scope (not under ``transpose(...)``),
leaving out the packed views, which ``packed_view_ms`` reads."""
from chipbench.marks import PACKED_VIEW, scoped_ms


def read(device, ctx):
    return scoped_ms(device, ctx, "model", lambda op, names, bwd:
                     "model" in names and not bwd and not names & PACKED_VIEW)
