"""Layer: trainer host loop. The chip's idle time per step inside the
program's ``fit.dispatch`` host spans, the calls of the jitted step."""
from chipbench.marks import idle_in_spans_ms


def read(device, ctx):
    return idle_in_spans_ms(device, ctx, "fit.dispatch")
