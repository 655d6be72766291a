"""Layer: trainer host loop. The chip's idle time per step inside the
program's ``fit.log`` host spans: the loss sync, the consensus error's
eager dispatches and the log appends."""
from chipbench.marks import idle_in_spans_ms


def read(device, ctx):
    return idle_in_spans_ms(device, ctx, "fit.log")
