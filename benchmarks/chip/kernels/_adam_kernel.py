"""``fused_adam``'s kernel: one Adam step over (rows, 128) tiles.

Reads p, g, m, v and writes p, m, v once each: the required HBM bytes
are the operands' and results' bytes. About 12 operations per element
(two moment updates, a square root, a divide and the step).
"""

FLOPS_PER_ELEMENT = 12


def cost(call):
    n = call.results[0].size
    nbytes = sum(a.nbytes for a in call.operands + call.results)
    return FLOPS_PER_ELEMENT * n, nbytes
