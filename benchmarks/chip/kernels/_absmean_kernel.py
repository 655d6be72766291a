"""``sign_compress``'s first kernel: per-block sums of |x - xhat| for the
scale of the scaled sign.

Reads x and xhat once and writes one partial sum per block: the
operands' and result's bytes. Three operations per element.
"""


def cost(call):
    n = call.operands[0].size
    nbytes = sum(a.nbytes for a in call.operands + call.results)
    return 3 * n, nbytes
