"""``sign_compress``'s second kernel: the int8 sign of x - xhat and the
estimate's update ``xhat + scale * sign``.

Reads x, xhat and the scales, writes the int8 payload and the new
estimate: the operands' and results' bytes. Four operations per element.
"""


def cost(call):
    n = call.results[-1].size
    nbytes = sum(a.nbytes for a in call.operands + call.results)
    return 4 * n, nbytes
