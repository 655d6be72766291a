"""``consensus_mix``'s kernel: CD-Adam's local mixing
``x + gamma * sum_j w_j (xhat_j - xhat_self)`` over the resident buffers.

Reads x, the worker's own estimate and one estimate per neighbour, and
writes x once: the operands' and result's bytes. Two operations per
neighbour term and two more per element.
"""


def cost(call):
    n = call.results[0].size
    nbrs = max(len(call.operands) - 2, 0)
    nbytes = sum(a.nbytes for a in call.operands + call.results)
    return (2 * nbrs + 2) * n, nbytes
