"""``gossip_adam_mix``'s kernel: the Adam half-step of every worker and
the gossip mix with its neighbours in one pass.

The call passes p, g, m, v once for the worker itself and again for each
neighbour (``arity = 4 * (1 + degree)``); what the algorithm requires of
HBM is each distinct buffer read once and p, m, v written once. Each
element costs the Adam step (about 12 operations) for itself and for
each neighbour it recomputes, plus a multiply-add per mixed term.
"""

ADAM_FLOPS = 12


def cost(call):
    terms = max(call.arity // 4, 1)          # the worker and its neighbours
    n = call.results[0].size
    nbytes = sum(a.nbytes for a in call.operands + call.results)
    return (ADAM_FLOPS + 2) * terms * n, nbytes
