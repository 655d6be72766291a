"""CD-Adam (Algorithm 2 of arXiv:2008.10422), plain float32.

Each worker runs D-Adam's local step; at a communication round
(t % p == 0) worker k, holding estimates ``xhat_j`` of itself and of each
neighbour j (all zero at the start):

    x_k   = x_k + gamma * sum_{j != k} W[k, j] (xhat_j - xhat_k)
    q_k   = Q(x_k - xhat_k)            Q: scaled sign, one scale per leaf,
                                       mean |.| over the leaf
    xhat_k += q_k on every worker that holds an estimate of k

so every estimate of worker k moves by the same ``q_k`` and one estimate
per worker stands for all its copies. ``exchange=False`` plants a fault:
no worker receives its neighbours' ``q``, so their estimates never move
from zero on its side.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference import dadam


def scaled_sign(d: jax.Array) -> jax.Array:
    return jnp.mean(jnp.abs(d)) * jnp.sign(d)


_adam_tree = dadam._adam_tree


def run(params0, batches: Sequence[Sequence], grad_fn: Callable, hp: dict,
        *, gamma: float, exchange: bool = True):
    """As :func:`reference.dadam.run`, with compressed gossip."""
    K = len(batches[0])
    W = dadam.ring_weights(K)
    tm = jax.tree_util.tree_map
    xs = [params0] * K
    ms = [tm(jnp.zeros_like, params0)] * K
    vs = list(ms)
    hats = [tm(jnp.zeros_like, params0)] * K       # xhat_k, as all hold it
    own = list(hats)                               # fault: k's own view
    losses, first = [], None
    for t, step_batches in enumerate(batches, start=1):
        out = [grad_fn(xs[k], step_batches[k]) for k in range(K)]
        losses.append(float(np.mean([float(l) for l, _ in out])))
        if first is None:
            first = [g for _, g in out]
        for k in range(K):
            xs[k], ms[k], vs[k] = dadam.split3(
                _adam_tree(xs[k], out[k][1], ms[k], vs[k], hp))
        del out
        if t % hp["period"]:
            continue
        view = hats if exchange else None
        new_x = []
        for k in range(K):
            nbr = [j for j in range(K) if j != k and W[k, j] > 0]
            acc = tm(jnp.zeros_like, xs[k])
            for j in nbr:
                hj = view[j] if exchange else tm(jnp.zeros_like, xs[k])
                hk = hats[k] if exchange else own[k]
                acc = tm(lambda a, x, y, w=float(W[k, j]): a + w * (x - y),
                         acc, hj, hk)
            new_x.append(tm(lambda x, a: x + gamma * a, xs[k], acc))
        xs = new_x
        base = hats if exchange else own
        q = [tm(lambda x, h: scaled_sign(x - h), xs[k], base[k])
             for k in range(K)]
        if exchange:
            hats = [tm(jnp.add, hats[k], q[k]) for k in range(K)]
        else:
            own = [tm(jnp.add, own[k], q[k]) for k in range(K)]
    return losses, first, xs
