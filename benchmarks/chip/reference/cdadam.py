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

Worker k's estimate ``xhat_k`` (and its own view of it under the fault)
lives on worker k's device with its x, m and v; the round moves a
neighbour's estimate to worker k's device one leaf at a time.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference import dadam


def scaled_sign(d: jax.Array) -> jax.Array:
    return jnp.mean(jnp.abs(d)) * jnp.sign(d)


_adam_tree = dadam._adam_tree


def run(params0, batches: Sequence[Sequence], grad_fn: Callable, hp: dict,
        *, gamma: float, first_norms: Callable, exchange: bool = True,
        devices: Optional[Sequence] = None):
    """As :func:`reference.dadam.run`, with compressed gossip."""
    K = len(batches[0])
    W = dadam.ring_weights(K)
    tm = jax.tree_util.tree_map
    devs = [dadam.device_of(devices, k) for k in range(K)]
    xs = [dadam.fresh(params0, d) for d in devs]
    ms = [tm(jnp.zeros_like, x) for x in xs]
    vs = [tm(jnp.zeros_like, x) for x in xs]
    hats = [tm(jnp.zeros_like, x) for x in xs]     # xhat_k, as all hold it
    own = list(hats)                               # fault: k's own view

    def consensus(k, x, hk, *hs):
        """One leaf of x_k + gamma sum_j W[k, j] (xhat_j - xhat_k), the
        neighbours j in increasing order."""
        acc = jnp.zeros_like(x)
        for j in range(K):
            if j == k or W[k, j] <= 0:
                continue
            hj = dadam.put(hs[j], devs[k]) if exchange else jnp.zeros_like(x)
            acc = jax.block_until_ready(acc + float(W[k, j]) * (hj - hk))
        return jax.block_until_ready(x + gamma * acc)

    def advance(h, x):
        """One leaf of xhat + Q(x - xhat)."""
        return jax.block_until_ready(jnp.add(h, scaled_sign(x - h)))

    losses, first = [], []
    for t, step_batches in enumerate(batches, start=1):
        out = [grad_fn(xs[k], dadam.put(step_batches[k], devs[k]))
               for k in range(K)]
        losses.append(float(np.mean([float(l) for l, _ in out])))
        if t == 1:
            first = [first_norms(g, k) for k, (_, g) in enumerate(out)]
        for k in range(K):
            xs[k], ms[k], vs[k] = dadam.split3(
                _adam_tree(xs[k], out[k][1], ms[k], vs[k], hp))
        del out
        if t % hp["period"]:
            continue
        base = hats if exchange else own
        xs = [tm(functools.partial(consensus, k), xs[k], base[k], *hats)
              for k in range(K)]
        base = [tm(advance, base[k], xs[k]) for k in range(K)]
        if exchange:
            hats = base
        else:
            own = base
    return losses, first, xs
