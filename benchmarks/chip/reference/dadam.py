"""D-Adam (Algorithm 1 of arXiv:2008.10422), plain float32, one worker's
parameter tree at a time.

Per worker k at step t (counted from 1):

    m = b1 m + (1 - b1) g
    v = b2 v + (1 - b2) g^2
    x = x - eta m / (sqrt(v) + tau)
    if t % p == 0:  x_k = sum_j W[k, j] x_j

with no bias correction, as the paper states it. ``W`` is the ring: 1/3
to itself and to each of its two neighbours; with two workers the two
neighbours are one worker and the matrix is 1/2 everywhere.
"""
from __future__ import annotations

from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def ring_weights(K: int) -> np.ndarray:
    if K == 1:
        return np.ones((1, 1))
    if K == 2:
        return np.full((2, 2), 0.5)
    W = np.zeros((K, K))
    for k in range(K):
        W[k, k] = W[k, (k + 1) % K] = W[k, (k - 1) % K] = 1.0 / 3.0
    return W


def adam(x, g, m, v, hp: dict):
    m = hp["beta1"] * m + (1.0 - hp["beta1"]) * g
    v = hp["beta2"] * v + (1.0 - hp["beta2"]) * g * g
    x = x - hp["eta"] * m / (jnp.sqrt(v) + hp["tau"])
    return x, m, v


_adam_tree = jax.jit(lambda x, g, m, v, hp: jax.tree_util.tree_map(
    lambda *a: adam(*a, hp), x, g, m, v))


def split3(tree):
    """A tree of (x, m, v) tuples -> three trees."""
    is_t = lambda t: isinstance(t, tuple)        # noqa: E731
    return tuple(jax.tree_util.tree_map(lambda t: t[i], tree, is_leaf=is_t)
                 for i in range(3))


def mix(params: Sequence, W: np.ndarray) -> List:
    """x_k = sum_j W[k, j] x_j for every worker."""
    K = len(params)
    return [jax.tree_util.tree_map(
        lambda *xs, k=k: sum(float(W[k, j]) * xs[j] for j in range(K)),
        *params) for k in range(K)]


def run(params0, batches: Sequence[Sequence], grad_fn: Callable, hp: dict,
        *, gossip: bool = True):
    """Follow ``len(batches)`` steps of K workers from the same params.

    ``batches[t][k]`` is worker k's batch at step t + 1; ``grad_fn(params,
    batch) -> (loss, grads)``. Returns the per-step mean losses, worker
    k's first gradient for each k, and the final per-worker params.
    ``gossip=False`` leaves the exchange out (a planted fault)."""
    K = len(batches[0])
    W = ring_weights(K)
    xs = [params0] * K
    ms = [jax.tree_util.tree_map(jnp.zeros_like, params0)] * K
    vs = list(ms)
    losses, first = [], None
    for t, step_batches in enumerate(batches, start=1):
        out = [grad_fn(xs[k], step_batches[k]) for k in range(K)]
        losses.append(float(np.mean([float(l) for l, _ in out])))
        if first is None:
            first = [g for _, g in out]
        for k in range(K):
            xs[k], ms[k], vs[k] = split3(
                _adam_tree(xs[k], out[k][1], ms[k], vs[k], hp))
        del out
        if gossip and t % hp["period"] == 0:
            xs = mix(xs, W)
    return losses, first, xs
