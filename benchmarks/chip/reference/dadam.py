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

Worker k lives on ``devices[k % len(devices)]``: its x, m and v, its
batch and its gradient's call. The mix moves a neighbour's leaf to
worker k's device one leaf at a time. ``devices=None`` keeps every worker
on the default device.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence

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


# x, m and v are donated: a worker's update holds no second copy of them
_adam_tree = jax.jit(lambda x, g, m, v, hp: jax.tree_util.tree_map(
    lambda *a: adam(*a, hp), x, g, m, v), donate_argnums=(0, 2, 3))


def device_of(devices: Optional[Sequence], k: int):
    """Worker k's device; None (the default device) without ``devices``."""
    return None if devices is None else devices[k % len(devices)]


def put(tree, device):
    """``tree`` on ``device``; where it is for None."""
    return tree if device is None else jax.device_put(tree, device)


def fresh(tree, device):
    """A copy of ``tree`` on ``device`` that no other array shares, so
    that the update may donate it."""
    return jax.device_put(tree, device, may_alias=False)


def split3(tree):
    """A tree of (x, m, v) tuples -> three trees."""
    is_t = lambda t: isinstance(t, tuple)        # noqa: E731
    return tuple(jax.tree_util.tree_map(lambda t: t[i], tree, is_leaf=is_t)
                 for i in range(3))


def mix(params: Sequence, W: np.ndarray, devs: Sequence) -> List:
    """x_k = sum_j W[k, j] x_j for every worker, on worker k's device
    ``devs[k]``, summed in the order j = 0..K-1. Each term is added
    before the next neighbour's leaf is moved, so that a few copies of one
    leaf are in flight and not all of them."""
    K = len(params)

    def leaf(k, *xs):
        acc = 0
        for j in range(K):
            acc = jax.block_until_ready(
                acc + float(W[k, j]) * put(xs[j], devs[k]))
        return acc

    return [jax.tree_util.tree_map(functools.partial(leaf, k), *params)
            for k in range(K)]


def run(params0, batches: Sequence[Sequence], grad_fn: Callable, hp: dict,
        *, first_norms: Callable, gossip: bool = True,
        devices: Optional[Sequence] = None):
    """Follow ``len(batches)`` steps of K workers from the same params.

    ``batches[t][k]`` is worker k's batch at step t + 1; ``grad_fn(params,
    batch) -> (loss, grads)``; ``first_norms(grads, k)`` reduces worker k's
    first gradient on its device, and the tree is dropped. Returns the
    per-step mean losses, ``first_norms`` of each worker, and the final
    per-worker params, each on its worker's device. ``gossip=False``
    leaves the exchange out (a planted fault)."""
    K = len(batches[0])
    W = ring_weights(K)
    devs = [device_of(devices, k) for k in range(K)]
    xs = [fresh(params0, d) for d in devs]
    ms = [jax.tree_util.tree_map(jnp.zeros_like, x) for x in xs]
    vs = [jax.tree_util.tree_map(jnp.zeros_like, x) for x in xs]
    losses, first = [], []
    for t, step_batches in enumerate(batches, start=1):
        out = [grad_fn(xs[k], put(step_batches[k], devs[k]))
               for k in range(K)]
        losses.append(float(np.mean([float(l) for l, _ in out])))
        if t == 1:
            first = [first_norms(g, k) for k, (_, g) in enumerate(out)]
        for k in range(K):
            xs[k], ms[k], vs[k] = split3(
                _adam_tree(xs[k], out[k][1], ms[k], vs[k], hp))
        del out
        if gossip and t % hp["period"] == 0:
            xs = mix(xs, W, devs)
    return losses, first, xs
