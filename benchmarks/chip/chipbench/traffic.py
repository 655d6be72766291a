"""The one general generator of training batches, driven by a traffic
file's parameters and the seed. It makes a pool of distinct batches on the
device in one jitted call; the window cycles through the pool, so it
measures the trainer and not an input pipeline.

Two kinds of batch, chosen by the configuration's family:

* ``ctr`` (DeepFM): one id per field. Field f draws a popularity rank
  from a Zipf law of exponent ``zipf_s`` over its ``n_f`` rows (the
  continuous inverse CDF, floored), and worker k maps ranks to rows
  through its own random permutation of the field, so the workers'
  popular rows differ (non-IID, as in the paper). Labels are Bernoulli
  at ``click_rate``.
* ``lm``: tokens uniform over the (sliced) vocabulary, of which a share
  ``0.5 * min(skew, 1)`` is folded into the worker's own band of
  ``vocab // K`` ids (the non-IID skew of the program's ``lm_batch``).
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def field_rows(config: dict) -> List[int]:
    """Rows of each field of a ``deepfm`` configuration, numeric fields
    first (the Criteo column order: I1-I13, C1-C26), tables capped at
    ``max_ind_range`` (DLRM's hashing of a table into that many rows)."""
    cap = config["max_ind_range"]
    return ([config["numeric_buckets"]] * config["n_numeric"]
            + [min(n, cap) for n in config["table_rows"]])


def zipf_rank(u: jax.Array, n: int, s: float) -> jax.Array:
    """Rank in [0, n) for uniforms ``u``: the inverse CDF of the density
    x^-s on [1, n + 1)."""
    a = 1.0 - s
    top = (n + 1.0) ** a
    x = (1.0 + u * (top - 1.0)) ** (1.0 / a)
    return jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, n - 1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _ctr_pool(key, rows: Tuple[int, ...], K: int, B: int, pool: int,
              s: float, click_rate: float):
    offsets = np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int32)
    k_perm, k_ids, k_lab = jax.random.split(key, 3)
    cols = []
    for f, (n, off) in enumerate(zip(rows, offsets)):
        kf = jax.random.fold_in(k_perm, f)
        perms = jnp.stack([jax.random.permutation(jax.random.fold_in(kf, k),
                                                  n) for k in range(K)])
        u = jax.random.uniform(jax.random.fold_in(k_ids, f), (pool, K, B))
        rank = zipf_rank(u, n, s)
        ids = jnp.take_along_axis(perms[None], rank, axis=-1)
        cols.append(ids + off)
    ids = jnp.stack(cols, axis=-1).astype(jnp.int32)       # (P, K, B, F)
    labels = jax.random.bernoulli(k_lab, click_rate,
                                  (pool, K, B)).astype(jnp.int32)
    return tuple({"feat_ids": ids[i], "label": labels[i]}
                 for i in range(pool))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _lm_pool(key, vocab: int, K: int, B: int, seq: int, pool: int,
             skew: float):
    k_base, k_mask = jax.random.split(key)
    base = jax.random.randint(k_base, (pool, K, B, seq + 1), 0, vocab)
    band = max(vocab // K, 1)
    lo = (jnp.arange(K) * band)[None, :, None, None]
    banded = lo + base % band
    if skew > 0 and K > 1:
        mask = jax.random.bernoulli(k_mask, 0.5 * min(skew, 1.0),
                                    base.shape)
        base = jnp.where(mask, banded, base)
    toks = base.astype(jnp.int32)
    return tuple({"tokens": toks[i]} for i in range(pool))


def make_pool(key: jax.Array, config: dict, traffic: dict) -> Sequence[dict]:
    """``traffic['pool']`` distinct batches, each with a leading worker
    dim K on every leaf."""
    K = traffic["optimizer"]["workers"]
    if config["family"] == "deepfm":
        return _ctr_pool(key, tuple(field_rows(config)), K,
                         traffic["batch_per_worker"], traffic["pool"],
                         float(traffic["zipf_s"]),
                         float(traffic["click_rate"]))
    if config["family"] == "lm":
        return _lm_pool(key, config["vocab_size"], K,
                        traffic["batch_per_worker"], traffic["seq_len"],
                        traffic["pool"], float(traffic["skew"]))
    raise ValueError(f"no traffic generator for family "
                     f"{config['family']!r}")
