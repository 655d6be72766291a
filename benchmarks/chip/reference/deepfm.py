"""DeepFM (Guo et al., arXiv:1703.04247), plain float32.

One example has one active id per field. Its logit is the sum of

* the first-order term: the linear weights of its ids, plus a bias;
* the factorization machine's second-order term over the ids'
  embeddings, ``0.5 * sum_e((sum_f v_fe)^2 - sum_f v_fe^2)``;
* the deep part: the concatenated embeddings through an MLP (ReLU
  between layers, one output unit).

The loss is the mean binary cross-entropy against the click label. The
embedding and linear tables are one table each over the fields' rows
placed end to end (the ids carry their field's offset). No dropout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.numerics import mm


def logits(params: dict, ids: jax.Array, mode: str = "highest"
           ) -> jax.Array:
    """ids: (B, fields) int32 -> (B,) float32."""
    emb = params["embed"][ids].astype(jnp.float32)            # (B, F, E)
    first = jnp.sum(params["linear"][ids], axis=-1) + params["bias"]
    s = jnp.sum(emb, axis=1)
    second = 0.5 * jnp.sum(s * s - jnp.sum(emb * emb, axis=1), axis=-1)
    h = emb.reshape(emb.shape[0], -1)
    layers = params["mlp"]
    for i, layer in enumerate(layers):
        h = mm(h, layer["w"], mode) + layer["b"]
        if i < len(layers) - 1:
            h = jnp.maximum(h, 0.0)
    return first + second + h[:, 0]


def loss(params: dict, batch: dict, mode: str = "highest") -> jax.Array:
    """Mean binary cross-entropy of one worker's batch."""
    z = logits(params, batch["feat_ids"], mode)
    y = batch["label"].astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0.0) - z * y
                    + jnp.log1p(jnp.exp(-jnp.abs(z))))
