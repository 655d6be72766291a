"""Weights from the seed, made on the device in one jitted call.

The program is fed these weights and the reference makes them again from
the same seed after the window. The trees have the program's parameter
layout (its ``init`` takes them as a user's model would hand them over).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size up to 64 bits (``PRNGKey`` alone
    keeps only the low 32 bits of a large Python int)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _dense(key, d_in, d_out):
    return _normal(key, (d_in, d_out), 1.0 / math.sqrt(d_in))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def deepfm(key, rows: int, fields: int, embed: int, hidden: tuple) -> dict:
    ks = jax.random.split(key, 3 + len(hidden))
    mlp, d_in = [], fields * embed
    for i, h in enumerate(tuple(hidden) + (1,)):
        mlp.append({"w": _dense(ks[2 + i], d_in, h),
                    "b": jnp.zeros((h,), jnp.float32)})
        d_in = h
    return {"embed": _normal(ks[0], (rows, embed), 0.01),
            "linear": _normal(ks[1], (rows,), 0.01),
            "bias": jnp.zeros((), jnp.float32),
            "mlp": mlp}


@functools.partial(jax.jit, static_argnums=(1,))
def lm(key, sizes: tuple) -> dict:
    """``sizes``: the sorted ``(name, value)`` pairs of the ``lm``
    family's ``sizes``."""
    c = dict(sizes)
    L, d, F, V = c["n_layers"], c["d_model"], c["d_ff"], c["vocab_size"]
    hq, hkv = c["n_heads"] * c["head_dim"], c["n_kv_heads"] * c["head_dim"]
    ks = iter(jax.random.split(key, 9))

    def stack(k, d_in, d_out):
        return jax.vmap(lambda kk: _dense(kk, d_in, d_out))(
            jax.random.split(k, L))

    ones = jnp.ones((L, d), jnp.float32)
    return {
        "embed": _normal(next(ks), (V, d), 0.02),
        "layers": {
            "attn": {"wq": stack(next(ks), d, hq),
                     "wk": stack(next(ks), d, hkv),
                     "wv": stack(next(ks), d, hkv),
                     "wo": stack(next(ks), hq, d)},
            "norm1": ones, "norm2": ones,
            "mlp": {"w_gate": stack(next(ks), d, F),
                    "w_up": stack(next(ks), d, F),
                    "w_down": stack(next(ks), F, d)},
        },
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": _dense(next(ks), d, V),
    }
