"""Shared model building blocks: norms, RoPE, embeddings, initializers.

All modules are pure functions over explicit parameter pytrees (dicts of
jnp arrays). Parameters are created by ``init_*`` helpers and consumed by
the matching ``apply`` functions; there is no module framework — this keeps
pytrees trivially stackable over the decentralized worker dim and over the
layer dim (for scan).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import pack as packing

PyTree = Any


# ------------------------------- init --------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None
               ) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
            ).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
            ).astype(dtype)


# ------------------------------- lookup ------------------------------------


def lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """``table[ids]``, read from the packed buffer's own rows where the
    table is a narrow leaf of it (``kernels.pack.take_rows``)."""
    return packing.take_rows(table, ids)


# ------------------------------- norms -------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    # statistics in f32, but the HIDDEN tensor itself stays in its compute
    # dtype: upcasting it lets XLA hoist converts past the TP partial-sum
    # boundary and doubles every activation all-reduce to f32 bytes
    # (EXPERIMENTS.md perf iteration on train_4k).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return (x * inv) * weight.astype(x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return (x - mu.astype(x.dtype)) * inv * weight.astype(x.dtype) \
        + bias.astype(x.dtype)


# -------------------------------- RoPE --------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    dt = x.dtype
    freqs = rope_frequencies(x.shape[-1], theta)              # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (.., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                    # (.., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(dt)


def sinusoidal_positions(n_ctx: int, d: int) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings."""
    pos = jnp.arange(n_ctx, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-math.log(10000.0) * dim / max(d // 2 - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ------------------------------ activations ---------------------------------


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x.astype(jnp.float32), approximate=True).astype(x.dtype)


# ------------------------------- losses -------------------------------------


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token CE. logits (..., S, V), labels (..., S) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def stable_softmax(x: jax.Array, axis: int = -1) -> jax.Array:
    x = x.astype(jnp.float32)
    return jax.nn.softmax(x, axis=axis)
