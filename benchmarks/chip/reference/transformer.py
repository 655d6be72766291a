"""A LLaMA-style dense decoder (the Yi-6B architecture, arXiv:2403.04652),
plain float32.

Per layer: RMSNorm, grouped-query attention with rotary position
embeddings (rotate-half convention, inverse frequencies
``theta^(-2i/head_dim)``) under a causal mask, a residual add; RMSNorm,
the SwiGLU feed-forward ``W_down(silu(W_gate x) * W_up x)``, a residual
add. Then a final RMSNorm and an untied output head. Query head ``h``
reads key/value head ``h // (n_heads / n_kv_heads)``. The loss is the mean
next-token cross-entropy over the (sliced) vocabulary.

Layer weights are stacked over a leading layer dim, the layout the
benchmark feeds the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.numerics import mm


def linear(x: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    lead = x.shape[:-1]
    return mm(x.reshape(-1, x.shape[-1]), w, mode).reshape(
        lead + (w.shape[-1],))


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, heads, head_dim)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(layer: dict, x: jax.Array, cfg: dict, mode: str) -> jax.Array:
    B, S, _ = x.shape
    H, Hk, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q = rope(linear(x, layer["wq"], mode).reshape(B, S, H, hd),
             cfg["rope_theta"])
    k = rope(linear(x, layer["wk"], mode).reshape(B, S, Hk, hd),
             cfg["rope_theta"])
    v = linear(x, layer["wv"], mode).reshape(B, S, Hk, hd)
    k = jnp.repeat(k, H // Hk, axis=2)
    v = jnp.repeat(v, H // Hk, axis=2)
    q, k, v = (jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v))
    scores = mm(q, jnp.swapaxes(k, -1, -2), mode) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.transpose(mm(probs, v, mode), (0, 2, 1, 3)).reshape(
        B, S, H * hd)
    return linear(out, layer["wo"], mode)


def swiglu(layer: dict, x: jax.Array, mode: str) -> jax.Array:
    gate = linear(x, layer["w_gate"], mode)
    up = linear(x, layer["w_up"], mode)
    return linear(jax.nn.silu(gate) * up, layer["w_down"], mode)


def loss(params: dict, batch: dict, cfg: dict, mode: str = "highest"
         ) -> jax.Array:
    """batch: {'tokens': (B, S + 1) int32}; cfg: the sizes of
    ``chipbench.systems.lm_sizes``."""
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    h = params["embed"][inputs].astype(jnp.float32)
    eps = cfg["norm_eps"]
    layers = params["layers"]
    for i in range(cfg["n_layers"]):
        layer = jax.tree_util.tree_map(lambda x: x[i], layers)
        h = h + attention(layer["attn"], rms_norm(h, layer["norm1"], eps),
                          cfg, mode)
        h = h + swiglu(layer["mlp"], rms_norm(h, layer["norm2"], eps), mode)
    z = linear(rms_norm(h, params["final_norm"], eps), params["lm_head"],
               mode)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
