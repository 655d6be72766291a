"""The paper's own experiment models: DeepFM [8], Wide&Deep [6], ResNet20 [9].

These are the models the paper trains with D-Adam / CD-Adam (Criteo CTR,
MovieLens-20M, CIFAR-10). Hyperparameters match Section 6.1: embedding dim
10, MLP 400-400-400, dropout 0.5 (we expose the rate; benchmarks run
deterministic eval-mode unless a key is passed).

All parameters are float32 (these are small models; the paper's setting).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common

PyTree = Any


# ------------------------------- DeepFM --------------------------------------


def init_deepfm(key, n_features: int, n_fields: int, embed_dim: int = 10,
                hidden: Tuple[int, ...] = (400, 400, 400)) -> PyTree:
    ks = jax.random.split(key, 4 + len(hidden))
    p = {
        "embed": (jax.random.normal(ks[0], (n_features, embed_dim))
                  * 0.01),
        "linear": jax.random.normal(ks[1], (n_features,)) * 0.01,
        "bias": jnp.zeros(()),
        "mlp": [],
    }
    d_in = n_fields * embed_dim
    mlp = []
    for i, h in enumerate(hidden):
        mlp.append({
            "w": common.dense_init(ks[2 + i], d_in, h, jnp.float32),
            "b": jnp.zeros((h,)),
        })
        d_in = h
    mlp.append({
        "w": common.dense_init(ks[2 + len(hidden)], d_in, 1, jnp.float32),
        "b": jnp.zeros((1,)),
    })
    p["mlp"] = mlp
    return p


def deepfm_logits(params: PyTree, feat_ids: jax.Array,
                  dropout_key: Optional[jax.Array] = None,
                  dropout_rate: float = 0.5) -> jax.Array:
    """feat_ids: (B, n_fields) int32 — one active feature id per field."""
    emb = common.lookup(params["embed"], feat_ids)    # (B, F, E)
    # first order
    first = (jnp.sum(common.lookup(params["linear"], feat_ids), axis=-1)
             + params["bias"])
    # FM second order: 0.5 * ((sum e)^2 - sum e^2)
    s = jnp.sum(emb, axis=1)
    s2 = jnp.sum(emb * emb, axis=1)
    second = 0.5 * jnp.sum(s * s - s2, axis=-1)
    # deep part
    h = emb.reshape(emb.shape[0], -1)
    for i, layer in enumerate(params["mlp"]):
        h = h @ layer["w"] + layer["b"]
        if i < len(params["mlp"]) - 1:
            h = jax.nn.relu(h)
            if dropout_key is not None:
                dropout_key, sub = jax.random.split(dropout_key)
                mask = jax.random.bernoulli(sub, 1 - dropout_rate, h.shape)
                h = h * mask / (1 - dropout_rate)
    return first + second + h[:, 0]


def deepfm_loss(params: PyTree, batch: PyTree,
                dropout_key: Optional[jax.Array] = None) -> jax.Array:
    """batch: {'feat_ids': (B, F) int32, 'label': (B,) in {0,1}}."""
    logits = deepfm_logits(params, batch["feat_ids"], dropout_key)
    y = batch["label"].astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


# ------------------------------ Wide&Deep ------------------------------------


def init_widedeep(key, n_features: int, n_fields: int, embed_dim: int = 10,
                  hidden: Tuple[int, ...] = (400, 400, 400)) -> PyTree:
    p = init_deepfm(key, n_features, n_fields, embed_dim, hidden)
    return p  # wide part = 'linear'; deep part = 'mlp'; no FM term


def widedeep_logits(params: PyTree, feat_ids: jax.Array,
                    dropout_key: Optional[jax.Array] = None,
                    dropout_rate: float = 0.5) -> jax.Array:
    emb = common.lookup(params["embed"], feat_ids)
    wide = (jnp.sum(common.lookup(params["linear"], feat_ids), axis=-1)
            + params["bias"])
    h = emb.reshape(emb.shape[0], -1)
    for i, layer in enumerate(params["mlp"]):
        h = h @ layer["w"] + layer["b"]
        if i < len(params["mlp"]) - 1:
            h = jax.nn.relu(h)
            if dropout_key is not None:
                dropout_key, sub = jax.random.split(dropout_key)
                mask = jax.random.bernoulli(sub, 1 - dropout_rate, h.shape)
                h = h * mask / (1 - dropout_rate)
    return wide + h[:, 0]


def widedeep_loss(params: PyTree, batch: PyTree,
                  dropout_key: Optional[jax.Array] = None) -> jax.Array:
    logits = widedeep_logits(params, batch["feat_ids"], dropout_key)
    y = batch["label"].astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


# ------------------------------- ResNet20 ------------------------------------


def _conv_init(key, k, c_in, c_out):
    fan_in = k * k * c_in
    return jax.random.normal(key, (k, k, c_in, c_out)) * jnp.sqrt(
        2.0 / fan_in)


def init_resnet20(key, n_classes: int = 10, width: int = 16) -> PyTree:
    """He et al. CIFAR ResNet: 3 stages x 3 blocks x 2 convs + stem + fc."""
    ks = iter(jax.random.split(key, 64))
    p = {"stem": _conv_init(next(ks), 3, 3, width), "stages": []}
    c_in = width
    stages = []
    for stage, c_out in enumerate([width, 2 * width, 4 * width]):
        blocks = []
        for b in range(3):
            blk = {
                "conv1": _conv_init(next(ks), 3, c_in, c_out),
                "conv2": _conv_init(next(ks), 3, c_out, c_out),
                "scale1": jnp.ones((c_out,)), "bias1": jnp.zeros((c_out,)),
                "scale2": jnp.ones((c_out,)), "bias2": jnp.zeros((c_out,)),
            }
            if c_in != c_out:
                blk["proj"] = _conv_init(next(ks), 1, c_in, c_out)
            blocks.append(blk)
            c_in = c_out
        stages.append(blocks)
    p["stages"] = stages
    p["fc_w"] = common.dense_init(next(ks), c_in, n_classes, jnp.float32)
    p["fc_b"] = jnp.zeros((n_classes,))
    return p


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _norm_act(x, scale, bias):
    # GroupNorm(8) stand-in for BatchNorm — batch-stat-free so the per-worker
    # loss stays a pure function (decentralized workers have no shared BN
    # stats; the paper syncs none either).
    B, H, W, C = x.shape
    g = min(8, C)
    xg = x.reshape(B, H, W, g, C // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + 1e-5)
    return jax.nn.relu(xg.reshape(B, H, W, C) * scale + bias)


def resnet20_logits(params: PyTree, images: jax.Array) -> jax.Array:
    """images: (B, 32, 32, 3) float32."""
    x = _conv(images, params["stem"])
    for stage, blocks in enumerate(params["stages"]):
        for b, blk in enumerate(blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            h = _conv(x, blk["conv1"], stride)
            h = _norm_act(h, blk["scale1"], blk["bias1"])
            h = _conv(h, blk["conv2"])
            sc = x
            if "proj" in blk:
                sc = _conv(x, blk["proj"], stride)
            x = _norm_act(h + sc, blk["scale2"], blk["bias2"])
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["fc_w"] + params["fc_b"]


def resnet20_loss(params: PyTree, batch: PyTree) -> jax.Array:
    logits = resnet20_logits(params, batch["images"])
    labels = batch["label"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)
