"""Flash attention (prefill/train) as a Pallas TPU kernel with GQA.

Grid: (B, Hq, n_q_blocks, n_kv_blocks) with the KV dim innermost and
``arbitrary`` semantics so the (acc, m, l) online-softmax state persists in
VMEM scratch across KV iterations — the score tile never leaves VMEM (the
insight flash attention brings to the TPU memory hierarchy: HBM->VMEM
streaming of K/V tiles against a resident Q tile, MXU-shaped (block, 128)
tiles).

Causal/window masking is applied per-tile from block indices; fully-masked
tiles still iterate (static grid) but skip the dot via ``pl.when``.
"""
from __future__ import annotations

import functools
import math
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_kv: int, n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_kv
    # static-shape tile positions
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_kv), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_kv), 1)
    needed = jnp.bool_(True)
    if causal:
        needed = needed & (k_start <= q_start + block_q - 1)
    if window and window > 0:
        needed = needed & (k_start + block_kv - 1 >= q_start - window + 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (block_q, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (block_kv, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        ok = jnp.ones((block_q, block_kv), bool)
        if causal:
            ok = ok & (k_pos <= q_pos)
        if window and window > 0:
            ok = ok & (q_pos - k_pos < window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v_t = v_ref[0, 0].astype(jnp.float32)        # (block_kv, D)
        pv = jax.lax.dot_general(p, v_t, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _final():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 512, block_kv: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B, S, Hq, D); k, v: (B, T, Hk, D) -> (B, S, Hq, D).

    D should be a multiple of 128 lanes for MXU alignment (64 works via
    padding by Mosaic); block_q/block_kv are sublane-aligned tile heights.
    """
    B, S, Hq, D = q.shape
    T, Hk = k.shape[1], k.shape[2]
    G = Hq // Hk
    bq = min(block_q, S)
    while S % bq:
        bq -= 1
    bkv = min(block_kv, T)
    while T % bkv:
        bkv -= 1
    n_q, n_kv = S // bq, T // bkv
    scale = 1.0 / math.sqrt(D)

    # layout: (B, H, S, D) so tiles are (bq, D) matrices
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_kv=bkv, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bkv, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            # VMEM scratch: acc (bq, D), running max/denominator (bq, 1)
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)
