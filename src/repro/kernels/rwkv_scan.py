"""RWKV6 WKV recurrence as a sequence-chunked Pallas TPU kernel.

The WKV scan is the compute hot spot of the rwkv6-3b assigned arch: per
(batch, head) it carries a (D, D) state through S sequential steps

    y_t = r_t . (S + (u * k_t) (x) v_t)
    S  <- diag(w_t) S + k_t (x) v_t

TPU adaptation: the state lives in VMEM scratch for the whole sequence —
grid (B, H, n_chunks) with the chunk dim ``arbitrary`` — and each grid step
streams one (C, D) chunk of r/k/v/w from HBM, runs the C sequential updates
entirely in VMEM (fori_loop over rows; D=64 head matrices are VPU-friendly),
and writes the (C, D) output chunk. HBM traffic is exactly one read of
r,k,v,w and one write of y — the recurrence itself never leaves VMEM
(the XLA scan path round-trips the (D, D) state per step).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sf_ref,
                state_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)   # (C, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)      # (D,)

    def body(t, y):
        r_t = r[t]                         # (D,)
        kv = k[t][:, None] * v[t][None, :]             # (D, D)
        S = state_ref[...]
        y_t = (r_t[None, :] @ (S + u[:, None] * kv))[0]  # (D,)
        state_ref[...] = w[t][:, None] * S + kv
        return y.at[t].set(y_t)

    y = jax.lax.fori_loop(0, chunk, body,
                          jnp.zeros((chunk, r.shape[1]), jnp.float32))
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _final():
        sf_ref[0, 0] = state_ref[...]


def rwkv_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, state: jax.Array, *, chunk: int = 128,
              interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """r,k,v,w: (B, S, H, D); u: (H, D); state: (B, H, D, D) f32.
    Returns (y (B, S, H, D) f32, final state (B, H, D, D) f32)."""
    B, S, H, D = r.shape
    c = min(chunk, S)
    while S % c:
        c -= 1
    n_chunks = S // c

    # layout: (B, H, S, D) chunk tiles
    rt, kt, vt, wt = (jnp.moveaxis(t, 1, 2) for t in (r, k, v, w))

    kernel = functools.partial(_wkv_kernel, chunk=c, n_chunks=n_chunks)
    y, sf = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, c, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, c, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, c, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, c, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, D), lambda b, h, i: (h, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rt, kt, vt, wt, u, state)
    return jnp.moveaxis(y, 2, 1), sf
