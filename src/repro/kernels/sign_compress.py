"""CHOCO error-feedback sign compression as Pallas TPU kernels.

CD-Adam's communication round compresses the residual delta = x - xhat to
``q = int8 sign(delta)`` with a single fp32 scale = mean|delta| (the paper's
sign operator [4], made delta-contractive by the L1 scale), then applies
``xhat += scale * q`` locally. Two kernels:

  1. ``_absmean_kernel`` — grid reduction producing per-block |delta| sums
     (one VMEM pass over x, xhat); each grid step writes its sum at its
     own index of one whole-array SMEM output;
  2. ``_apply_kernel``   — given the final scale, emits the int8 payload and
     the updated xhat in one fused pass (the int8 tensor is what the
     runtime ppermutes to neighbors — 1 byte/elem on the wire).

The scale reduction stays exact: block partials are summed in fp32 by XLA
between the two kernels.

``sign_compress_stacked`` is the same pair of kernels lifted to a stacked
(K, ...) worker dim with a 2-D grid: one scale per worker, matching the
vmap-per-worker semantics of the reference CD-Adam encode path.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pack import BLOCK_ROWS, LANE


def _absmean_kernel(x_ref, h_ref, out_ref, *, stacked: bool = False):
    # out_ref is the whole 1-D partials array in SMEM, one entry per grid
    # step in row-major grid order; every step revisits it, so the steps
    # run in order
    i = (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
         if stacked else pl.program_id(0))
    d = x_ref[...].astype(jnp.float32) - h_ref[...].astype(jnp.float32)
    out_ref[i] = jnp.sum(jnp.abs(d))


def _apply_kernel(x_ref, h_ref, scale_ref, q_ref, ho_ref, *,
                  stacked: bool = False):
    # scale_ref is the whole (K,) scale vector in SMEM: worker k's scale
    # sits at grid index k of the stacked variant, at 0 of the flat one
    scale = scale_ref[pl.program_id(0) if stacked else 0]
    d = x_ref[...].astype(jnp.float32) - h_ref[...].astype(jnp.float32)
    s = jnp.sign(d)
    q_ref[...] = s.astype(jnp.int8)
    ho_ref[...] = (h_ref[...].astype(jnp.float32)
                   + scale * s).astype(ho_ref.dtype)


# the scale operand and the partials output: whole 1-D arrays in SMEM —
# Mosaic can't load a scalar directly from an ANY-space ref, and a blocked
# SMEM spec is refused unless the block is the whole array
_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def sign_compress(x: jax.Array, hat: jax.Array, *,
                  block_rows: int = BLOCK_ROWS, interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (q int8 [x.shape], scale f32 [], hat_new [hat.dtype])."""
    n = x.size
    per_block = block_rows * LANE
    n_pad = (-n) % per_block

    def prep(t):
        flat = t.reshape(-1)
        if n_pad:
            flat = jnp.pad(flat, (0, n_pad))
        return flat.reshape(-1, LANE)

    xx, hh = prep(x), prep(hat)
    rows = xx.shape[0]
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))

    partials = pl.pallas_call(
        _absmean_kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=_SMEM_SPEC,
        out_shape=jax.ShapeDtypeStruct(grid, jnp.float32),
        interpret=interpret,
    )(xx, hh)
    # padded entries are x=0, hat=0 -> contribute 0 to the sum; divide by
    # the true element count.
    scale = jnp.sum(partials) / n

    q, hat_new = pl.pallas_call(
        _apply_kernel,
        grid=grid,
        in_specs=[spec, spec, _SMEM_SPEC],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(xx.shape, jnp.int8),
            jax.ShapeDtypeStruct(hh.shape, hat.dtype),
        ],
        interpret=interpret,
    )(xx, hh, scale.reshape(1))

    def unprep(t, shape):
        flat = t.reshape(-1)
        if n_pad:
            flat = flat[:n]
        return flat.reshape(shape)

    return unprep(q, x.shape), scale, unprep(hat_new, hat.shape)


# --------------------------- stacked-K variant ------------------------------


def sign_compress_stacked(x: jax.Array, hat: jax.Array, *,
                          n_true: Optional[int] = None,
                          block_rows: int = BLOCK_ROWS,
                          interpret: bool = False,
                          reduce_axis: Optional[str] = None
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-worker sign compression over a stacked (K, ...) tensor.

    Returns (q int8 [x.shape], scale f32 [K], hat_new [hat.dtype]); row k
    of every output depends only on row k of the inputs — identical to
    vmapping :func:`sign_compress` over the worker dim, but lowered as one
    (K, blocks)-grid kernel pair so the worker dim can stay sharded.

    ``n_true`` overrides the scale divisor (mean |delta| denominator) when
    ``x`` is a zero-padded slice of a resident packed buffer: the padding
    contributes 0 to the |delta| sum but must not inflate the element
    count, or the per-leaf scale would diverge from the reference
    compressor's mean over the leaf's true elements.

    ``reduce_axis`` names a mesh axis to ``psum`` the |delta| partial sums
    over before dividing — the 2D (worker × model) mesh path, where ``x``
    is one model shard's slice of the leaf and the scale must still be the
    L1 mean over the *whole* (worker, leaf): every shard then computes the
    identical global scale and a consistent local ``hat`` update. With
    ``reduce_axis`` set, ``n_true`` is the leaf's GLOBAL true element
    count and may exceed this shard's slot count."""
    if x.ndim < 1:
        raise ValueError("stacked sign compress needs a leading worker dim")
    K = x.shape[0]
    n = x.size // max(K, 1)
    if n == 0:  # zero-element leaves: nothing to compress (reference path
        #         is a no-op on empties too; avoid a 0-row pallas grid)
        return (jnp.zeros(x.shape, jnp.int8), jnp.zeros((K,), jnp.float32),
                hat)
    if n_true is None:
        n_true = n
    if reduce_axis is None:
        if not 0 < n_true <= n:
            raise ValueError(f"n_true={n_true} out of range (0, {n}]")
    elif n_true <= 0:
        raise ValueError(f"n_true={n_true} must be positive")
    per_block = block_rows * LANE
    n_pad = (-n) % per_block

    def prep(t):
        flat = t.reshape(K, -1)
        if n_pad:
            flat = jnp.pad(flat, ((0, 0), (0, n_pad)))
        return flat.reshape(K, -1, LANE)

    xx, hh = prep(x), prep(hat)
    rows = xx.shape[1]
    grid = (K, rows // block_rows)
    spec = pl.BlockSpec((1, block_rows, LANE), lambda k, i: (k, i, 0))

    partials = pl.pallas_call(
        functools.partial(_absmean_kernel, stacked=True),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=_SMEM_SPEC,
        out_shape=jax.ShapeDtypeStruct((K * grid[1],), jnp.float32),
        interpret=interpret,
    )(xx, hh)
    partials = partials.reshape(grid)
    # padded entries are x=0, hat=0 -> contribute 0; divide by the true
    # per-worker element count. On a 2D mesh the partial sums of the other
    # model shards join via psum, so the scale is the global per-leaf L1
    # mean on every shard.
    local = jnp.sum(partials, axis=1)
    if reduce_axis is not None:
        local = jax.lax.psum(local, reduce_axis)
    scale = local / n_true

    q, hat_new = pl.pallas_call(
        functools.partial(_apply_kernel, stacked=True),
        grid=grid,
        in_specs=[spec, spec, _SMEM_SPEC],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(xx.shape, jnp.int8),
            jax.ShapeDtypeStruct(hh.shape, hat.dtype),
        ],
        interpret=interpret,
    )(xx, hh, scale)

    def unprep(t, shape):
        flat = t.reshape(K, -1)
        if n_pad:
            flat = flat[:, :n]
        return flat.reshape(shape)

    return unprep(q, x.shape), scale, unprep(hat_new, hat.shape)
