"""Matrix products of the reference at a stated precision.

``highest`` is the reference itself: float32 products with
``Precision.HIGHEST`` (a TPU otherwise runs a float32 product in one
bfloat16 pass). The lower modes are the correctness check's controls, the
reference put in the program's place one precision step below the one the
configuration states:

* ``high``: three bfloat16 passes (``a_hi b_hi + a_hi b_lo + a_lo b_hi``),
  the TPU's ``Precision.HIGH``, spelled out so that it reads the same on
  every platform;
* ``bf16``: operands rounded to bfloat16, float32 accumulation;
* ``fp8``: operands rounded to float8_e4m3 with one scale per tensor
  (amax / 448, as fp8 training recipes do), float32 accumulation.

The backward products run in the same mode as the forward one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MODES = ("highest", "high", "bf16", "fp8")
_HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _round_to(x: jax.Array, dtype) -> jax.Array:
    return x.astype(dtype).astype(jnp.float32)


def _fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return _round_to(x / scale, jnp.float8_e4m3fn) * scale


def _dot(a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "highest":
        return jnp.matmul(a, b, precision=_HI)
    if mode == "high":
        a_hi, b_hi = _round_to(a, jnp.bfloat16), _round_to(b, jnp.bfloat16)
        a_lo = _round_to(a - a_hi, jnp.bfloat16)
        b_lo = _round_to(b - b_hi, jnp.bfloat16)
        return (jnp.matmul(a_hi, b_hi, precision=_HI)
                + jnp.matmul(a_hi, b_lo, precision=_HI)
                + jnp.matmul(a_lo, b_hi, precision=_HI))
    if mode == "bf16":
        return jnp.matmul(_round_to(a, jnp.bfloat16),
                          _round_to(b, jnp.bfloat16), precision=_HI)
    if mode == "fp8":
        return jnp.matmul(_fp8(a), _fp8(b), precision=_HI)
    raise ValueError(f"unknown precision mode {mode!r}; have {MODES}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mm(a: jax.Array, b: jax.Array, mode: str = "highest") -> jax.Array:
    """``a @ b`` (same batch dims on both) in ``mode``, float32 result."""
    return _dot(a, b, mode)


def _mm_fwd(a, b, mode):
    return _dot(a, b, mode), (a, b)


def _mm_bwd(mode, res, g):
    a, b = res
    da = _dot(g, jnp.swapaxes(b, -1, -2), mode)
    db = _dot(jnp.swapaxes(a, -1, -2), g, mode)
    return da, db


mm.defvjp(_mm_fwd, _mm_bwd)
