"""Gossip mixing over the resident packed optimizer state, as Pallas
kernels.

Both kernels operate on the stacked packed (K, rows, LANE) buffer that is
the persistent representation of ``backend='pallas'`` optimizer state —
no per-step pack/unpack, no per-leaf tree_map launches:

``gossip_mix``
    D-Adam's shift-invariant mixing  out[k] = w_self * x[k] +
    sum_s w_s * x[(k + s) % K].  The reference path materializes one full
    rolled copy of the parameter stack per offset (deg extra HBM
    round-trips for the intermediates); here every grid step accumulates
    all neighbor blocks in VMEM and writes the mixed block ONCE. The
    neighbor blocks are expressed as extra input BlockSpecs over the SAME
    buffer whose index maps shift the worker coordinate by the (static)
    topology offset — the Pallas pipeline turns each into exactly the
    neighbor-block DMA the ring actually needs.

``payload_mix``
    The staleness-tolerant twin of ``gossip_mix``: the neighbor payloads
    were already selected (fresh vs buffered, outside the kernel) into
    per-offset (K, rows, LANE) buffers aligned with the destination
    worker, so every operand reads block (k, i) — same accumulation order
    and f32 arithmetic as ``gossip_mix``, which is what makes the tau=0
    path bit-for-bit identical to the synchronous round.

``consensus_mix``
    CD-Adam's consensus update  out[k] = x[k] + gamma * sum_s w_s *
    (hat_s[k] - hat_self[k])  (Alg. 2 line 8) — a (deg + 2)-operand
    elementwise pass, fused into a single VMEM visit per block.

``gossip_adam_mix``
    D-Adam's whole communication step — fused_adam THEN gossip_mix — as a
    single VMEM pass: each grid cell recomputes the Adam half-step for
    its own block AND each neighbor block straight from (p, g, m, v) and
    mixes them in registers, so the half-stepped parameter stack is never
    written to (or re-read from) HBM at all. The half-step result is
    rounded through the parameter dtype before mixing, as the
    stored-then-reloaded two-pass sequence does: m and v match it bit for
    bit, and the params within one rounding of each mixed half-step
    (the compiler may contract the Adam multiply-adds differently in
    this kernel body than in fused_adam's). The Adam math for neighbor
    blocks is redundant compute
    ((deg + 1)× per block), but the kernel is memory-bound: trading VPU
    flops for one full HBM round-trip of the parameter stack wins.

Hyperparameters (offsets, weights, gamma) are compile-time constants: the
optimizer jits one step per config, matching fused_adam / sign_compress.
Zero-filled padding rows mix to zero under both kernels (all-zero inputs
=> zero output), so resident buffer padding stays zero across steps.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.topology import GridShift
from repro.kernels.pack import BLOCK_ROWS, LANE  # shared tile quantum

# VMEM is ~16 MiB/core; cap the operand count so (deg + 2) blocks of
# 128 KiB (plus pipeline double-buffering) stay comfortably inside it.
# Denser graphs fall back to the XLA einsum path in the dispatcher.
MAX_FUSED_DEGREE = 32

# gossip_adam_mix reads FOUR operands (p, g, m, v) per worker block —
# 4 * (deg + 1) inputs + 3 outputs of 128 KiB, double-buffered — so its
# degree cap is tighter; denser graphs take the two-pass sequence.
MAX_GOSSIP_ADAM_DEGREE = 8


def _check_buf(x: jax.Array, block_rows: int) -> Tuple[int, int]:
    if x.ndim != 3 or x.shape[-1] != LANE:
        raise ValueError(f"expected a stacked (K, rows, {LANE}) packed "
                         f"buffer; got shape {x.shape}")
    K, rows = x.shape[0], x.shape[1]
    if rows % block_rows:
        raise ValueError(f"rows={rows} not a multiple of block_rows="
                         f"{block_rows}; pack with block_rows={block_rows}")
    return K, rows


def _mix_kernel(*refs, self_weight: float, weights: Tuple[float, ...]):
    ins, out_ref = refs[:-1], refs[-1]
    acc = self_weight * ins[0][...].astype(jnp.float32)
    for w, r in zip(weights, ins[1:]):
        acc = acc + w * r[...].astype(jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


def gossip_mix(x: jax.Array, offsets: Sequence[int],
               offset_weights: Sequence[float], self_weight: float, *,
               block_rows: int = BLOCK_ROWS,
               interpret: bool = False) -> jax.Array:
    """Shift-invariant gossip over a stacked packed buffer, one VMEM pass.

    ``x`` is (K, rows, LANE); row-block i of output worker k reads row-block
    i of workers k and ``src(k)`` for each static offset — plain ints are
    the circulant ``(k + s) % K``, :class:`GridShift` offsets compute the
    row-wrap-aware torus neighbor right in the BlockSpec index map (its
    ``src`` uses only ``//`` and ``%``, so it traces).
    """
    K, rows = _check_buf(x, block_rows)
    offsets = tuple(s if isinstance(s, GridShift) else int(s)
                    for s in offsets)
    weights = tuple(float(w) for w in offset_weights)
    if len(offsets) != len(weights):
        raise ValueError("offsets and offset_weights must align")
    for s in offsets:
        if isinstance(s, GridShift) and s.rows * s.cols != K:
            raise ValueError(f"GridShift {s} does not cover K={K}")
    if not offsets:
        return x

    def spec_for(shift) -> pl.BlockSpec:
        if isinstance(shift, GridShift):
            return pl.BlockSpec((1, block_rows, LANE),
                                lambda k, i, s=shift: (s.src(k), i, 0))
        return pl.BlockSpec((1, block_rows, LANE),
                            lambda k, i, s=shift: ((k + s) % K, i, 0))

    kernel = functools.partial(_mix_kernel, self_weight=float(self_weight),
                               weights=weights)
    return pl.pallas_call(
        kernel,
        grid=(K, rows // block_rows),
        in_specs=[spec_for(0)] + [spec_for(s) for s in offsets],
        out_specs=spec_for(0),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, *([x] * len(offsets)))


def payload_mix(x: jax.Array, payloads: Sequence[jax.Array],
                offset_weights: Sequence[float], self_weight: float, *,
                block_rows: int = BLOCK_ROWS,
                interpret: bool = False) -> jax.Array:
    """Mix pre-aligned neighbor payloads into the resident packed buffer:

        out[k] = w_self * x[k] + sum_i w_i * payloads[i][k]

    ``payloads[i]`` already holds offset i's neighbor value for every
    destination worker (the staleness runtime selects fresh-vs-buffered
    copies before the kernel), so all operands use identity index maps —
    same kernel body, weight order and f32 accumulation as ``gossip_mix``.
    """
    K, rows = _check_buf(x, block_rows)
    payloads = tuple(payloads)
    weights = tuple(float(w) for w in offset_weights)
    if len(payloads) != len(weights):
        raise ValueError("payloads and offset_weights must align")
    for p in payloads:
        if p.shape != x.shape:
            raise ValueError(f"payload shape {p.shape} != x {x.shape}")
    if not payloads:
        return x

    spec = pl.BlockSpec((1, block_rows, LANE), lambda k, i: (k, i, 0))
    kernel = functools.partial(_mix_kernel, self_weight=float(self_weight),
                               weights=weights)
    return pl.pallas_call(
        kernel,
        grid=(K, rows // block_rows),
        in_specs=[spec] * (1 + len(payloads)),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, *payloads)


def _gossip_adam_kernel(*refs, self_weight: float,
                        weights: Tuple[float, ...], eta: float,
                        beta1: float, beta2: float, tau: float,
                        weight_decay: float):
    ins, (po_ref, mo_ref, vo_ref) = refs[:-3], refs[-3:]

    def half_step(p_ref, g_ref, m_ref, v_ref):
        # identical ops, order and constants as fused_adam._adam_kernel —
        # that is what keeps the fused path within one rounding of the
        # two-pass one
        g = g_ref[...].astype(jnp.float32)
        p = p_ref[...]
        if weight_decay:
            g = g + weight_decay * p.astype(jnp.float32)
        m = beta1 * m_ref[...].astype(jnp.float32) + (1.0 - beta1) * g
        v = beta2 * v_ref[...].astype(jnp.float32) + (1.0 - beta2) * g * g
        step = eta * m * jax.lax.rsqrt(v + 1e-30) \
            if tau == 0.0 else eta * m / (jnp.sqrt(v) + tau)
        # round through the parameter dtype BEFORE mixing: the two-pass
        # sequence stores the half-step and reloads it for the mix
        po = (p.astype(jnp.float32) - step).astype(po_ref.dtype)
        return po, m, v

    po_self, m_self, v_self = half_step(*ins[0:4])
    acc = self_weight * po_self.astype(jnp.float32)
    for j, w in enumerate(weights):
        po_nbr, _, _ = half_step(*ins[4 * (j + 1):4 * (j + 2)])
        acc = acc + w * po_nbr.astype(jnp.float32)
    po_ref[...] = acc.astype(po_ref.dtype)
    mo_ref[...] = m_self.astype(mo_ref.dtype)
    vo_ref[...] = v_self.astype(vo_ref.dtype)


def gossip_adam_mix(p: jax.Array, g: jax.Array, m: jax.Array,
                    v: jax.Array, offsets: Sequence[int],
                    offset_weights: Sequence[float], self_weight: float, *,
                    eta: float, beta1: float = 0.9, beta2: float = 0.999,
                    tau: float = 1e-6, weight_decay: float = 0.0,
                    block_rows: int = BLOCK_ROWS, interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused Adam half-step + shift-invariant gossip over resident packed
    buffers: ``fused_adam`` followed by ``gossip_mix``, in ONE VMEM pass.

    All four operands are stacked (K, rows, LANE) buffers; returns
    (mixed params, m, v). Each output block's neighbor half-steps are
    recomputed in VMEM from the neighbor's (p, g, m, v) blocks via
    shifted BlockSpec index maps (same shift arithmetic as
    ``gossip_mix``), with the half-step rounded through the parameter
    dtype before the f32 mix. m and v are bit for bit the two-pass
    result; each mixed param is within ``2 eps sum_j |w_j p_j|`` of it.
    """
    K, rows = _check_buf(p, block_rows)
    for name, b in (("g", g), ("m", m), ("v", v)):
        if b.shape != p.shape:
            raise ValueError(f"{name} shape {b.shape} != p {p.shape}")
    offsets = tuple(s if isinstance(s, GridShift) else int(s)
                    for s in offsets)
    weights = tuple(float(w) for w in offset_weights)
    if len(offsets) != len(weights):
        raise ValueError("offsets and offset_weights must align")
    if not offsets:
        raise ValueError("gossip_adam_mix needs at least one offset; "
                         "offset-free topologies have no mix to fuse "
                         "(use fused_adam)")
    if len(offsets) > MAX_GOSSIP_ADAM_DEGREE:
        raise ValueError(
            f"degree {len(offsets)} > MAX_GOSSIP_ADAM_DEGREE="
            f"{MAX_GOSSIP_ADAM_DEGREE}; the dispatcher should take the "
            "two-pass sequence for denser graphs")
    for s in offsets:
        if isinstance(s, GridShift) and s.rows * s.cols != K:
            raise ValueError(f"GridShift {s} does not cover K={K}")

    def spec_for(shift) -> pl.BlockSpec:
        if isinstance(shift, GridShift):
            return pl.BlockSpec((1, block_rows, LANE),
                                lambda k, i, s=shift: (s.src(k), i, 0))
        return pl.BlockSpec((1, block_rows, LANE),
                            lambda k, i, s=shift: ((k + s) % K, i, 0))

    kernel = functools.partial(
        _gossip_adam_kernel, self_weight=float(self_weight),
        weights=weights, eta=float(eta), beta1=float(beta1),
        beta2=float(beta2), tau=float(tau),
        weight_decay=float(weight_decay))
    in_specs, operands = [], []
    for s in (0,) + offsets:
        in_specs.extend([spec_for(s)] * 4)
        operands.extend([p, g, m, v])
    return pl.pallas_call(
        kernel,
        grid=(K, rows // block_rows),
        in_specs=in_specs,
        out_specs=[spec_for(0)] * 3,
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(*operands)


def _consensus_kernel(*refs, gamma: float, weights: Tuple[float, ...]):
    x_ref, hs_ref = refs[0], refs[1]
    hn_refs, out_ref = refs[2:-1], refs[-1]
    hs = hs_ref[...].astype(jnp.float32)
    acc = jnp.zeros_like(hs)
    for w, hn in zip(weights, hn_refs):
        acc = acc + w * (hn[...].astype(jnp.float32) - hs)
    out_ref[...] = (x_ref[...].astype(jnp.float32)
                    + gamma * acc).astype(out_ref.dtype)


def consensus_mix(x: jax.Array, hat_self: jax.Array,
                  hat_nbrs: Sequence[jax.Array],
                  offset_weights: Sequence[float], gamma: float, *,
                  block_rows: int = BLOCK_ROWS,
                  interpret: bool = False) -> jax.Array:
    """CD-Adam consensus update on resident packed buffers, one VMEM pass.

    All operands are (K, rows, LANE); no communication happens here — the
    neighbor xhat copies are CHOCO-style local state.
    """
    K, rows = _check_buf(x, block_rows)
    hat_nbrs = tuple(hat_nbrs)
    weights = tuple(float(w) for w in offset_weights)
    if len(hat_nbrs) != len(weights):
        raise ValueError("hat_nbrs and offset_weights must align")
    for h in (hat_self,) + hat_nbrs:
        if h.shape != x.shape:
            raise ValueError(f"hat buffer shape {h.shape} != x {x.shape}")
    if not hat_nbrs:
        return x

    spec = pl.BlockSpec((1, block_rows, LANE), lambda k, i: (k, i, 0))
    kernel = functools.partial(_consensus_kernel, gamma=float(gamma),
                               weights=weights)
    return pl.pallas_call(
        kernel,
        grid=(K, rows // block_rows),
        in_specs=[spec] * (2 + len(hat_nbrs)),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, hat_self, *hat_nbrs)
