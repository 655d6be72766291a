"""CD-Adam (Algorithm 2): D-Adam with compressed gossip + error feedback.

At a communication round (mod(t+1, p) == 0), worker k:

    x_{t+1}   = x_{t+1/2} + gamma * sum_j w_kj (xhat_j - xhat_k)     (local)
    q_k       = Q(x_{t+1} - xhat_k)                                  (compress)
    send q_k to neighbors / receive q_j                              (wire)
    xhat_j   += q_j   for j in N_k ∪ {k}                             (update)

Every worker stores xhat copies of itself and each neighbor (CHOCO-style
state), so the mixing step needs *no* communication; only the compressed
residual q travels. The neighbor exchange of the *encoded* payload (int8
sign bits / top-k pairs) is a worker shift (:func:`repro.core.dadam
.shift_worker`): under comm='stacked' a ``jnp.roll`` over the (possibly
sharded) worker dim, under comm='axis' a ``jax.lax.ppermute`` over the
worker mesh axis inside shard_map — either way the lowered
collective-permute genuinely carries the compressed byte count.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dadam
from repro.core.compression import Compressor
from repro.core.dadam import AdamMoments, DAdamConfig, init_moments, local_update
from repro.core.schedule import TopologySchedule, comm_offsets
from repro.core.topology import Topology
# light import only — the Pallas kernel stack (repro.kernels.ops) loads
# lazily inside the pallas-only paths
from repro.kernels import pack as packing
from repro.tracing import scoped

PyTree = Any


@dataclasses.dataclass(frozen=True)
class CDAdamConfig(DAdamConfig):
    gamma: float = 0.4  # paper's consensus step size
    scales: str = "leaf"  # compression-scale granularity: 'leaf' keeps the
    #                       reference per-(worker, leaf) L1 scales;
    #                       'worker' opts into ONE scale per worker,
    #                       computed by a single fused kernel pass over the
    #                       whole resident buffer (backend='pallas' only)

    def validate(self) -> None:  # type: ignore[override]
        super().validate()
        if not 0 < self.gamma <= 1:
            raise ValueError("gamma must be in (0, 1]")
        if self.scales not in ("leaf", "worker"):
            raise ValueError(f"unknown scales {self.scales!r} "
                             "(use 'leaf' or 'worker')")
        if self.scales == "worker" and self.backend != "pallas":
            raise ValueError(
                "scales='worker' is the fused whole-buffer compressor: one "
                "kernel pass over the resident packed buffer; it requires "
                "backend='pallas' (the reference path compresses per leaf)")
        if (self.staleness or 0) > 0 and self.comm == "axis":
            raise ValueError(
                "CD-Adam staleness delays payloads through per-edge ring "
                "buffers indexed by the static delay table; the sharded "
                "comm='axis' lowering is not wired yet — use comm='stacked' "
                "(D-Adam supports staleness under comm='axis')")


class CDAdamState(NamedTuple):
    params: PyTree                 # x,     stacked (K, ...)
    moments: AdamMoments
    hat_self: PyTree               # xhat^{(k)},         stacked (K, ...)
    hat_nbrs: Tuple[PyTree, ...]   # xhat^{((k+s)%K)} per topology offset s
    # transient straggler-tolerant payload ring buffers (cfg.staleness > 0):
    # one ring per offset, encoded-payload pytrees with a T = tau + 1 time
    # dim at axis 1. Stripped from checkpoints, rebuilt cold on restore.
    pending: Optional[Tuple[PyTree, ...]] = None


@jax.tree_util.register_pytree_node_class
class PackedCDAdamState:
    """Resident packed CD-Adam state for ``backend='pallas'``.

    Everything CHOCO-style state touches per step — params, both moments,
    xhat_self and one xhat copy per topology offset — lives as stacked,
    leaf-aligned ``(K, rows, 128)`` buffers across steps: the fused-Adam,
    consensus-mix and sign-compress kernels consume them directly (zero
    per-step pack/unpack; leaf-aligned row slices keep the compression
    scale per (worker, leaf), exactly the reference semantics). Unpacked
    pytree views (``.params`` / ``.moments`` / ``.hat_self`` /
    ``.hat_nbrs``) materialize only at eval/checkpoint boundaries."""

    __slots__ = ("buf", "m", "v", "count", "hat_buf", "hat_nbr_bufs",
                 "spec", "spec_m", "pending")

    def __init__(self, buf, m, v, count, hat_buf, hat_nbr_bufs, spec,
                 spec_m, pending=None):
        self.buf, self.m, self.v, self.count = buf, m, v, count
        self.hat_buf, self.hat_nbr_bufs = hat_buf, tuple(hat_nbr_bufs)
        self.spec, self.spec_m = spec, spec_m
        self.pending = pending

    def tree_flatten(self):
        return ((self.buf, self.m, self.v, self.count, self.hat_buf,
                 self.hat_nbr_bufs, self.pending), (self.spec, self.spec_m))

    @classmethod
    def tree_unflatten(cls, aux, children):
        buf, m, v, count, hat_buf, hat_nbr_bufs, pending = children
        return cls(buf, m, v, count, hat_buf, hat_nbr_bufs, *aux, pending)

    def with_pending(self, pending) -> "PackedCDAdamState":
        return PackedCDAdamState(self.buf, self.m, self.v, self.count,
                                 self.hat_buf, self.hat_nbr_bufs, self.spec,
                                 self.spec_m, pending)

    # ------- unpacked views: boundary use only (eval/log/checkpoint) -------

    @property
    def params(self) -> PyTree:
        return packing.unpack(self.buf, self.spec)

    @property
    def moments(self) -> AdamMoments:
        return AdamMoments(packing.unpack(self.m, self.spec_m),
                           packing.unpack(self.v, self.spec_m), self.count)

    @property
    def hat_self(self) -> PyTree:
        return packing.unpack(self.hat_buf, self.spec)

    @property
    def hat_nbrs(self) -> Tuple[PyTree, ...]:
        return tuple(packing.unpack(h, self.spec)
                     for h in self.hat_nbr_bufs)

    def unpacked(self) -> CDAdamState:
        """Portable NamedTuple state — the checkpoint wire format,
        leaf-for-leaf identical to a reference-backend state."""
        return CDAdamState(self.params, self.moments, self.hat_self,
                           self.hat_nbrs)

    @classmethod
    def from_unpacked(cls, state: CDAdamState, *,
                      row_shards: int = 1) -> "PackedCDAdamState":
        """``row_shards=M`` packs into the 2D-mesh row-sharded layout
        (each leaf split across M shard blocks; see kernels/pack.py)."""
        spec = packing.make_spec(state.params, stacked=True,
                                 block_rows=packing.BLOCK_ROWS,
                                 leaf_align=True, row_shards=row_shards)
        spec_m = packing.make_spec(state.moments.m, stacked=True,
                                   block_rows=packing.BLOCK_ROWS,
                                   leaf_align=True, row_shards=row_shards)
        return cls(packing.pack(state.params, spec),
                   packing.pack(state.moments.m, spec_m),
                   packing.pack(state.moments.v, spec_m),
                   state.moments.count,
                   packing.pack(state.hat_self, spec),
                   tuple(packing.pack(h, spec) for h in state.hat_nbrs),
                   spec, spec_m)


# --------------------- stacked encode/decode helpers -----------------------


@scoped("compress")
def _encode_stacked(comp: Compressor, tree: PyTree) -> PyTree:
    """vmap Q.encode over the leading worker dim of every leaf (per-worker
    scales!), producing payload leaves that keep the leading K dim.

    Leaves are NOT flattened: elementwise payloads (sign bits, quantized
    levels) keep the leaf's full shape so the tensor-parallel 'model'
    sharding of the parameter survives onto the payload — flattening would
    force each device to hold and ppermute the whole worker's payload
    (measured 16x wire inflation; EXPERIMENTS.md §Perf iteration 4)."""
    return jax.tree_util.tree_map(
        lambda x: jax.vmap(comp.encode)(x), tree
    )


@scoped("compress")
def _decode_stacked(comp: Compressor, payload: PyTree, like: PyTree) -> PyTree:
    def dec(p, x):
        return jax.vmap(lambda q: comp.decode(q, x.shape[1:], x.dtype))(p)

    return jax.tree_util.tree_map(
        dec, payload, like,
        is_leaf=lambda t: isinstance(t, dict) and ("bits" in t or "values" in t
                                                   or "q" in t),
    )


def _shift_payload(payload: PyTree, s: int, topo: Topology,
                   cfg: CDAdamConfig) -> PyTree:
    """Worker k receives worker (k + s) % K's encoded message — the wire
    hop of Alg. 2 line 10, carrying only the compressed payload. A roll
    over the stacked worker dim (comm='stacked'; per-worker scale scalars
    roll along axis 0 too) or a ppermute over the worker mesh axis
    (comm='axis')."""
    axis = cfg.axis_name if cfg.comm == "axis" else None
    return jax.tree_util.tree_map(
        lambda a: dadam.shift_worker(a, s, topo.K, axis), payload
    )


# ---------------- straggler-tolerant payload delay rings --------------------
#
# CD-Adam's staleness model differs from D-Adam's: a CHOCO hat copy is a
# running SUM of residual payloads, so dropping (or re-applying) a payload
# permanently desyncs worker k's copy of its neighbor's hat. Stragglers
# therefore DELAY payloads, never drop them: each edge (k, offset i) has a
# static delay d <= tau, incoming encoded payloads enter a ring buffer with
# T = tau + 1 slots, and round r applies the payload pushed at round r - d —
# in order, exactly once, at most tau rounds late.


def _wire_tau(cfg: CDAdamConfig) -> int:
    """Rounds of wire delay the payload rings implement: the explicit
    staleness bound, or EXACTLY one round under ``cfg.overlap`` — the
    eager-issue schedule is the tau=1 ring path with an all-ones delay
    table, which is what pins overlap ≡ staleness(1) bitwise."""
    if cfg.overlap:
        return 1
    return int(cfg.staleness or 0)


def _payload_delays(cfg: CDAdamConfig, K: int, deg: int) -> np.ndarray:
    """Static (K, deg) per-edge delay table, reproducible from the seed.
    A fraction ``straggler_rate`` of edges is persistently slow (delay
    uniform in [1, tau]); the rest deliver same-round. Under
    ``cfg.overlap`` EVERY edge is exactly one round late: round r issues
    its payload and round r+1 applies it, so the wire exchange overlaps
    the p local Adam steps in between."""
    if cfg.overlap:
        return np.ones((K, deg), np.int32)
    tau = _wire_tau(cfg)
    if tau == 0 or cfg.straggler_rate <= 0.0:
        return np.zeros((K, deg), np.int32)
    rs = np.random.RandomState(cfg.straggler_seed)
    slow = rs.rand(K, deg) < cfg.straggler_rate
    d = np.where(slow, rs.randint(1, tau + 1, size=(K, deg)), 0)
    return d.astype(np.int32)


def _ring_like(payload_like: PyTree, T: int) -> PyTree:
    """A cold (zero) ring: every leaf gains a T-slot time dim at axis 1
    (axis 0 stays the worker dim). Zero payloads decode to zero residuals,
    so warm-up rounds apply no hat update — 'no message yet'."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros((p.shape[0], T) + p.shape[1:], p.dtype),
        payload_like)


def _ring_push(ring: PyTree, payload: PyTree, slot: jax.Array) -> PyTree:
    return jax.tree_util.tree_map(
        lambda rb, p: rb.at[:, slot].set(p.astype(rb.dtype)), ring, payload)


def _ring_gather(ring: PyTree, sel: jax.Array) -> PyTree:
    """Per-worker slot read: leaf (K, T, ...) + sel (K,) -> (K, ...)."""
    def g(rb):
        s = sel.reshape((-1,) + (1,) * (rb.ndim - 1)).astype(jnp.int32)
        return jnp.take_along_axis(rb, s, axis=1)[:, 0]

    return jax.tree_util.tree_map(g, ring)


def _delayed_recv(recv: PyTree, ring: Optional[PyTree], d_col: np.ndarray,
                  r: jax.Array, tau: int) -> Tuple[PyTree, Optional[PyTree]]:
    """Push this round's received payload, pop each worker's delayed one."""
    if ring is None:
        return recv, None
    T = tau + 1
    new_ring = _ring_push(ring, recv, r % T)
    sel = (r - jnp.asarray(d_col)) % T
    return _ring_gather(new_ring, sel), new_ring


# ---------------------- schedule round dispatch -----------------------------


def _round_dispatch(operand: Any, topo: "Topology | TopologySchedule",
                    r: jax.Array, fn: Callable[[Any, Topology], Any]) -> Any:
    """Run ``fn(operand, view)`` for round r's topology. Schedules switch
    over their union views — every branch sees the SAME offset tuple (per-
    edge hat/ring state stays aligned), only the static mixing weights
    change — so the whole cycle compiles into one step."""
    if isinstance(topo, TopologySchedule):
        views = topo.union_views()
        if len(views) == 1:
            return fn(operand, views[0])
        return jax.lax.switch(
            r % len(views),
            [(lambda op, v=v: fn(op, v)) for v in views],
            operand)
    return fn(operand, topo)


# ------------------------------- algorithm ---------------------------------


def init(params_stacked: PyTree, cfg: CDAdamConfig,
         topo: "Topology | TopologySchedule",
         comp: Optional[Compressor] = None
         ) -> "CDAdamState | PackedCDAdamState":
    cfg.validate()
    offs = comm_offsets(topo)
    if not offs and topo.K > 1:
        raise ValueError("CD-Adam runtime requires a shift-invariant topology")
    tau = _wire_tau(cfg)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params_stacked)
    # xhat_0 = 0 (CHOCO convention); neighbor copies likewise — one per
    # offset that can EVER be active (a schedule's union edge set).
    hat_nbrs = tuple(jax.tree_util.tree_map(jnp.zeros_like, params_stacked)
                     for _ in offs)
    state = CDAdamState(params_stacked, init_moments(params_stacked, cfg),
                        zeros, hat_nbrs)
    if cfg.backend == "pallas":
        packed = PackedCDAdamState.from_unpacked(
            state, row_shards=cfg.model_parallel)
        if tau > 0:
            K = topo.K
            rows = packed.buf.shape[1]
            sc_shape = ((K,) if cfg.scales == "worker"
                        else (K, len(packed.spec.sizes)))
            like = {"q": jnp.zeros((K, rows, packing.LANE), jnp.int8),
                    "scale": jnp.zeros(sc_shape, jnp.float32)}
            packed = packed.with_pending(
                tuple(_ring_like(like, tau + 1) for _ in offs))
        return packed
    if tau > 0:
        if comp is None:
            raise ValueError(
                "cfg.staleness > 0 rings buffer ENCODED payloads; the "
                "reference backend needs the compressor at init (pass "
                "comp=, as make_optimizer does)")
        payload_like = _encode_stacked(comp, zeros)
        state = state._replace(
            pending=tuple(_ring_like(payload_like, tau + 1) for _ in offs))
    return state


def _mix_with_hats(x_half: PyTree, hat_self: PyTree,
                   hat_nbrs: Tuple[PyTree, ...], topo: Topology,
                   cfg: CDAdamConfig) -> PyTree:
    """(8) local mixing using stored neighbor copies — no communication."""

    def mixed(xh, hs, *hns):
        acc = jnp.zeros_like(hs, dtype=jnp.float32)
        for w, hn in zip(topo.offset_weights, hns):
            acc = acc + w * (hn.astype(jnp.float32) - hs.astype(jnp.float32))
        return (xh.astype(jnp.float32) + cfg.gamma * acc).astype(xh.dtype)

    return jax.tree_util.tree_map(mixed, x_half, hat_self, *hat_nbrs)


@scoped("gossip")
def _comm_round(state_half: CDAdamState, topo: Topology, cfg: CDAdamConfig,
                comp: Compressor, r: jax.Array) -> CDAdamState:
    """Lines 8-11 of Alg. 2 on the half-step parameters."""
    x_half, mom, hat_self, hat_nbrs, pending = state_half

    x_new = _mix_with_hats(x_half, hat_self, hat_nbrs, topo, cfg)

    # (9) compress the residual against our own xhat.
    resid = jax.tree_util.tree_map(lambda a, b: a - b, x_new, hat_self)
    q_enc = _encode_stacked(comp, resid)
    q_dec = _decode_stacked(comp, q_enc, resid)

    # (11a) update own copy: xhat_k += q_k
    new_hat_self = jax.tree_util.tree_map(
        lambda h, q: h + q.astype(h.dtype), hat_self, q_dec)

    # (10)+(11b) neighbors: worker k needs q_{(k+s)%K}; the *encoded* payload
    # travels (worker shift => compressed-size collective-permute in either
    # comm mode), then is decoded locally. Under cfg.staleness > 0 the
    # received payload detours through the per-edge delay ring: slow edges
    # apply it up to tau rounds late, in order, never dropped.
    tau = _wire_tau(cfg)
    delays = _payload_delays(cfg, topo.K, len(topo.offsets))
    new_hat_nbrs = []
    new_pending = []
    for i, (s, hn) in enumerate(zip(topo.offsets, hat_nbrs)):
        recv_enc = _shift_payload(q_enc, s, topo, cfg)
        ring = None if pending is None else pending[i]
        d_col = dadam._local_worker_rows(jnp.asarray(delays[:, i]), cfg)
        use_enc, ring = _delayed_recv(recv_enc, ring, d_col, r, tau)
        recv = _decode_stacked(comp, use_enc, resid)
        new_hat_nbrs.append(jax.tree_util.tree_map(
            lambda h, q: h + q.astype(h.dtype), hn, recv))
        new_pending.append(ring)

    return CDAdamState(x_new, mom, new_hat_self, tuple(new_hat_nbrs),
                       None if pending is None else tuple(new_pending))


@scoped("gossip")
def _comm_round_pallas(state_half: CDAdamState, topo: Topology,
                       cfg: CDAdamConfig) -> CDAdamState:
    """Lines 8-11 of Alg. 2 with the sign compressor fused into Pallas
    kernels (interpret mode off-TPU).

    Per leaf, one (K, blocks)-grid kernel pair computes the int8 sign
    payload, the per-worker L1 scale AND the ``xhat_k += q_k`` update in a
    single VMEM pass over (x_new, xhat) — fusing reference steps (9) and
    (11a). Compression stays per-(worker, leaf), so the math is identical
    to the reference path with ``compressor='sign'``. Neighbor copies
    (10)+(11b) are then updated from the *payload* — the int8 q and the
    (K,) scales roll over the worker dim, which is exactly the compressed
    byte count on the wire when the dim is sharded."""
    from repro.kernels import ops

    if cfg.scales == "worker":
        raise ValueError(
            "scales='worker' is the whole-buffer pass over the RESIDENT "
            "packed state; the pytree (repack) pallas path compresses per "
            "leaf — use the packed-resident runtime (opt.init's default)")

    x_half, mom, hat_self, hat_nbrs, pending = state_half
    if pending is not None or cfg.overlap:
        raise ValueError(
            "staleness > 0 / overlap are wired for the packed-resident "
            "pallas runtime and the reference backend; the pytree (repack) "
            "pallas path does not thread payload rings")
    x_new = _mix_with_hats(x_half, hat_self, hat_nbrs, topo, cfg)

    enc = jax.tree_util.tree_map(
        lambda xl, hl: ops.sign_compress_stacked(xl, hl), x_new, hat_self)
    is_enc = lambda t: isinstance(t, tuple)
    q = jax.tree_util.tree_map(lambda t: t[0], enc, is_leaf=is_enc)
    scale = jax.tree_util.tree_map(lambda t: t[1], enc, is_leaf=is_enc)
    new_hat_self = jax.tree_util.tree_map(lambda t: t[2], enc, is_leaf=is_enc)

    axis = cfg.axis_name if cfg.comm == "axis" else None
    new_hat_nbrs = []
    for s, hn in zip(topo.offsets, hat_nbrs):
        def upd(h, qb, sc, s=s):
            q_recv = dadam.shift_worker(qb, s, topo.K, axis)
            sc_recv = dadam.shift_worker(sc, s, topo.K, axis)
            sc_recv = sc_recv.reshape((-1,) + (1,) * (qb.ndim - 1))
            return h + (sc_recv * q_recv.astype(jnp.float32)).astype(h.dtype)
        new_hat_nbrs.append(jax.tree_util.tree_map(upd, hn, q, scale))

    return CDAdamState(x_new, mom, new_hat_self, tuple(new_hat_nbrs))


@scoped("gossip")
def _comm_round_packed(state_half: PackedCDAdamState, topo: Topology,
                       cfg: CDAdamConfig, r: jax.Array) -> PackedCDAdamState:
    """Lines 8-11 of Alg. 2 entirely on resident packed buffers.

    (8) is ONE fused consensus-mix kernel pass over the stacked buffer
    (``kernels/gossip.py``). (9)+(11a) run the sign-compress kernel pair on
    the *leaf-aligned row slices* of the resident buffers — compression
    stays per (worker, leaf) with the true-element-count divisor, so the
    math is bit-for-bit the reference semantics, with zero pack/unpack.
    (10)+(11b) update the neighbor copies from the payload: the int8 q
    buffer and the (K, L) per-leaf scales travel by worker shift — a roll
    over the stacked dim (comm='stacked') or a ppermute over the worker
    mesh axis (comm='axis', where the local buffers are one worker's
    (1, rows, 128) shard) — still exactly the compressed byte count on
    the wire.

    On a 2D (worker × model) mesh (``cfg.model_parallel`` = M > 1, traced
    inside shard_map with both axes bound) the local buffers are one
    (worker, model) shard's (1, rows/M, 128) block of the row-sharded
    layout: ``leaf_row_ranges`` hands out the shard-invariant local leaf
    slices, and the sign-compress scale reduction psums its |delta|
    partial sums over the model axis — compression stays per
    (worker, leaf) with the exact reference semantics, while the ppermute
    payload per device shrinks to that device's 1/M row block."""
    from repro.kernels import ops

    x_new = ops.consensus_mix(state_half.buf, state_half.hat_buf,
                              state_half.hat_nbr_bufs, topo.offset_weights,
                              cfg.gamma)

    spec = state_half.spec
    # local view: the per-shard row ranges / row count (== the full buffer
    # when not row-sharded)
    ranges = packing.leaf_row_ranges(spec)
    lrows = spec.local_rows
    maxis = (cfg.model_axis_name
             if getattr(cfg, "model_parallel", 1) > 1 else None)
    axis = cfg.axis_name if cfg.comm == "axis" else None
    tau = _wire_tau(cfg)
    pending = state_half.pending
    delays = _payload_delays(cfg, topo.K, len(topo.offsets))

    def recv_payload(i, shift, q_buf, scales):
        """Shift (wire hop) then, under staleness, detour through offset
        i's delay ring; returns the payload to apply plus the new ring."""
        q_recv = dadam.shift_worker(q_buf, shift, topo.K, axis)
        sc_recv = dadam.shift_worker(scales, shift, topo.K, axis)
        ring = None if pending is None else pending[i]
        d_col = dadam._local_worker_rows(jnp.asarray(delays[:, i]), cfg)
        recv, ring = _delayed_recv({"q": q_recv, "scale": sc_recv}, ring,
                                   d_col, r, tau)
        return recv["q"], recv["scale"], ring

    if cfg.scales == "worker":
        # Fused whole-buffer compressor: ONE kernel-pair pass over the
        # entire resident buffer with a single scale per worker — the
        # mean |delta| over the worker's whole true parameter vector
        # (padding contributes 0 to the sum and is excluded from the
        # divisor; on a 2D mesh the |delta| partials psum over 'model' so
        # every shard computes the identical global scale). Deliberately
        # coarser than the reference per-(worker, leaf) semantics — the
        # opt-in trade: one kernel launch and a 4-byte scale payload
        # instead of L of each.
        q_buf, w_scales, new_hat_buf = ops.sign_compress_stacked(
            x_new, state_half.hat_buf, n_true=spec.n, reduce_axis=maxis)

        new_hat_nbrs, new_pending = [], []
        for i, (s, hn) in enumerate(zip(topo.offsets,
                                        state_half.hat_nbr_bufs)):
            q_recv, sc_recv, ring = recv_payload(i, s, q_buf, w_scales)
            new_hat_nbrs.append(hn + (sc_recv[:, None, None]
                                      * q_recv.astype(jnp.float32)
                                      ).astype(hn.dtype))
            new_pending.append(ring)
        return PackedCDAdamState(
            x_new, state_half.m, state_half.v, state_half.count,
            new_hat_buf, tuple(new_hat_nbrs), spec, state_half.spec_m,
            None if pending is None else tuple(new_pending))

    q_parts, scale_cols, hat_parts = [], [], []
    for (r0, r1), size in zip(ranges, spec.sizes):
        q_l, s_l, h_l = ops.sign_compress_stacked(
            x_new[:, r0:r1], state_half.hat_buf[:, r0:r1],
            n_true=size if size else None, reduce_axis=maxis)
        q_parts.append(q_l)
        scale_cols.append(s_l)
        hat_parts.append(h_l)
    q_buf = jnp.concatenate(q_parts, axis=1)           # (K, local rows, 128)
    scales = jnp.stack(scale_cols, axis=1)             # (K, L)
    new_hat_buf = jnp.concatenate(hat_parts, axis=1)

    # broadcast the per-(worker, leaf) scale over each leaf's row range
    rows_per_leaf = np.array([r1 - r0 for r0, r1 in ranges])

    new_hat_nbrs, new_pending = [], []
    for i, (s, hn) in enumerate(zip(topo.offsets, state_half.hat_nbr_bufs)):
        q_recv, sc_recv, ring = recv_payload(i, s, q_buf, scales)
        sc_rows = jnp.repeat(sc_recv, rows_per_leaf, axis=1,
                             total_repeat_length=lrows)       # (K, rows)
        new_hat_nbrs.append(hn + (sc_rows[:, :, None]
                                  * q_recv.astype(jnp.float32)
                                  ).astype(hn.dtype))
        new_pending.append(ring)
    return PackedCDAdamState(
        x_new, state_half.m, state_half.v, state_half.count, new_hat_buf,
        tuple(new_hat_nbrs), spec, state_half.spec_m,
        None if pending is None else tuple(new_pending))


def _step_packed(state: PackedCDAdamState, grads: Any,
                 topo: "Topology | TopologySchedule",
                 cfg: CDAdamConfig) -> PackedCDAdamState:
    po, mo, vo, count = dadam._fused_local_packed(state, grads, cfg)
    half = PackedCDAdamState(po, mo, vo, count, state.hat_buf,
                             state.hat_nbr_bufs, state.spec, state.spec_m,
                             state.pending)
    if topo.K == 1:
        return half
    r = dadam._round_index(count, cfg.period)
    comm = lambda s: _round_dispatch(
        s, topo, r, lambda sh, v: _comm_round_packed(sh, v, cfg, r))
    if cfg.period == 1:
        return comm(half)
    do_comm = (count % cfg.period) == 0
    return jax.lax.cond(do_comm, comm, lambda s: s, half)


def step(state: "CDAdamState | PackedCDAdamState", grads: PyTree,
         topo: "Topology | TopologySchedule", cfg: CDAdamConfig,
         comp: Compressor) -> "CDAdamState | PackedCDAdamState":
    """One iteration of Alg. 2 (stacked mode).

    Packed-resident states (pallas backend) stay in the (K, rows, 128)
    layout end to end; ``grads`` may be a congruent pytree (packed once at
    this boundary) or an already packed buffer (zero pack/unpack)."""
    if isinstance(state, PackedCDAdamState):
        return _step_packed(state, grads, topo, cfg)
    half, mom = local_update(state.params, grads, state.moments, cfg)
    half_state = CDAdamState(half, mom, state.hat_self, state.hat_nbrs,
                             state.pending)
    if topo.K == 1:
        return half_state
    r = dadam._round_index(mom.count, cfg.period)
    if cfg.backend == "pallas":
        once = lambda sh, v: _comm_round_pallas(sh, v, cfg)
    else:
        once = lambda sh, v: _comm_round(sh, v, cfg, comp, r)
    comm = lambda s: _round_dispatch(s, topo, r, once)
    if cfg.period == 1:
        return comm(half_state)
    do_comm = (mom.count % cfg.period) == 0
    return jax.lax.cond(do_comm, comm, lambda s: s, half_state)


def round_step(state: "CDAdamState | PackedCDAdamState",
               grad_fn: Callable[[PyTree, Any], PyTree],
               batches: Any, topo: Topology, cfg: CDAdamConfig,
               comp: Compressor) -> "CDAdamState | PackedCDAdamState":
    """One communication round: p local Adam steps + one compressed gossip.

    For packed-resident states ``grad_fn`` receives the raw (K, rows, 128)
    parameter buffer (differentiate through ``packing.unpack`` for the
    zero-pack steady state; a returned pytree is packed at the boundary).
    """
    if isinstance(state, PackedCDAdamState):
        def body_packed(carry: PackedCDAdamState, batch):
            grads = grad_fn(carry.buf, batch)
            po, mo, vo, count = dadam._fused_local_packed(carry, grads, cfg)
            return PackedCDAdamState(po, mo, vo, count, carry.hat_buf,
                                     carry.hat_nbr_bufs, carry.spec,
                                     carry.spec_m, carry.pending), ()

        inner, _ = jax.lax.scan(body_packed, state, batches)
        if topo.K == 1:
            return inner
        r = dadam._round_index(inner.count, cfg.period)
        return _round_dispatch(
            inner, topo, r, lambda sh, v: _comm_round_packed(sh, v, cfg, r))

    def body(carry: CDAdamState, batch):
        grads = grad_fn(carry.params, batch)
        half, mom = local_update(carry.params, grads, carry.moments, cfg)
        return CDAdamState(half, mom, carry.hat_self, carry.hat_nbrs,
                           carry.pending), ()

    inner, _ = jax.lax.scan(body, state, batches)
    if topo.K == 1:
        return inner
    r = dadam._round_index(inner.moments.count, cfg.period)
    if cfg.backend == "pallas":
        once = lambda sh, v: _comm_round_pallas(sh, v, cfg)
    else:
        once = lambda sh, v: _comm_round(sh, v, cfg, comp, r)
    return _round_dispatch(inner, topo, r, once)


# The pre-unification ``CDAdamAxisState`` / ``comm_round_axis`` duplicate
# of this algorithm is gone: comm='axis' now runs the SAME ``step`` /
# ``round_step`` code inside shard_map (``make_optimizer(comm='axis',
# mesh=...)`` installs the wrapper), with the worker shifts lowering to
# ppermute via ``_shift_payload`` / ``dadam.shift_worker``.
