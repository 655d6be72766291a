"""D-Adam (Algorithm 1): decentralized Adam with periodic gossip.

Per worker k and iteration t:

    m_t = b1 * m_{t-1} + (1 - b1) * g_t
    v_t = b2 * v_{t-1} + (1 - b2) * g_t ** 2
    x_{t+1/2} = x_t - eta * m_t / (sqrt(v_t) + tau)
    if (t + 1) % p == 0:   x_{t+1} = sum_j W[k, j] * x_{t+1/2}^{(j)}
    else:                  x_{t+1} = x_{t+1/2}

Two equivalent runtime realizations, selected by ``DAdamConfig.comm`` and
sharing one code path (the only difference is how "worker k reads worker
(k + s) % K" is expressed — see :func:`shift_worker`):

* **comm='stacked'**: every pytree leaf carries a leading worker dim ``K``
  and the whole step runs as one program. Gossip is either a dense mixing
  einsum (paper-faithful baseline: lowered by XLA as gather-style
  collectives) or a sum of ``jnp.roll`` shifts over the worker dim for
  shift-invariant graphs (optimized: lowered as collective-permutes that
  only touch ring neighbors when the dim is sharded).
* **comm='axis'**: the SAME stacked state is partitioned over a named mesh
  axis (``cfg.axis_name``, one worker per mesh slot) and the step runs
  per-shard inside ``shard_map``; every worker shift is a
  ``jax.lax.ppermute`` over the axis, so the wire carries exactly one
  neighbor block per offset. ``make_optimizer(comm='axis', mesh=...)``
  installs the shard_map wrapper; the functions here only assume they are
  traced with ``cfg.axis_name`` bound.

Both share the same math; tests pin them against each other and against the
K=1 == Adam identity. The pallas backend composes with either comm mode:
the resident packed (K, rows, 128) buffer is sharded along its leading dim
and the fused kernels run on each worker's (1, rows, 128) shard.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.schedule import TopologySchedule, comm_offsets
from repro.core.topology import GridShift, Topology, offset_perm
# the pack layer is dependency-light (no Pallas import); the kernel stack
# itself (repro.kernels.ops) is imported lazily inside the pallas-only
# paths so backend='reference' users never pay for it
from repro.kernels import pack as packing
from repro.kernels.pack import BLOCK_ROWS

PyTree = Any

# staleness ages start "infinitely old" so the FIRST gossip round always
# takes a fresh payload (cold buffers never mix in); half of int32 max so
# age + 1 cannot overflow
COLD_AGE = np.int32(2**30)


@dataclasses.dataclass(frozen=True)
class DAdamConfig:
    eta: float = 1e-3           # initial learning rate (paper's eta)
    beta1: float = 0.9
    beta2: float = 0.999
    tau: float = 1e-6           # paper's tau > 0 (denominator guard)
    period: int = 1             # p: communicate every p iterations
    weight_decay: float = 0.0   # L2 (paper: 1e-4 for CIFAR-10)
    bias_correction: bool = False  # paper's Alg. 1 has none; optional extra
    mixing: str = "roll"        # 'dense' | 'roll' (comm='stacked' only)
    moment_dtype: Optional[Any] = None  # e.g. jnp.bfloat16 for huge models
    backend: str = "reference"  # 'reference' (jnp tree_map) | 'pallas'
                                # (fused one-pass kernel over the packed
                                # parameter vector; interpret mode off-TPU)
    comm: str = "stacked"       # 'stacked' (roll over the leading worker
                                # dim) | 'axis' (ppermute over axis_name
                                # inside shard_map; one worker per slot)
    axis_name: str = "worker"   # mesh axis carrying the worker dim when
                                # comm='axis'
    model_parallel: int = 1     # inner model-parallel group size per
                                # worker (comm='axis' 2D mesh): the packed
                                # row dim is sharded M-ways over
                                # model_axis_name and each worker's local
                                # step runs on a (1, rows/M, 128) shard
    model_axis_name: str = "model"  # mesh axis carrying the inner model
                                # shards when model_parallel > 1
    staleness: Optional[int] = None  # straggler-tolerant gossip: mix the
                                # last-arrived neighbor payload, at most
                                # tau rounds old (None = synchronous;
                                # tau=0 == synchronous bit-for-bit)
    straggler_rate: float = 0.0  # probability a neighbor payload misses a
                                # round (deterministic per straggler_seed)
    straggler_seed: int = 0
    overlap: bool = False       # comm/compute overlap: issue round r's
                                # gossip payload eagerly and fold it into
                                # round r+1's mix, so the wire exchange
                                # runs concurrently with the next p local
                                # Adam steps. Wire-equivalent to a
                                # staleness bound of one round with EVERY
                                # payload exactly one round late.

    def validate(self) -> None:
        if not 0 <= self.beta1 < 1 or not 0 <= self.beta2 < 1:
            raise ValueError("beta1/beta2 must be in [0, 1)")
        if self.tau <= 0:
            raise ValueError("tau must be > 0")
        if self.period < 1:
            raise ValueError("period p must be >= 1")
        if self.mixing not in ("dense", "roll"):
            raise ValueError(f"unknown mixing {self.mixing!r}")
        if self.backend not in ("reference", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.comm not in ("stacked", "axis"):
            raise ValueError(f"unknown comm {self.comm!r}")
        if self.comm == "axis":
            if not self.axis_name:
                raise ValueError("comm='axis' needs a non-empty axis_name")
            if self.mixing == "dense":
                raise ValueError(
                    "comm='axis' gossips with ppermute along the graph "
                    "offsets and has no dense-mixing lowering; use "
                    "mixing='roll' (shift-invariant topology) or "
                    "comm='stacked'")
        if self.model_parallel < 1:
            raise ValueError(
                f"model_parallel must be >= 1, got {self.model_parallel}")
        if self.model_parallel > 1:
            if self.comm != "axis":
                raise ValueError(
                    "model_parallel > 1 is the 2D (worker x model) mesh "
                    "execution and requires comm='axis'")
            if self.backend != "pallas":
                raise ValueError(
                    "model_parallel > 1 shards the packed row dim of the "
                    "resident (K, rows, 128) state and requires "
                    "backend='pallas' (the reference pytree layout has no "
                    "uniform row dim to shard)")
            if not self.model_axis_name:
                raise ValueError(
                    "model_parallel > 1 needs a non-empty model_axis_name")
        if self.backend == "pallas" and self.bias_correction:
            raise ValueError(
                "backend='pallas' implements the paper's Alg. 1 update "
                "(no bias correction); use backend='reference' for "
                "bias_correction=True")
        if self.staleness is not None:
            if self.staleness < 0:
                raise ValueError(
                    f"staleness bound tau must be >= 0, got {self.staleness}")
            if self.mixing == "dense":
                raise ValueError(
                    "staleness-bounded gossip double-buffers per-offset "
                    "neighbor payloads; it requires the shift lowering "
                    "(mixing='roll')")
            if self.model_parallel > 1:
                raise ValueError(
                    "staleness buffers are per-worker payload copies and "
                    "are not row-sharded; staleness requires "
                    "model_parallel == 1")
        if not 0.0 <= self.straggler_rate < 1.0:
            raise ValueError(
                f"straggler_rate must be in [0, 1), got "
                f"{self.straggler_rate}")
        if self.straggler_rate > 0.0 and self.staleness is None:
            raise ValueError(
                "straggler_rate > 0 models delayed payload arrivals and "
                "needs a staleness bound (set staleness=tau)")
        if self.overlap:
            if self.staleness is not None:
                raise ValueError(
                    "overlap IS the staleness tau=1 wire schedule (every "
                    "payload exactly one round late); combining it with "
                    "an explicit staleness bound is ambiguous — choose "
                    "one")
            if self.mixing == "dense":
                raise ValueError(
                    "overlap double-buffers per-offset neighbor payloads "
                    "and requires the shift lowering (mixing='roll')")


class AdamMoments(NamedTuple):
    m: PyTree
    v: PyTree
    count: jax.Array  # scalar int32 step counter


def init_moments(params: PyTree, cfg: DAdamConfig) -> AdamMoments:
    dt = cfg.moment_dtype

    def z(x):
        return jnp.zeros(x.shape, dtype=dt or x.dtype)

    zeros = jax.tree_util.tree_map(z, params)
    return AdamMoments(
        m=zeros,
        v=jax.tree_util.tree_map(jnp.zeros_like, zeros),
        count=jnp.zeros((), jnp.int32),
    )


def _local_update_pallas(
    params: PyTree, grads: PyTree, mom: AdamMoments, cfg: DAdamConfig
) -> Tuple[PyTree, PyTree, PyTree]:
    """Alg. 1 lines 4-6 as ONE fused kernel pass over the whole parameter
    vector: the pytree is packed into a lane-aligned buffer (the update is
    elementwise, so worker/leaf boundaries are irrelevant), updated in VMEM
    tiles, and unpacked. Moments keep their own (possibly narrower) dtype
    via a second spec over the same layout.

    This is the PR-1 *repack* path: it re-spends pack/unpack HBM traffic
    every call. The steady-state pallas runtime keeps the state resident in
    packed form instead (:class:`PackedDAdamState`); this path remains for
    pytree-state callers (``local_update`` on raw trees) and as the
    repack-vs-resident baseline in ``benchmarks/fused_step.py``."""
    from repro.kernels import ops

    spec_p = packing.make_spec(params, block_rows=BLOCK_ROWS)
    spec_m = packing.make_spec(mom.m, block_rows=BLOCK_ROWS)
    po, mo, vo = ops.fused_adam(
        packing.pack(params, spec_p),
        packing.pack(grads, spec_p),
        packing.pack(mom.m, spec_m),
        packing.pack(mom.v, spec_m),
        eta=cfg.eta, beta1=cfg.beta1, beta2=cfg.beta2, tau=cfg.tau,
        weight_decay=cfg.weight_decay)
    return (packing.unpack(po, spec_p), packing.unpack(mo, spec_m),
            packing.unpack(vo, spec_m))


def local_update(
    params: PyTree, grads: PyTree, mom: AdamMoments, cfg: DAdamConfig
) -> Tuple[PyTree, AdamMoments]:
    """Lines 3-6 of Alg. 1 — elementwise, stacked-K transparent."""
    count = mom.count + 1

    if cfg.backend == "pallas":
        new_params, new_m, new_v = _local_update_pallas(params, grads, mom,
                                                        cfg)
        return new_params, AdamMoments(new_m, new_v, count)

    def upd(x, g, m, v):
        g = g.astype(m.dtype)
        if cfg.weight_decay:
            g = g + cfg.weight_decay * x.astype(m.dtype)
        m_new = cfg.beta1 * m + (1.0 - cfg.beta1) * g
        v_new = cfg.beta2 * v + (1.0 - cfg.beta2) * (g * g)
        if cfg.bias_correction:
            t = count.astype(m.dtype)
            m_hat = m_new / (1.0 - cfg.beta1 ** t)
            v_hat = v_new / (1.0 - cfg.beta2 ** t)
        else:
            m_hat, v_hat = m_new, v_new
        step = cfg.eta * m_hat / (jnp.sqrt(v_hat) + cfg.tau)
        return (x - step.astype(x.dtype)), m_new, v_new

    flat = jax.tree_util.tree_map(upd, params, grads, mom.m, mom.v)
    new_params = jax.tree_util.tree_map(lambda t: t[0], flat,
                                        is_leaf=lambda t: isinstance(t, tuple))
    new_m = jax.tree_util.tree_map(lambda t: t[1], flat,
                                   is_leaf=lambda t: isinstance(t, tuple))
    new_v = jax.tree_util.tree_map(lambda t: t[2], flat,
                                   is_leaf=lambda t: isinstance(t, tuple))
    return new_params, AdamMoments(new_m, new_v, count)


# ------------------------------- gossip ------------------------------------


def shift_worker(x: jax.Array, s: Any, K: int,
                 axis_name: Optional[str] = None) -> jax.Array:
    """Worker k reads worker ``src(k)``'s value — THE primitive both comm
    modes share, for every offset kind: a plain int is the circulant
    ``src(k) = (k + s) % K``, a :class:`~repro.core.topology.GridShift` the
    row-wrap-aware torus neighbor, a ``PermShift`` an explicit permutation.

    comm='stacked' (``axis_name=None``): a roll (or gather, for explicit
    permutations) over the leading worker dim. comm='axis': a ``ppermute``
    over the mesh axis built from the offset's permutation — round-indexed
    schedules just switch between such perms — shipping exactly one
    neighbor block per offset on the wire."""
    if axis_name is not None:
        if isinstance(s, (int, np.integer)):
            perm = [((k + int(s)) % K, k) for k in range(K)]  # (src, dst)
        else:
            src = offset_perm(s, K)
            perm = [(int(src[k]), k) for k in range(K)]
        return jax.lax.ppermute(x, axis_name, perm)
    if x.ndim < 1:
        return x
    if isinstance(s, (int, np.integer)):
        return jnp.roll(x, -int(s), axis=0)
    if isinstance(s, GridShift):
        # roll the worker dim as its (rows, cols) grid — the column roll
        # wraps within the row, which is what the flat circulant got wrong
        xg = x.reshape((s.rows, s.cols) + x.shape[1:])
        xg = jnp.roll(xg, (-s.dr, -s.dc), axis=(0, 1))
        return xg.reshape(x.shape)
    return jnp.take(x, jnp.asarray(offset_perm(s, K)), axis=0)


def gossip_dense(params: PyTree, W: jax.Array | np.ndarray) -> PyTree:
    """x^{(k)} <- sum_j W[k, j] x^{(j)} via a dense mixing matmul.

    Paper-faithful baseline. On a sharded worker axis XLA lowers this to an
    all-gather of the full parameter stack — the cost the optimized 'roll'
    path removes.
    """
    Wj = jnp.asarray(W)

    def mix(x):
        return jnp.einsum(
            "kj,j...->k...", Wj.astype(jnp.float32), x.astype(jnp.float32)
        ).astype(x.dtype)

    return jax.tree_util.tree_map(mix, params)


def gossip_shift(params: PyTree, topo: Topology,
                 axis_name: Optional[str] = None) -> PyTree:
    """Shift-invariant gossip — ONE implementation for both comm modes.

    mixed[k] = w_self * x[k] + sum_s w_s * x[(k + s) % K]

    With ``axis_name=None`` each shift is a roll over the leading worker
    dim (comm='stacked'; when that dim is sharded, XLA lowers each roll to
    a collective-permute touching only the true graph neighbors). With a
    mesh axis name the shift IS a ``ppermute`` (comm='axis', inside
    shard_map): ring gossip costs 2 neighbor transfers instead of a K-way
    gather, in either lowering.
    """
    if not topo.offsets:
        if topo.K == 1:
            return params
        raise ValueError(
            f"topology {topo.name!r} has no shift structure; use gossip_dense"
        )

    def mix(x):
        acc = (topo.self_weight * x.astype(jnp.float32))
        for s, w in zip(topo.offsets, topo.offset_weights):
            acc = acc + w * shift_worker(x, s, topo.K,
                                         axis_name).astype(jnp.float32)
        return acc.astype(x.dtype)

    return jax.tree_util.tree_map(mix, params)


def gossip_roll(params: PyTree, topo: Topology) -> PyTree:
    """comm='stacked' spelling of :func:`gossip_shift` (kept as the
    reference oracle the kernel/axis variants are pinned against)."""
    return gossip_shift(params, topo)


def gossip_axis(params: PyTree, topo: Topology, axis_name: str) -> PyTree:
    """comm='axis' spelling of :func:`gossip_shift`, for use inside
    ``shard_map`` with one worker per slot of ``axis_name``."""
    if topo.K == 1:
        return params
    return gossip_shift(params, topo, axis_name)


def gossip(params: PyTree, topo: Topology, cfg: DAdamConfig) -> PyTree:
    """The comm dispatch both backends' pytree paths share."""
    if cfg.comm == "axis":
        return gossip_axis(params, topo, cfg.axis_name)
    if cfg.mixing == "dense" or not topo.offsets:
        return gossip_dense(params, topo.weights)
    return gossip_shift(params, topo)


# backward-compatible name (pre-unification callers: baselines, tests)
gossip_stacked = gossip


# -------------------- straggler-tolerant (stale) gossip ---------------------


class StaleBufs(NamedTuple):
    """Double-buffered neighbor payloads for staleness-bounded gossip.

    ``bufs[i]`` holds the payload last taken from offset i's neighbor (same
    structure as the params / packed buffer); ``age[k, i]`` counts rounds
    since worker k last refreshed it. A round mixes the buffered copy while
    it is younger than the bound tau, and MUST take a fresh payload once
    ``age >= tau`` — so no mixed-in value is ever more than tau rounds old,
    and tau=0 degenerates to today's synchronous gossip bit-for-bit."""

    bufs: Tuple[Any, ...]
    age: jax.Array            # (K, deg) int32; (1, deg) inside shard_map


def _round_index(count: jax.Array, period: int) -> jax.Array:
    """0-based communication-round index at a comm step (count = p, 2p...)."""
    return jnp.maximum(count // period - 1, 0)


def _local_worker_rows(arr: jax.Array, cfg: DAdamConfig) -> jax.Array:
    """Slice a (K, ...) per-worker constant down to this worker's row when
    traced inside shard_map (comm='axis'); identity under comm='stacked'."""
    if cfg.comm != "axis":
        return arr
    k = jax.lax.axis_index(cfg.axis_name)
    return jax.lax.dynamic_slice_in_dim(arr, k, 1, axis=0)


def _arrival_mask(cfg: DAdamConfig, r: jax.Array, K: int,
                  deg: int) -> jax.Array:
    """(K, deg) bool — which neighbor payloads arrive in round r. Derived
    from the round index with a fixed seed, so every worker (and every
    shard_map slot) agrees on the same arrival pattern without
    communication, and a rerun reproduces the same straggler trace."""
    local = 1 if cfg.comm == "axis" else K
    if cfg.straggler_rate <= 0.0:
        return jnp.ones((local, deg), bool)
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.straggler_seed),
                             jnp.asarray(r, jnp.int32))
    mask = jax.random.uniform(key, (K, deg)) >= cfg.straggler_rate
    return _local_worker_rows(mask, cfg)


def init_stale(params_like: PyTree,
               topo: "Topology | TopologySchedule") -> StaleBufs:
    """Cold staleness buffers over ``topo``'s (union) offsets: zero
    payloads at COLD_AGE, forcing a fresh exchange on first use."""
    offs = comm_offsets(topo)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params_like)
    return StaleBufs(tuple(zeros for _ in offs),
                     jnp.full((topo.K, len(offs)), COLD_AGE, jnp.int32))


def gossip_shift_stale(params: PyTree, stale: StaleBufs, topo: Topology,
                       cfg: DAdamConfig, r: jax.Array
                       ) -> Tuple[PyTree, StaleBufs]:
    """Shift gossip with a staleness bound: round r mixes, per offset, the
    freshly shifted payload when it arrives (or when the buffered copy hits
    the bound tau) and the buffered <= tau-rounds-old copy otherwise. The
    local Adam half-step never waits — this is the straggler-tolerant
    overlap. With tau=0 every payload is forced fresh and the result is
    bit-for-bit :func:`gossip_shift`."""
    if not topo.offsets:
        return params, stale
    axis = cfg.axis_name if cfg.comm == "axis" else None
    tau = int(cfg.staleness)
    if tau == 0:
        # ages are non-negative, so take = arrive | (age >= 0) is
        # STATICALLY all-true and the buffered copies are never read:
        # run the literal synchronous mix (bit-for-bit gossip_shift —
        # routing payloads through buffer outputs would perturb XLA's FMA
        # fusion by 1 ulp) and pass the untouched buffers through.
        return (gossip_shift(params, topo, axis),
                StaleBufs(stale.bufs, jnp.zeros_like(stale.age)))
    arrive = _arrival_mask(cfg, r, topo.K, len(topo.offsets))
    take = arrive | (stale.age >= tau)
    new_age = jnp.where(take, 0, stale.age + 1).astype(stale.age.dtype)
    new_bufs = []
    for i, s in enumerate(topo.offsets):
        m = take[:, i]

        def pick(x, b, s=s):
            mm = m.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.where(mm, shift_worker(x, s, topo.K, axis),
                             b.astype(x.dtype))

        new_bufs.append(jax.tree_util.tree_map(pick, params,
                                               stale.bufs[i]))

    def mix(x, *nbrs):
        acc = topo.self_weight * x.astype(jnp.float32)
        for w, nb in zip(topo.offset_weights, nbrs):
            acc = acc + w * nb.astype(jnp.float32)
        return acc.astype(x.dtype)

    mixed = jax.tree_util.tree_map(mix, params, *new_bufs)
    return mixed, StaleBufs(tuple(new_bufs), new_age)


def gossip_shift_overlap(params: PyTree, stale: StaleBufs, topo: Topology,
                         cfg: DAdamConfig) -> Tuple[PyTree, StaleBufs]:
    """Comm/compute-overlapped shift gossip: round r ISSUES this round's
    neighbor exchange (the fresh shifts) but MIXES the payloads issued at
    round r-1, held in the staleness buffers. The issued shifts have no
    data dependence on the mixed result, so XLA's async collectives +
    latency-hiding scheduler (see repro.launch.env) can run the wire
    exchange concurrently with the next p local Adam steps — a uniform
    delay-1 wire schedule, the deterministic cousin of
    :func:`gossip_shift_stale`'s bounded-staleness take.

    Cold buffers (first round, and post-:mod:`~repro.core.elastic` resize,
    marked by ``age >= COLD_AGE``) fold the fresh payload instead — the
    same forced-fresh rule the staleness bound applies at ``age >= tau``.
    """
    if not topo.offsets:
        return params, stale
    axis = cfg.axis_name if cfg.comm == "axis" else None
    cold = stale.age >= COLD_AGE
    fresh, used = [], []
    for i, s in enumerate(topo.offsets):
        c = cold[:, i]

        def issue(x, s=s):
            return shift_worker(x, s, topo.K, axis)

        def pick(f, b, c=c):
            cc = c.reshape((-1,) + (1,) * (f.ndim - 1))
            return jnp.where(cc, f, b.astype(f.dtype))

        f = jax.tree_util.tree_map(issue, params)
        fresh.append(f)
        used.append(jax.tree_util.tree_map(pick, f, stale.bufs[i]))

    def mix(x, *nbrs):
        acc = topo.self_weight * x.astype(jnp.float32)
        for w, nb in zip(topo.offset_weights, nbrs):
            acc = acc + w * nb.astype(jnp.float32)
        return acc.astype(x.dtype)

    mixed = jax.tree_util.tree_map(mix, params, *used)
    return mixed, StaleBufs(tuple(fresh), jnp.zeros_like(stale.age))


# -------------------- packed-resident gossip (pallas) ----------------------


def gossip_packed(buf: jax.Array, topo: Topology, cfg: DAdamConfig
                  ) -> jax.Array:
    """Gossip directly on the resident packed buffer — the state never
    leaves the (K, rows, LANE) layout in either comm mode.

    comm='stacked': shift-invariant graphs dispatch to the fused Pallas
    mixing kernel (one VMEM pass, no rolled intermediates); dense/non-shift
    topologies — and graphs too dense to keep every neighbor block in VMEM
    — fall back to the mixing einsum over the worker dim of the buffer.

    comm='axis' (inside shard_map, ``buf`` is this worker's (1, rows, LANE)
    shard): each offset is a ``ppermute`` of the packed row-block over the
    worker axis, accumulated in f32 — the wire carries exactly one packed
    neighbor block per graph offset."""
    from repro.kernels import ops
    from repro.kernels.gossip import MAX_FUSED_DEGREE

    if topo.K == 1:
        return buf
    if cfg.comm == "axis":
        if not topo.offsets:
            raise ValueError("comm='axis' gossip needs a shift-invariant "
                             "topology")
        acc = topo.self_weight * buf.astype(jnp.float32)
        for s, w in zip(topo.offsets, topo.offset_weights):
            acc = acc + w * shift_worker(buf, s, topo.K,
                                         cfg.axis_name).astype(jnp.float32)
        return acc.astype(buf.dtype)
    # PermShift offsets (randomized rings) have no index-map arithmetic the
    # fused kernel can express; they take the einsum against the entry's
    # weight matrix (ints and GridShifts fuse)
    fusable = all(isinstance(s, (int, np.integer)) or isinstance(s, GridShift)
                  for s in topo.offsets)
    if (cfg.mixing == "dense" or not topo.offsets or not fusable
            or len(topo.offsets) > MAX_FUSED_DEGREE):
        W = jnp.asarray(topo.weights, jnp.float32)
        return jnp.einsum("kj,jrc->krc", W,
                          buf.astype(jnp.float32)).astype(buf.dtype)
    return ops.gossip_mix(buf, topo.offsets, topo.offset_weights,
                          topo.self_weight)


def gossip_packed_stale(buf: jax.Array, stale: StaleBufs, topo: Topology,
                        cfg: DAdamConfig, r: jax.Array
                        ) -> Tuple[jax.Array, StaleBufs]:
    """Staleness-bounded gossip on the resident packed buffer: the
    payload-buffer update is elementwise over (K, rows, LANE) blocks, and
    the mix runs as the fused payload kernel (same accumulation order as
    ``gossip_mix``, so tau=0 is bit-for-bit the synchronous packed round).
    Under comm='axis' each fresh take is one ppermute of the packed block;
    a buffered take costs no wire traffic at all."""
    from repro.kernels import ops
    from repro.kernels.gossip import MAX_FUSED_DEGREE

    if not topo.offsets:
        return buf, stale
    axis = cfg.axis_name if cfg.comm == "axis" else None
    tau = int(cfg.staleness)
    if tau == 0:
        # statically always-fresh and the buffers are never read: run the
        # literal synchronous packed round (see gossip_shift_stale for why
        # this, not a masked select, is what keeps tau=0 bit-for-bit)
        return (gossip_packed(buf, topo, cfg),
                StaleBufs(stale.bufs, jnp.zeros_like(stale.age)))
    arrive = _arrival_mask(cfg, r, topo.K, len(topo.offsets))
    take = arrive | (stale.age >= tau)
    new_age = jnp.where(take, 0, stale.age + 1).astype(stale.age.dtype)
    used = []
    for i, s in enumerate(topo.offsets):
        m = take[:, i].reshape((-1, 1, 1))
        used.append(jnp.where(m, shift_worker(buf, s, topo.K, axis),
                              stale.bufs[i].astype(buf.dtype)))
    if axis is None and len(used) <= MAX_FUSED_DEGREE:
        mixed = ops.payload_mix(buf, used, topo.offset_weights,
                                topo.self_weight)
    else:
        acc = topo.self_weight * buf.astype(jnp.float32)
        for w, u in zip(topo.offset_weights, used):
            acc = acc + w * u.astype(jnp.float32)
        mixed = acc.astype(buf.dtype)
    return mixed, StaleBufs(tuple(used), new_age)


def gossip_packed_overlap(buf: jax.Array, stale: StaleBufs, topo: Topology,
                          cfg: DAdamConfig
                          ) -> Tuple[jax.Array, StaleBufs]:
    """Packed twin of :func:`gossip_shift_overlap`: issue this round's
    shifted packed blocks, mix last round's buffered ones (fresh on cold
    start / post-resize), with the same fused payload-mix kernel and f32
    accumulation order as the staleness path."""
    from repro.kernels import ops
    from repro.kernels.gossip import MAX_FUSED_DEGREE

    if not topo.offsets:
        return buf, stale
    axis = cfg.axis_name if cfg.comm == "axis" else None
    cold = stale.age >= COLD_AGE
    fresh, used = [], []
    for i, s in enumerate(topo.offsets):
        c = cold[:, i].reshape((-1, 1, 1))
        f = shift_worker(buf, s, topo.K, axis)
        fresh.append(f)
        used.append(jnp.where(c, f, stale.bufs[i].astype(buf.dtype)))
    if axis is None and len(used) <= MAX_FUSED_DEGREE:
        mixed = ops.payload_mix(buf, used, topo.offset_weights,
                                topo.self_weight)
    else:
        acc = topo.self_weight * buf.astype(jnp.float32)
        for w, u in zip(topo.offset_weights, used):
            acc = acc + w * u.astype(jnp.float32)
        mixed = acc.astype(buf.dtype)
    return mixed, StaleBufs(tuple(fresh), jnp.zeros_like(stale.age))


# --------------------- round dispatch (schedule-aware) ----------------------


def _gossip_round(params: PyTree, stale: Optional[StaleBufs],
                  topo: "Topology | TopologySchedule", cfg: DAdamConfig,
                  r: jax.Array) -> Tuple[PyTree, Optional[StaleBufs]]:
    """One communication round on the pytree path: schedule entries switch
    on the (traced) round index — each branch closes over its own STATIC
    offsets/weights, so a whole schedule still compiles to one step."""
    def once(op, topo_r):
        p, st = op
        if st is None:
            return gossip(p, topo_r, cfg), None
        if cfg.overlap:
            return gossip_shift_overlap(p, st, topo_r, cfg)
        return gossip_shift_stale(p, st, topo_r, cfg, r)

    if isinstance(topo, TopologySchedule):
        # per-edge payload buffers need the SAME offset tuple every round
        # (union views); without live buffers — no staleness/overlap, or
        # tau=0 where they are never read — each round gossips its entry
        use_union = stale is not None and (
            int(cfg.staleness or 0) > 0 or cfg.overlap)
        views = topo.union_views() if use_union else topo.entries
        if len(views) == 1:
            return once((params, stale), views[0])
        return jax.lax.switch(
            r % len(views),
            [(lambda op, v=v: once(op, v)) for v in views],
            (params, stale))
    return once((params, stale), topo)


def _gossip_packed_round(buf: jax.Array, stale: Optional[StaleBufs],
                         topo: "Topology | TopologySchedule",
                         cfg: DAdamConfig, r: jax.Array
                         ) -> Tuple[jax.Array, Optional[StaleBufs]]:
    """Packed twin of :func:`_gossip_round`."""
    def once(op, topo_r):
        b, st = op
        if st is None:
            return gossip_packed(b, topo_r, cfg), None
        if cfg.overlap:
            return gossip_packed_overlap(b, st, topo_r, cfg)
        return gossip_packed_stale(b, st, topo_r, cfg, r)

    if isinstance(topo, TopologySchedule):
        use_union = stale is not None and (
            int(cfg.staleness or 0) > 0 or cfg.overlap)
        views = topo.union_views() if use_union else topo.entries
        if len(views) == 1:
            return once((buf, stale), views[0])
        return jax.lax.switch(
            r % len(views),
            [(lambda op, v=v: once(op, v)) for v in views],
            (buf, stale))
    return once((buf, stale), topo)


# ------------------------------ state + step -------------------------------


class DAdamState(NamedTuple):
    params: PyTree          # stacked (K, ...) in stacked mode
    moments: AdamMoments
    # transient straggler-tolerant payload buffers (cfg.staleness != None);
    # stripped from checkpoints and rebuilt cold on restore
    stale: Optional[StaleBufs] = None


@jax.tree_util.register_pytree_node_class
class PackedDAdamState:
    """Resident packed D-Adam state for ``backend='pallas'``.

    The stacked, leaf-aligned ``(K, rows, 128)`` buffer is the *persistent*
    representation: params (``buf``) and both moments (``m``, ``v``) live
    packed across steps, so the fused-Adam and gossip kernels consume and
    produce it directly — zero per-step pack/unpack. Packing happens once
    in :func:`init`; unpacked pytree views materialize only at boundaries
    (``.params`` / ``.moments`` for eval, logging and checkpointing).

    The :class:`~repro.kernels.pack.PackSpec` pair rides along as *static*
    pytree aux_data, so the state jits/scans/conds like a NamedTuple while
    the specs stay Python-side."""

    __slots__ = ("buf", "m", "v", "count", "spec", "spec_m", "stale")

    def __init__(self, buf, m, v, count, spec, spec_m, stale=None):
        self.buf, self.m, self.v, self.count = buf, m, v, count
        self.spec, self.spec_m = spec, spec_m
        self.stale = stale

    def tree_flatten(self):
        return ((self.buf, self.m, self.v, self.count, self.stale),
                (self.spec, self.spec_m))

    @classmethod
    def tree_unflatten(cls, aux, children):
        buf, m, v, count, stale = children
        return cls(buf, m, v, count, *aux, stale)

    def with_stale(self, stale) -> "PackedDAdamState":
        return PackedDAdamState(self.buf, self.m, self.v, self.count,
                                self.spec, self.spec_m, stale)

    # ------- unpacked views: boundary use only (eval/log/checkpoint) -------

    @property
    def params(self) -> PyTree:
        return packing.unpack(self.buf, self.spec)

    @property
    def moments(self) -> AdamMoments:
        return AdamMoments(packing.unpack(self.m, self.spec_m),
                           packing.unpack(self.v, self.spec_m), self.count)

    def unpacked(self) -> DAdamState:
        """Portable (backend-agnostic) NamedTuple state — the checkpoint
        wire format, identical leaf-for-leaf to a reference-backend state."""
        return DAdamState(self.params, self.moments)

    @classmethod
    def from_unpacked(cls, state: DAdamState, *,
                      row_shards: int = 1) -> "PackedDAdamState":
        """``row_shards=M`` packs into the 2D-mesh row-sharded layout
        (each leaf split across M shard blocks; see kernels/pack.py)."""
        spec = packing.make_spec(state.params, stacked=True,
                                 block_rows=BLOCK_ROWS, leaf_align=True,
                                 row_shards=row_shards)
        spec_m = packing.make_spec(state.moments.m, stacked=True,
                                   block_rows=BLOCK_ROWS, leaf_align=True,
                                   row_shards=row_shards)
        return cls(packing.pack(state.params, spec),
                   packing.pack(state.moments.m, spec_m),
                   packing.pack(state.moments.v, spec_m),
                   state.moments.count, spec, spec_m)


def grads_buffer(grads: Any, spec: packing.PackSpec, dtype: Any,
                 like_shape: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """Admit gradients in either form at the step boundary: an already
    packed ``(K, rows, 128)`` buffer passes through untouched (the
    steady-state path — differentiate the loss through ``packing.unpack``
    and AD's transpose delivers grads packed for free); a pytree —
    including a bare array for single-leaf parameter trees — is packed
    once here as a convenience.

    ``like_shape`` is the resident parameter buffer's shape; under
    comm='axis' it is the per-shard ``(K_local, rows, 128)`` shape inside
    shard_map (the spec keeps the *global* K), so buffer grads are checked
    against it rather than against ``spec.buf_shape()``."""
    want = tuple(like_shape) if like_shape is not None else spec.buf_shape()
    if isinstance(grads, jax.Array):
        if tuple(grads.shape) == want:
            return grads.astype(dtype)
        if len(spec.shapes) == 1 and tuple(grads.shape) == spec.shapes[0]:
            # bare-array gradient of a single-leaf parameter tree
            return packing.pack(grads, spec, dtype=dtype)
        raise ValueError(
            f"packed grads shape {tuple(grads.shape)} != resident "
            f"buffer {want}")
    return packing.pack(grads, spec, dtype=dtype)


def init(params_stacked: PyTree, cfg: DAdamConfig,
         topo: "Topology | TopologySchedule | None" = None
         ) -> "DAdamState | PackedDAdamState":
    cfg.validate()
    needs_bufs = cfg.staleness is not None or cfg.overlap
    if needs_bufs and topo is None:
        raise ValueError(
            "cfg.staleness/cfg.overlap buffer one payload per topology "
            "offset; init needs the topology (pass topo=, as "
            "make_optimizer does)")
    state = DAdamState(params_stacked, init_moments(params_stacked, cfg))
    if cfg.backend == "pallas":
        packed = PackedDAdamState.from_unpacked(
            state, row_shards=cfg.model_parallel)
        if needs_bufs:
            packed = packed.with_stale(init_stale(packed.buf, topo))
        return packed
    if needs_bufs:
        state = state._replace(stale=init_stale(params_stacked, topo))
    return state


def _fused_local_packed(state: PackedDAdamState, grads: Any,
                        cfg: DAdamConfig
                        ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array]:
    """Alg. 1 lines 3-6 on resident buffers: one fused kernel pass, no
    packing. Returns (params_buf, m_buf, v_buf, count)."""
    from repro.kernels import ops

    gbuf = grads_buffer(grads, state.spec, state.buf.dtype,
                        like_shape=state.buf.shape)
    po, mo, vo = ops.fused_adam(
        state.buf, gbuf, state.m, state.v,
        eta=cfg.eta, beta1=cfg.beta1, beta2=cfg.beta2, tau=cfg.tau,
        weight_decay=cfg.weight_decay)
    return po, mo, vo, state.count + 1


def _gossip_adam_eligible(topo: "Topology | TopologySchedule",
                          cfg: DAdamConfig) -> bool:
    """True when the synchronous comm='stacked' step can run as the
    single-pass ``gossip_adam_mix`` kernel: a static shift-invariant
    topology whose fused degree fits VMEM, with no payload buffers in
    flight (staleness/overlap route the mix through StaleBufs)."""
    from repro.kernels.gossip import MAX_GOSSIP_ADAM_DEGREE

    if isinstance(topo, TopologySchedule):
        return False
    if cfg.comm != "stacked" or cfg.mixing == "dense":
        return False
    if cfg.staleness is not None or cfg.overlap:
        return False
    if topo.K == 1 or not topo.offsets:
        return False
    if len(topo.offsets) > MAX_GOSSIP_ADAM_DEGREE:
        return False
    return all(isinstance(s, (int, np.integer, GridShift))
               for s in topo.offsets)


def _step_packed_fused(state: PackedDAdamState, grads: Any,
                       topo: Topology, cfg: DAdamConfig
                       ) -> PackedDAdamState:
    """Comm-step fast path: Adam half-step AND gossip mix in one VMEM
    pass over the resident buffers (``kernels.gossip.gossip_adam_mix``) —
    the half-stepped parameter stack never round-trips HBM. Matches the
    two-pass (fused_adam → gossip_mix) sequence: m and v bit for bit,
    params within one rounding per mixed term; non-comm steps under
    period > 1 run the plain fused_adam branch of the same cond."""
    from repro.kernels import ops

    gbuf = grads_buffer(grads, state.spec, state.buf.dtype,
                        like_shape=state.buf.shape)
    count = state.count + 1
    kw = dict(eta=cfg.eta, beta1=cfg.beta1, beta2=cfg.beta2, tau=cfg.tau,
              weight_decay=cfg.weight_decay)

    def fused(op):
        p, m, v = op
        return ops.gossip_adam_mix(p, gbuf, m, v, topo.offsets,
                                   topo.offset_weights, topo.self_weight,
                                   **kw)

    def plain(op):
        p, m, v = op
        return ops.fused_adam(p, gbuf, m, v, **kw)

    op = (state.buf, state.m, state.v)
    if cfg.period == 1:
        po, mo, vo = fused(op)
    else:
        do_comm = (count % cfg.period) == 0
        po, mo, vo = jax.lax.cond(do_comm, fused, plain, op)
    return PackedDAdamState(po, mo, vo, count, state.spec, state.spec_m,
                            state.stale)


def _step_packed(state: PackedDAdamState, grads: Any,
                 topo: "Topology | TopologySchedule",
                 cfg: DAdamConfig) -> PackedDAdamState:
    if _gossip_adam_eligible(topo, cfg):
        return _step_packed_fused(state, grads, topo, cfg)
    po, mo, vo, count = _fused_local_packed(state, grads, cfg)
    r = _round_index(count, cfg.period)

    def comm(op):
        return _gossip_packed_round(op[0], op[1], topo, cfg, r)

    if cfg.period == 1:
        buf, stale = comm((po, state.stale))
    else:
        do_comm = (count % cfg.period) == 0
        buf, stale = jax.lax.cond(do_comm, comm, lambda op: op,
                                  (po, state.stale))
    return PackedDAdamState(buf, mo, vo, count, state.spec, state.spec_m,
                            stale)


def step(
    state: "DAdamState | PackedDAdamState",
    grads: PyTree,
    topo: Topology,
    cfg: DAdamConfig,
) -> "DAdamState | PackedDAdamState":
    """One iteration of Alg. 1 with the communication-skip condition
    evaluated in-graph (lax.cond keeps a single jitted step). Under
    comm='axis' this function is traced inside shard_map (one worker per
    mesh slot) — the code is identical; only the worker shifts lower
    differently.

    Packed-resident states (pallas backend) never leave the (K, rows, 128)
    layout: fused-Adam and the gossip kernel consume the buffers directly.
    ``grads`` may be a congruent pytree (packed once at this boundary) or
    an already packed buffer (zero pack/unpack)."""
    if isinstance(state, PackedDAdamState):
        return _step_packed(state, grads, topo, cfg)
    half, mom = local_update(state.params, grads, state.moments, cfg)
    r = _round_index(mom.count, cfg.period)

    def comm(op):
        return _gossip_round(op[0], op[1], topo, cfg, r)

    if cfg.period == 1:
        new_params, stale = comm((half, state.stale))
        return DAdamState(new_params, mom, stale)
    do_comm = (mom.count % cfg.period) == 0
    new_params, stale = jax.lax.cond(do_comm, comm, lambda op: op,
                                     (half, state.stale))
    return DAdamState(new_params, mom, stale)


def round_step(
    state: "DAdamState | PackedDAdamState",
    grad_fn: Callable[[PyTree, Any], PyTree],
    batches: Any,  # pytree with leading dim p (one microbatch per local step)
    topo: Topology,
    cfg: DAdamConfig,
) -> "DAdamState | PackedDAdamState":
    """One *communication round* = p local steps (lax.scan) + one gossip.

    This is the unit the launcher lowers for the dry-run: the compiled HLO
    contains exactly one gossip exchange per p local Adam steps, so the
    roofline's collective bytes reflect the paper's skipping schedule.

    For packed-resident states ``grad_fn`` receives the raw (K, rows, 128)
    parameter buffer and may return grads as a congruent buffer (the
    zero-pack steady state: differentiate the loss through
    ``packing.unpack``) or as a pytree (packed at the boundary).
    """
    if isinstance(state, PackedDAdamState):
        def body_packed(carry: PackedDAdamState, batch):
            grads = grad_fn(carry.buf, batch)
            po, mo, vo, count = _fused_local_packed(carry, grads, cfg)
            return PackedDAdamState(po, mo, vo, count, carry.spec,
                                    carry.spec_m, carry.stale), ()

        inner, _ = jax.lax.scan(body_packed, state, batches)
        buf, stale = _gossip_packed_round(
            inner.buf, inner.stale, topo, cfg,
            _round_index(inner.count, cfg.period))
        return PackedDAdamState(buf, inner.m, inner.v, inner.count,
                                state.spec, state.spec_m, stale)

    def body(carry: DAdamState, batch):
        grads = grad_fn(carry.params, batch)
        half, mom = local_update(carry.params, grads, carry.moments, cfg)
        return DAdamState(half, mom, carry.stale), ()

    inner, _ = jax.lax.scan(body, state, batches)
    new_params, stale = _gossip_round(
        inner.params, inner.stale, topo, cfg,
        _round_index(inner.moments.count, cfg.period))
    return DAdamState(new_params, inner.moments, stale)


def consensus_error(params_stacked: PyTree) -> jax.Array:
    """(1/K) sum_k ||x_k - x_bar||^2 — the quantity Lemma 1 bounds."""
    def per_leaf(x):
        mean = jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True)
        return jnp.sum((x.astype(jnp.float32) - mean) ** 2) / x.shape[0]
    leaves = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(per_leaf, params_stacked))
    return sum(leaves)


def mean_params(params_stacked: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: jnp.mean(x.astype(jnp.float32), axis=0).astype(x.dtype),
        params_stacked)
