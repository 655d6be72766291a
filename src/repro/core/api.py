"""Public facade: build a decentralized optimizer from a config dict/str.

    opt = make_optimizer("d-adam", K=8, period=16, topology="ring")
    state = opt.init(stacked_params)
    state = opt.step(state, stacked_grads)      # in-graph comm-skip cond
    state = opt.round(state, grad_fn, batches)  # p local steps + 1 gossip

Everything is a pure function closed over static config — safe to jit,
shard, scan and checkpoint.

With ``backend='pallas'`` the state returned by ``opt.init`` is
packed-resident (:class:`~repro.core.dadam.PackedDAdamState` /
:class:`~repro.core.cdadam.PackedCDAdamState`): params and moments live in
the stacked (K, rows, 128) kernel layout across steps and ``opt.step``
accepts grads either as a congruent pytree or as an already packed buffer.
``opt.params_of`` transparently materializes the unpacked pytree view at
eval/logging boundaries for both backends.

With ``comm='axis'`` (device-parallel execution) pass ``mesh=`` with a
worker axis of size K: ``opt.init`` places every state leaf's leading
worker dim on that axis and ``opt.step`` / ``opt.round`` run the SAME core
step per-shard inside ``shard_map``, gossiping with ``ppermute`` — for the
pallas backend each device updates its own (1, rows, 128) shard of the
resident packed buffer and only packed neighbor row-blocks (or, for
CD-Adam, the int8 sign payload + per-(worker, leaf) scales) travel over
the axis.

When the mesh ALSO carries a 'model' axis of size M
(``launch.mesh.make_worker_mesh(K, model_parallel=M)``), execution goes 2D:
the packed state is built in the row-sharded layout (``kernels.pack
row_shards=M``) and partitioned ``P('worker', 'model')`` — each of the
K × M devices holds a (1, rows/M, 128) block carrying 1/M of every leaf.
Gossip/payload ppermutes cross ONLY the worker axis (each model column
exchanges its own row block), grads are computed model-parallel against
the row-sharded buffer — either by the grad pipeline's sharded-packed
mode (``opt.sharded_value_and_grad`` runs the loss inside the 2D
shard_map on each device's local block: zero full-param all-gather; see
``train/grad.py``) or by GSPMD through the row-sharded unpack — and
CD-Adam's per-(worker, leaf) compression scales psum their |delta|
partials over 'model' so the math stays exactly the reference semantics
(``scales='worker'`` opts into one whole-buffer scale per worker
instead). Requires ``backend='pallas'``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import numpy as np

from repro.core import baselines, cdadam, dadam
from repro.core import schedule as _sched
from repro.core.cdadam import CDAdamConfig, PackedCDAdamState
from repro.core.compression import Compressor, make_compressor
from repro.core.dadam import DAdamConfig, PackedDAdamState
from repro.core.schedule import TopologySchedule, make_schedule
from repro.core.topology import Topology, make_topology
from repro.kernels import pack as _pack

PyTree = Any


def is_packed_state(state: Any) -> bool:
    """True for the packed-resident optimizer states of backend='pallas'."""
    return isinstance(state, (PackedDAdamState, PackedCDAdamState))


# --------------------- comm='axis' shard_map dispatch -----------------------


def worker_pspec_tree(tree: PyTree, K: int, axis_name: str,
                      worker_dim: int = 0,
                      model_axis: Optional[str] = None) -> PyTree:
    """PartitionSpecs putting each leaf's worker dim (size K at
    ``worker_dim``) on ``axis_name``; scalars and worker-free leaves are
    replicated. ``worker_dim=1`` matches ``round``'s (p, K, ...) batch
    leaves.

    With ``model_axis`` (the 2D worker × model mesh) packed
    ``(K, rows, 128)`` buffers — recognized by their 3-D lane-aligned
    shape — additionally put their row dim on the model axis, and
    ``(K, T, rows, 128)`` payload delay rings (a packed buffer with a
    T-slot time dim at axis 1; CD-Adam staleness/overlap) their row dim
    likewise; non-buffer leaves (the scalar count, batch stacks, scale
    rings) stay replicated over it."""
    def one(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) > worker_dim and shape[worker_dim] == K:
            entries = [None] * worker_dim + [axis_name]
            if model_axis is not None and worker_dim == 0:
                if _pack.is_packed_buffer_shape(shape, K):
                    entries.append(model_axis)
                elif (len(shape) == 4 and _pack.is_packed_buffer_shape(
                        (shape[0],) + shape[2:], K)):
                    entries.extend([None, model_axis])
            return P(*entries)
        return P()
    return jax.tree_util.tree_map(one, tree)


def shard_over_workers(tree: PyTree, mesh: Any, K: int, axis_name: str,
                       model_axis: Optional[str] = None) -> PyTree:
    """device_put every leaf with its worker dim on the mesh axis (and,
    for packed buffers on a 2D mesh, the row dim on ``model_axis``)."""
    specs = worker_pspec_tree(tree, K, axis_name, model_axis=model_axis)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs)
    return jax.device_put(tree, shardings)


def _with_axis_execution(opt: "DecentralizedOptimizer", mesh: Any,
                         axis_name: str) -> "DecentralizedOptimizer":
    """Install comm='axis' execution: ``init`` shards the state over the
    worker mesh axis; ``step`` / ``round`` run the unmodified core step
    per-shard inside shard_map (one worker per slot of ``axis_name``), so
    worker shifts lower to ppermute and — for the pallas backend — the
    fused kernels consume each worker's (1, rows, 128) resident shard.

    With ``cfg.model_parallel`` = M > 1 the shard_map runs over the full
    2D (worker × model) mesh: packed buffers go ``P(worker, model)`` (one
    (1, rows/M, 128) block per device, the row-sharded pack layout), the
    scalar count and batch stacks replicate over 'model', and the core
    step's worker shifts still cross only the worker axis."""
    K = opt.K
    if mesh is None:
        raise ValueError("comm='axis' needs mesh= (a jax Mesh with a "
                         f"{axis_name!r} axis of size K)")
    if axis_name not in mesh.shape or mesh.shape[axis_name] != K:
        raise ValueError(
            f"comm='axis' needs mesh axis {axis_name!r} of size K={K}; "
            f"mesh has {dict(mesh.shape)}")
    M = int(getattr(opt.cfg, "model_parallel", 1))
    model_axis = (getattr(opt.cfg, "model_axis_name", "model")
                  if M > 1 else None)
    if model_axis is not None and (model_axis not in mesh.shape
                                   or mesh.shape[model_axis] != M):
        raise ValueError(
            f"model_parallel={M} needs mesh axis {model_axis!r} of size "
            f"{M}; mesh has {dict(mesh.shape)}")
    if K > 1 and not opt.topo.offsets:
        # fail at construction, not at first step trace: axis gossip is
        # ppermute along the shift offsets and has no dense fallback
        raise ValueError(
            f"comm='axis' needs a shift-invariant topology; "
            f"{opt.topo.name!r} has no shift structure (use comm='stacked' "
            "for dense-mixing graphs)")
    base_init, base_step, base_round = opt.init, opt.step, opt.round

    def init(params: PyTree) -> Any:
        # built straight onto the worker axis, so no device ever holds all
        # K workers' state; on a 2D mesh each worker's rows are then split
        # over the model axis by a reshard (compiling that split into the
        # init itself takes minutes)
        specs = worker_pspec_tree(jax.eval_shape(base_init, params), K,
                                  axis_name)
        state = jax.jit(base_init, out_shardings=jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs))(params)
        return shard_over_workers(state, mesh, K, axis_name,
                                  model_axis=model_axis)

    def step(state: Any, grads: PyTree) -> Any:
        state_specs = worker_pspec_tree(state, K, axis_name,
                                        model_axis=model_axis)
        return jax.shard_map(
            base_step, mesh=mesh,
            in_specs=(state_specs,
                      worker_pspec_tree(grads, K, axis_name,
                                        model_axis=model_axis)),
            out_specs=state_specs, check_vma=False)(state, grads)

    def round_(state: Any, grad_fn: Callable, batches: Any) -> Any:
        state_specs = worker_pspec_tree(state, K, axis_name,
                                        model_axis=model_axis)
        return jax.shard_map(
            lambda s, b: base_round(s, grad_fn, b), mesh=mesh,
            in_specs=(state_specs,
                      worker_pspec_tree(batches, K, axis_name,
                                        worker_dim=1)),
            out_specs=state_specs, check_vma=False)(state, batches)

    sharded_vag = None
    if model_axis is not None:
        # The 2D grad-pipeline hook: run a local value-and-grad over each
        # device's (1, rows/M, 128) row-shard block of the resident
        # parameter buffer, inside the SAME 2D shard_map the step uses.
        # ``local_vag(buf_local, batch_local) -> (losses (1,), gbuf_local)``
        # is traced with both mesh axes bound, so the loss psums over the
        # model axis explicitly and the returned grads buffer comes out
        # sharded exactly like the state — no resharding between the grad
        # shard_map and the step shard_map, and no collective the loss
        # does not spell out (the zero-all-gather property
        # tests/test_grad_pipeline.py pins).
        def sharded_vag(local_vag: Callable, state: Any, batch: PyTree):
            buf_spec = P(axis_name, model_axis)
            batch_specs = worker_pspec_tree(batch, K, axis_name)
            return jax.shard_map(
                local_vag, mesh=mesh,
                in_specs=(buf_spec, batch_specs),
                out_specs=(P(axis_name), buf_spec),
                check_vma=False)(state.buf, batch)

    return dataclasses.replace(
        opt, init=init, step=step,
        round=round_ if base_round is not None else None, mesh=mesh,
        sharded_value_and_grad=sharded_vag)


@dataclasses.dataclass(frozen=True)
class DecentralizedOptimizer:
    name: str
    topo: "Topology | TopologySchedule"
    cfg: Any
    compressor: Optional[Compressor]
    init: Callable[[PyTree], Any]
    step: Callable[[Any, PyTree], Any]
    round: Callable[[Any, Callable, Any], Any]
    params_of: Callable[[Any], PyTree]
    mesh: Any = None              # set when comm='axis': the worker mesh
    # set on 2D (worker x model) meshes: run a local value-and-grad over
    # each device's row-shard block inside the 2D shard_map (the grad
    # pipeline's sharded-packed mode; see train/grad.py)
    sharded_value_and_grad: Any = None
    # re-run make_optimizer with this optimizer's full kwargs plus
    # overrides (rebuild(eta=...) is the damping lr-decay hook; None on
    # hand-assembled optimizers that bypassed the factory)
    rebuild: Any = None

    @property
    def K(self) -> int:
        return self.topo.K

    def _bytes_for_degree(self, deg, per_worker: PyTree):
        """Wire bytes one worker sends in a round of gossip degree
        ``deg`` (the payload model ``comm_bytes_per_round`` uses)."""
        from repro.core.compression import tree_dense_bytes, tree_wire_bytes

        if self.compressor is None:
            return deg * tree_dense_bytes(per_worker)
        if getattr(self.cfg, "scales", "leaf") == "worker":
            # whole-buffer compression: int8 sign payload per element plus
            # ONE f32 scale per worker (instead of one per leaf)
            n = sum(x.size for x in jax.tree_util.tree_leaves(per_worker))
            return deg * (n + 4)
        return deg * tree_wire_bytes(self.compressor, per_worker)

    def _union_exchange(self) -> bool:
        """Whether a schedule exchanges over the UNION edge set every
        round: per-edge-state consumers (CD-Adam payloads, staleness /
        overlap delay buffers) must keep every edge's state aligned
        across the cycle."""
        return (self.compressor is not None
                or (getattr(self.cfg, "staleness", None) or 0) > 0
                or bool(getattr(self.cfg, "overlap", False)))

    def comm_bytes_per_round(self, params: PyTree) -> int:
        """Bytes each worker sends per communication round (per the paper's
        'communication cost (MB)' x-axes). For a ``TopologySchedule``
        without per-edge state this is the CYCLE-AVERAGE; per-round
        accounting is :meth:`comm_bytes_round_list`."""
        # strip the stacked worker dim for per-worker accounting
        per_worker = jax.tree_util.tree_map(lambda x: x[0], params)
        # Degree = the number of peers each worker actually exchanges with.
        # The shift offsets only describe the roll lowering; when the
        # runtime mixes densely (mixing='dense', or a topology with no
        # shift structure) the offsets are empty/unused and the true degree
        # comes from the weight matrix's off-diagonal support.
        mixing = getattr(self.cfg, "mixing", "roll")
        if isinstance(self.topo, TopologySchedule):
            if self._union_exchange():
                deg = len(self.topo.union_offsets())
            else:
                deg = float(np.mean([len(e.offsets)
                                     for e in self.topo.entries]))
        elif self.topo.offsets and mixing != "dense":
            deg = len(self.topo.offsets)
        else:
            deg = len(self.topo.neighbors_of(0))
        return self._bytes_for_degree(deg, per_worker)

    def comm_bytes_round_list(self, params: PyTree) -> "list":
        """Per-round bytes across one schedule cycle: entry ``r % len``
        is what a worker sends in communication round ``r``. Static
        topologies return a single-entry list; schedules with per-edge
        state exchange over the union edge set every round, so theirs is
        uniform too. Plain D-Adam under a schedule gets the true
        per-entry degree — the accounting ``TrainLog.comm_mb`` sums."""
        per_worker = jax.tree_util.tree_map(lambda x: x[0], params)
        if isinstance(self.topo, TopologySchedule):
            if self._union_exchange():
                deg = len(self.topo.union_offsets())
                return [self._bytes_for_degree(deg, per_worker)]
            return [self._bytes_for_degree(len(e.offsets), per_worker)
                    for e in self.topo.entries]
        mixing = getattr(self.cfg, "mixing", "roll")
        if self.topo.offsets and mixing != "dense":
            deg = len(self.topo.offsets)
        else:
            deg = len(self.topo.neighbors_of(0))
        return [self._bytes_for_degree(deg, per_worker)]


def resolve_topology(topology: "str | Topology | TopologySchedule",
                     K: int) -> "Topology | TopologySchedule":
    """A string names either a static zoo graph (-> Topology) or a
    time-varying schedule family like ``one-peer-exp`` / ``rand-ring:6``
    (-> TopologySchedule); built instances pass through (K-checked)."""
    if isinstance(topology, (Topology, TopologySchedule)):
        if topology.K != K:
            raise ValueError(
                f"topology {topology.name!r} is over K={topology.K} "
                f"workers, optimizer has K={K}")
        return topology
    name = topology.partition(":")[0].replace("_", "-")
    if name in _sched._SCHEDULES:
        return make_schedule(topology, K)
    return make_topology(topology, K)


def make_optimizer(
    kind: str,
    K: int,
    *,
    topology: "str | Topology | TopologySchedule" = "ring",
    period: int = 1,
    eta: float = 1e-3,
    beta1: float = 0.9,
    beta2: float = 0.999,
    tau: float = 1e-6,
    weight_decay: float = 0.0,
    gamma: float = 0.4,
    compressor: str | Compressor = "sign",
    scales: str = "leaf",
    mixing: str = "roll",
    moment_dtype=None,
    backend: str = "reference",
    comm: str = "stacked",
    mesh: Any = None,
    axis_name: str = "worker",
    model_axis_name: str = "model",
    staleness: Optional[int] = None,
    straggler_rate: float = 0.0,
    straggler_seed: int = 0,
    overlap: bool = False,
    **comp_kw,
) -> DecentralizedOptimizer:
    """Build a decentralized optimizer over ``K`` workers.

    The single factory behind every entrypoint: picks the algorithm, the
    execution backend, and the communication lowering, validates the
    combination, and returns a :class:`DecentralizedOptimizer` whose
    ``init`` / ``step`` / ``round`` closures carry the whole config.

    Args:
      kind: ``"d-adam"`` (Alg. 1), ``"cd-adam"`` (Alg. 2, compressed
        gossip with error feedback), ``"d-adam-vanilla"`` (period forced
        to 1), or the baselines ``"d-psgd"`` / ``"adam"``.
      K: number of workers. Params enter ``opt.init`` stacked with a
        leading K dim on every leaf.
      topology: zoo name (``"ring"``, ``"torus"``, ``"exponential"``,
        ``"fully_connected"``), a schedule spec (``"one-peer-exp"``,
        ``"rand-ring:N"``), or a built ``Topology`` /
        ``TopologySchedule`` (K-checked).
      period: local steps per gossip round (the paper's p).
      eta, beta1, beta2, tau: Adam step size, moment decays, and the
        denominator floor epsilon (the paper writes it tau).
      weight_decay: decoupled (AdamW-style) weight decay.
      gamma: CD-Adam consensus step size (ignored by D-Adam).
      compressor: CD-Adam wire compressor — ``"sign"`` (the only one the
        pallas backend fuses), ``"topk"``, ``"qsgd"``, ... or a built
        ``Compressor``; ``**comp_kw`` is forwarded to its factory.
      scales: CD-Adam sign-scale granularity, ``"leaf"`` or ``"global"``.
      mixing: ``"roll"`` lowers gossip as per-offset shifts;
        ``"dense"`` as a mixing matmul (static graphs only).
      moment_dtype: storage dtype for the Adam moments (e.g.
        ``jnp.bfloat16``); ``None`` keeps the param dtype.
      backend: ``"reference"`` (pytree-of-leaves math, debuggable) or
        ``"pallas"`` (packed ``(K, rows, 128)`` resident state, fused
        kernels).
      comm: how "worker k reads worker (k+s) % K" lowers — ``"stacked"``
        rolls over the stacked dim on one device; ``"axis"`` ppermutes
        inside a ``shard_map`` over ``mesh``. Same math, pinned by the
        comm-parity tests.
      mesh: required for ``comm="axis"``; a model axis of size M > 1 on
        it (pallas only) row-shards the packed state M-ways per worker.
      axis_name, model_axis_name: mesh axis names.
      staleness: bounded-staleness gossip (tau rounds); with
        ``straggler_rate`` / ``straggler_seed`` modelling late payloads.
        Mutually exclusive with ``overlap``.
      overlap: delay-1 wire schedule — round r issues its payload and
        round r+1 mixes it, so the exchange overlaps the next local
        steps. For CD-Adam this is bitwise the ``staleness=1`` schedule
        with every payload late.
      **comp_kw: forwarded to the compressor factory (e.g. ``k=...``
        for topk).

    Returns:
      A :class:`DecentralizedOptimizer`; use ``opt.init(params)``,
      ``opt.step(state, grads)``, ``opt.params_of(state)``.

    Raises:
      ValueError: for inconsistent combinations — e.g. ``scales`` on a
        non-CD-Adam kind, ``mixing="dense"`` with a schedule or with
        ``overlap``, a non-sign compressor under ``backend="pallas"``,
        ``staleness`` together with ``overlap``.
      KeyError: unknown topology or kind name.

    Example:
      >>> import jax, jax.numpy as jnp
      >>> from repro.core import make_optimizer
      >>> opt = make_optimizer("d-adam", K=4, eta=1e-2, period=2,
      ...                      topology="ring")
      >>> params = {"w": jnp.ones((4, 8, 2))}   # leading K dim
      >>> state = opt.init(params)
      >>> grads = jax.tree_util.tree_map(jnp.ones_like, params)
      >>> state = opt.step(state, grads)
      >>> opt.params_of(state)["w"].shape
      (4, 8, 2)
    """
    # capture the full factory call before any normalization, so
    # opt.rebuild(**overrides) reproduces THIS optimizer with a few knobs
    # turned (the damping lr-decay hook rebuilds with a smaller eta)
    factory_kwargs: Dict[str, Any] = dict(
        kind=kind, K=K, topology=topology, period=period, eta=eta,
        beta1=beta1, beta2=beta2, tau=tau, weight_decay=weight_decay,
        gamma=gamma, compressor=compressor, scales=scales, mixing=mixing,
        moment_dtype=moment_dtype, backend=backend, comm=comm, mesh=mesh,
        axis_name=axis_name, model_axis_name=model_axis_name,
        staleness=staleness, straggler_rate=straggler_rate,
        straggler_seed=straggler_seed, overlap=overlap, **comp_kw)
    topo = resolve_topology(topology, K)
    kind = kind.lower().replace("_", "-")
    if scales != "leaf" and kind not in ("cd-adam", "cdadam"):
        raise ValueError("scales= selects CD-Adam's compression-scale "
                         f"granularity; meaningless for {kind!r}")
    if isinstance(topo, TopologySchedule):
        if mixing == "dense":
            raise ValueError(
                "time-varying schedules lower per-entry rolls/ppermutes "
                "over their shift offsets; mixing='dense' has no "
                "round-indexed lowering (use mixing='roll')")
        if kind in ("d-psgd", "dpsgd"):
            raise ValueError(
                "d-psgd is the static-graph baseline; time-varying "
                "schedules are wired for d-adam / cd-adam")
    opt: Optional[DecentralizedOptimizer] = None

    # 2D (worker x model) execution is declared by the mesh itself: a
    # model axis of size M > 1 row-shards the packed state M-ways per
    # worker. Only the pallas backend has a row dim to shard — under
    # backend='reference' a model axis on the mesh keeps its pre-2D
    # meaning (state replicated over it; tensor sharding is the launch
    # layer's business), so detection is gated on the backend.
    model_parallel = 1
    if (comm == "axis" and backend == "pallas" and mesh is not None
            and hasattr(mesh, "shape")):
        model_parallel = int(dict(mesh.shape).get(model_axis_name, 1))

    if kind in ("d-adam", "dadam", "d-adam-vanilla"):
        if kind == "d-adam-vanilla":
            period = 1
        cfg = DAdamConfig(eta=eta, beta1=beta1, beta2=beta2, tau=tau,
                          period=period, weight_decay=weight_decay,
                          mixing=mixing, moment_dtype=moment_dtype,
                          backend=backend, comm=comm, axis_name=axis_name,
                          model_parallel=model_parallel,
                          model_axis_name=model_axis_name,
                          staleness=staleness,
                          straggler_rate=straggler_rate,
                          straggler_seed=straggler_seed,
                          overlap=overlap)
        cfg.validate()
        opt = DecentralizedOptimizer(
            name=kind, topo=topo, cfg=cfg, compressor=None,
            init=lambda p: dadam.init(p, cfg, topo),
            step=lambda s, g: dadam.step(s, g, topo, cfg),
            round=lambda s, fn, b: dadam.round_step(s, fn, b, topo, cfg),
            params_of=lambda s: s.params,
        )

    elif kind in ("cd-adam", "cdadam"):
        comp = (compressor if isinstance(compressor, Compressor)
                else make_compressor(compressor, **comp_kw))
        if backend == "pallas" and comp.name != "sign":
            raise ValueError(
                "backend='pallas' fuses the sign compressor; got "
                f"compressor={comp.name!r} (use backend='reference')")
        cfg = CDAdamConfig(eta=eta, beta1=beta1, beta2=beta2, tau=tau,
                           period=period, weight_decay=weight_decay,
                           gamma=gamma, mixing=mixing,
                           moment_dtype=moment_dtype, backend=backend,
                           comm=comm, axis_name=axis_name,
                           model_parallel=model_parallel,
                           model_axis_name=model_axis_name,
                           scales=scales, staleness=staleness,
                           straggler_rate=straggler_rate,
                           straggler_seed=straggler_seed,
                           overlap=overlap)
        cfg.validate()
        opt = DecentralizedOptimizer(
            name=kind, topo=topo, cfg=cfg, compressor=comp,
            init=lambda p: cdadam.init(p, cfg, topo, comp),
            step=lambda s, g: cdadam.step(s, g, topo, cfg, comp),
            round=lambda s, fn, b: cdadam.round_step(s, fn, b, topo, cfg,
                                                     comp),
            params_of=lambda s: s.params,
        )

    elif kind in ("d-psgd", "dpsgd"):
        if overlap:
            raise ValueError("overlap is wired for d-adam / cd-adam")
        if backend != "reference":
            raise ValueError("d-psgd has no kernel backend; "
                             "use backend='reference'")
        if comm != "stacked":
            raise ValueError("d-psgd only implements comm='stacked'")
        cfg = baselines.DPSGDConfig(eta=eta, weight_decay=weight_decay,
                                    period=period, mixing=mixing)
        opt = DecentralizedOptimizer(
            name=kind, topo=topo, cfg=cfg, compressor=None,
            init=lambda p: baselines.dpsgd_init(p, cfg),
            step=lambda s, g: baselines.dpsgd_step(s, g, topo, cfg),
            round=None,  # type: ignore[arg-type]
            params_of=lambda s: s.params,
        )

    if opt is None:
        raise KeyError(f"unknown optimizer kind {kind!r}")
    if getattr(opt.cfg, "comm", "stacked") == "axis":
        opt = _with_axis_execution(opt, mesh, axis_name)
    elif mesh is not None:
        raise ValueError("mesh= is only meaningful with comm='axis'")
    return dataclasses.replace(
        opt, rebuild=lambda **ov: make_optimizer(
            **{**factory_kwargs, **ov}))
