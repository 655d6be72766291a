"""Decentralized training loop.

Couples a per-worker loss function to a DecentralizedOptimizer: stacks K
parameter replicas, vmaps per-worker gradients, jits one step (with the
in-graph communication-skip cond), tracks loss / consensus / communication
cost. Works for any model in the registry and for the paper's own DeepFM /
Wide&Deep / ResNet20 models.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import DecentralizedOptimizer
from repro.core.api import shard_over_workers
from repro.core.dadam import consensus_error, mean_params
from repro.train import damping as damping_mod
from repro.train.damping import DampingConfig, DampingState
from repro.train.grad import make_grad_pipeline

PyTree = Any


def stack_params(params: PyTree, K: int, *, same_init: bool = True,
                 key: Optional[jax.Array] = None,
                 init_fn: Optional[Callable] = None) -> PyTree:
    """Replicate (or independently re-draw) params across the worker dim."""
    if same_init or init_fn is None:
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (K,) + x.shape).copy(), params)
    if key is None:
        raise ValueError(
            "stack_params(same_init=False, init_fn=...) draws K "
            "independent inits and needs key= (a jax PRNG key) to split "
            "across workers")
    keys = jax.random.split(key, K)
    per = [init_fn(k) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)


@dataclasses.dataclass
class TrainLog:
    """Training log. The list fields are one entry per log point; the
    ``*_total`` scalars are cumulative counters carried ACROSS ``fit``
    calls — pass the same log back in (``trainer.fit(..., log=log)``)
    and steps, comm volume, wall time, and gradient-evaluation counts
    resume from where the previous call left off instead of restarting
    at zero (the streaming / damping / elastic-resize use case)."""

    step: List[int] = dataclasses.field(default_factory=list)
    loss: List[float] = dataclasses.field(default_factory=list)
    consensus: List[float] = dataclasses.field(default_factory=list)
    comm_mb: List[float] = dataclasses.field(default_factory=list)
    wall_s: List[float] = dataclasses.field(default_factory=list)
    # cumulative worker-chunk gradient evaluations (the serverless
    # billing unit adaptive batch damping economizes; see train.damping)
    grad_evals: List[int] = dataclasses.field(default_factory=list)
    # cumulative counters resumed by the next fit(log=...) call
    steps_total: int = 0
    comm_rounds_total: int = 0
    comm_mb_total: float = 0.0
    wall_s_total: float = 0.0
    grad_evals_total: int = 0

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class DecentralizedTrainer:
    """Stacked-K decentralized trainer.

    loss_fn(params, batch) -> scalar, evaluated per worker; the batch
    carries a leading K dim on every leaf. Gradients are produced by the
    grad pipeline (``train.grad.make_grad_pipeline``): the reference vmap
    path for pytree states, the differentiate-through-``packing.unpack``
    path for packed-resident states (grads arrive packed, zero explicit
    pack/unpack in the step), or — on a 2D worker × model mesh with a
    ``sharded_loss`` — the model-parallel path that evaluates the loss
    inside the shard_map directly from each device's local
    (1, rows/M, 128) row-shard block, with no full-parameter all-gather.
    ``microbatch`` > 1 turns on gradient accumulation in every mode.

    With a comm='axis' optimizer (``make_optimizer(comm='axis', mesh=...)``)
    the state lives sharded over the worker mesh axis: ``opt.init`` places
    it there, the jitted step's shard_map keeps it there, and ``fit``
    device_puts each batch's worker dim onto the axis so the per-worker
    grads are computed where the state shard lives. On a 2D mesh the batch
    replicates over the 'model' axis (every device of a worker's model
    group sees the worker's whole microbatch). Without a ``sharded_loss``
    the 2D grad path falls back to GSPMD through the row-sharded unpack —
    pass ``plan`` (``launch.shardings.make_plan(mode='axis')``) to thread
    its head-aware ``param_pspec`` rules into that loss as sharding
    constraints.

    Args (constructor):
      loss_fn: per-worker scalar loss ``(params, batch) -> float``;
        sees ONE worker's params and batch (no K dim) — the pipeline
        vmaps / shard_maps it.
      opt: a ``DecentralizedOptimizer`` from ``make_optimizer``.
      microbatch: > 1 turns on gradient accumulation (the batch's
        per-worker dim is split into this many chunks).
      sharded_loss: model-parallel loss over local row shards (2D mesh
        only; see ``make_grad_pipeline``).
      plan: ``launch.shardings.ShardingPlan`` for the 2D GSPMD fallback.
      recompile_limit: arm the JXL003 recompile gate — ``fit`` raises
        once the jitted step has compiled for more than this many
        distinct abstract signatures (elastic resizes and lr-decay
        rebinds excluded).
      damping: adaptive batch damping — a ``train.damping.DampingConfig``
        or a spec string (``'adadamp:8'``, ``'geodamp:8:2:50'``; see
        ``train.damping.make_damping``). The grad pipeline then scans
        over ``max_chunks`` fixed-shape accumulation chunks and masks
        the tail past the policy's current per-worker count, so ONE
        compiled step serves every damping level; the damping state
        (loss EMA, level, eval counter) threads through the jitted step.
        Mutually exclusive with ``microbatch`` > 1. Once every worker
        sits at ``max_chunks``, ``lr_decay``/``lr_decay_every`` decay
        eta via ``opt.rebuild`` (one legitimate recompile per decay,
        like an elastic resize).
      donate: hand the optimizer state's buffers to the jitted step
        (``donate_argnums``), so its output reuses them instead of
        holding a second copy of params and moments during the step —
        what lets a full-width model fit one chip. The state passed to
        ``fit`` must then not be used again; ``fit`` returns the live one.

    Example:
      >>> import jax.numpy as jnp
      >>> from repro.core import make_optimizer
      >>> from repro.train.loop import DecentralizedTrainer
      >>> def loss(p, b):                    # ONE worker's view
      ...     return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)
      >>> opt = make_optimizer("d-adam", K=2, eta=1e-2)
      >>> tr = DecentralizedTrainer(loss, opt)
      >>> state = tr.init({"w": jnp.zeros((3, 1))})  # stacked to K inside
      >>> def batches():
      ...     while True:                    # leading K dim on each leaf
      ...         yield {"x": jnp.ones((2, 4, 3)), "y": jnp.ones((2, 4, 1))}
      >>> state, log = tr.fit(state, batches(), steps=3)
      >>> opt.params_of(state)["w"].shape
      (2, 3, 1)
      >>> len(log.loss)                      # logged on the final step
      1
    """

    def __init__(self, loss_fn: Callable[[PyTree, PyTree], jax.Array],
                 opt: DecentralizedOptimizer, *, microbatch: int = 1,
                 sharded_loss: Optional[Callable] = None,
                 plan: Any = None, recompile_limit: Optional[int] = None,
                 damping: "None | str | DampingConfig" = None,
                 donate: bool = False):
        self.loss_fn = loss_fn
        self._donate = donate
        self._microbatch = microbatch
        self._sharded_loss = sharded_loss
        self._plan = plan
        self._recompile_limit = recompile_limit
        self._damping = damping_mod.make_damping(damping)
        if self._damping is not None and microbatch > 1:
            raise ValueError(
                "damping owns the accumulation loop (max_chunks IS the "
                "chunk count); pass damping= OR microbatch=, not both")
        self.damp_state: Optional[DampingState] = None
        self._lr_decays = 0
        self.recompile_watch = None
        self._build(opt)

    def _build(self, opt: DecentralizedOptimizer) -> None:
        """(Re)bind the trainer to an optimizer: rebuild the grad pipeline
        and the jitted step. Called once at construction and again on each
        elastic membership change (``resize``) or damping lr decay."""
        self.opt = opt
        dcfg = self._damping
        self.pipeline = make_grad_pipeline(
            self.loss_fn, opt, microbatch=self._microbatch,
            sharded_loss=self._sharded_loss, plan=self._plan,
            damping_chunks=dcfg.max_chunks if dcfg is not None else 0)
        # per-round comm bytes are rebind-dependent (schedule entries,
        # elastic K): recompute lazily against the bound optimizer
        self._mb_rounds: Optional[List[float]] = None

        if dcfg is not None:
            if self.damp_state is None:
                self.damp_state = damping_mod.init_damping(dcfg, opt.K)

            def step(state, dstate, batch):
                n = damping_mod.chunks_of(dstate, dcfg, self.opt.K)
                with jax.named_scope("grad"):
                    losses, grads = self.pipeline.value_and_grad(
                        state, batch, n)
                    loss = jnp.mean(losses)
                with jax.named_scope("opt"):
                    new_state = self.opt.step(state, grads)
                # the damping signal updates OUTSIDE the comm shard_maps,
                # from the global (K,) per-worker losses — stacked and
                # axis comm modes see the identical EMA
                with jax.named_scope("damping"):
                    new_dstate = damping_mod.update(dstate, losses, dcfg)
                return new_state, new_dstate, loss
        else:
            def step(state, batch):
                with jax.named_scope("grad"):
                    losses, grads = self.pipeline.value_and_grad(state,
                                                                 batch)
                    loss = jnp.mean(losses)
                with jax.named_scope("opt"):
                    new_state = self.opt.step(state, grads)
                return new_state, loss

        self._step = jax.jit(step, donate_argnums=(0,) if self._donate
                             else ())
        if self._recompile_limit is not None:
            # JXL003 gate: every fit() call's abstract signature is hashed;
            # exceeding the limit raises. Built fresh here so an elastic
            # resize or damping lr decay (one legitimate recompile per
            # membership change / decay event) does not count against the
            # budget — damping LEVEL changes reuse the cache and do.
            from repro.analysis.jaxpr_lint import RecompileWatch
            self.recompile_watch = RecompileWatch(
                "trainer.step", limit=self._recompile_limit)

    def lower_step(self, state: Any, batch: PyTree) -> Any:
        """``jax.jit(step).lower`` for one (state, batch): the program
        ``fit`` runs, for inspection (``.compile().as_text()``, memory
        analysis). Nothing executes and ``state`` is not consumed."""
        batch = self._place_batch(batch)
        if self._damping is not None:
            return self._step.lower(state, self.damp_state, batch)
        return self._step.lower(state, batch)

    def init(self, params: PyTree) -> Any:
        stacked = stack_params(params, self.opt.K)
        return self.opt.init(stacked)

    def resize(self, state: Any, new_opt: DecentralizedOptimizer, *,
               strategy: str = "clone") -> Any:
        """Elastic membership change: carry ``state`` over to ``new_opt``
        (built for the new K / topology) and rebind the trainer to it.

        Exactly ONE recompile per membership change: the jitted step is
        rebuilt here, and subsequent ``fit`` steps at the new K reuse its
        cache. Params and Adam moments survive per ``strategy`` ("clone"
        bootstraps joiners from live workers round-robin, "mean" from the
        consensus mean); hats and straggler buffers restart cold."""
        from repro.core.elastic import resize_state
        new_state = resize_state(state, new_opt, strategy=strategy)
        if self._damping is not None and self.damp_state is not None:
            # per-worker damping signals follow the membership change
            # (joiners inherit signals round-robin); the eval counter and
            # ceiling clock carry through
            self.damp_state = damping_mod.resize_damp(
                self.damp_state, self._damping, new_opt.K)
        self._build(new_opt)
        return new_state

    def _place_batch(self, batch: PyTree) -> PyTree:
        """comm='axis': ship each leaf's worker dim onto the worker mesh
        axis (no-op for stacked-comm optimizers). On a 2D mesh the batch
        deliberately replicates over the model axis — data parallelism
        stays between workers, tensor parallelism within them."""
        if self.opt.mesh is None:
            return batch
        return shard_over_workers(batch, self.opt.mesh, self.opt.K,
                                  getattr(self.opt.cfg, "axis_name",
                                          "worker"))

    def comm_mb_per_round(self, state) -> float:
        return self.opt.comm_bytes_per_round(
            self.opt.params_of(state)) / 1e6

    def _round_mb(self, state, round_index: int) -> float:
        """MB this worker sends in communication round ``round_index``
        (cumulative across resumed fits). Recomputed on every rebind —
        an elastic resize changes K and per-worker bytes, a
        TopologySchedule changes the per-entry degree round to round."""
        if self._mb_rounds is None:
            params = self.opt.params_of(state)
            self._mb_rounds = [
                b / 1e6 for b in self.opt.comm_bytes_round_list(params)]
        return self._mb_rounds[round_index % len(self._mb_rounds)]

    def _maybe_decay_lr(self) -> None:
        """Damping's hand-off back to the step size: once every worker
        sits at ``max_chunks``, decay eta by ``lr_decay`` per
        ``lr_decay_every`` steps spent at the ceiling. Checked at log
        boundaries (one host sync per check, not per step); each decay
        rebinds via ``opt.rebuild`` — one legitimate recompile, like an
        elastic resize."""
        dcfg = self._damping
        if (dcfg is None or not dcfg.lr_decay_every
                or getattr(self.opt, "rebuild", None) is None):
            return
        due = int(self.damp_state.at_max) // dcfg.lr_decay_every
        if due > self._lr_decays:
            factor = dcfg.lr_decay ** (due - self._lr_decays)
            self._lr_decays = due
            self._build(self.opt.rebuild(
                eta=float(self.opt.cfg.eta) * factor))

    def fit(self, state, batch_iter: Iterator[PyTree], steps: int, *,
            log_every: int = 50, log: Optional[TrainLog] = None,
            hook: Optional[Callable[[int, Any], None]] = None,
            hook_every: int = 0) -> Tuple[Any, TrainLog]:
        """Run ``steps`` optimizer steps, logging every ``log_every``.

        Pass the previous call's ``log`` back in to CONTINUE it: the
        cumulative ``*_total`` counters on :class:`TrainLog` make
        ``log.step`` / ``log.comm_mb`` / ``log.wall_s`` resume instead of
        restarting at zero, and under a ``TopologySchedule`` the
        schedule-entry round index stays aligned across calls (a fresh
        log restarts the entry accounting at the cycle head).

        ``hook(global_step, state)`` is called every ``hook_every`` steps
        (cumulative step count, aligned with ``log.step``) — the online
        train→serve publish point (``train.online`` installs a
        ``ParamStore`` publish here). The hook runs on the host between
        jitted steps: it must not mutate ``state``, and anything it
        launches (a ``device_put``, an unpack-once publish) is async, so
        training does not stall on it.

        Each iteration is a profiler step (``StepTraceAnnotation("train")``)
        holding the host spans ``fit.batch``, ``fit.watch``,
        ``fit.dispatch``, ``fit.hook`` and ``fit.log`` (see
        ``repro.tracing``)."""
        log = log or TrainLog()
        comm_rounds = log.comm_rounds_total
        comm_mb = log.comm_mb_total
        step0 = log.steps_total
        evals0_dev = (int(self.damp_state.evals)
                      if self._damping is not None else 0)
        evals_per_step = self.opt.K * self.pipeline.microbatch
        t0 = time.perf_counter()
        for t in range(steps):
            with jax.profiler.StepTraceAnnotation("train",
                                                  step_num=step0 + t + 1):
                with jax.profiler.TraceAnnotation("fit.batch"):
                    batch = self._place_batch(next(batch_iter))
                damp = () if self._damping is None else (self.damp_state,)
                if self.recompile_watch is not None:
                    with jax.profiler.TraceAnnotation("fit.watch"):
                        self.recompile_watch.observe(state, *damp, batch)
                        self.recompile_watch.check()
                with jax.profiler.TraceAnnotation("fit.dispatch"):
                    state, *damp, loss = self._step(state, *damp, batch)
                if damp:
                    self.damp_state, = damp
                if (step0 + t + 1) % self.opt.cfg.period == 0:
                    comm_mb += self._round_mb(state, comm_rounds)
                    comm_rounds += 1
                if hook is not None and hook_every > 0 \
                        and (t + 1) % hook_every == 0:
                    with jax.profiler.TraceAnnotation("fit.hook"):
                        hook(step0 + t + 1, state)
                if (t + 1) % log_every == 0 or t == steps - 1:
                    with jax.profiler.TraceAnnotation("fit.log"):
                        if self._damping is not None:
                            evals = (log.grad_evals_total
                                     + int(self.damp_state.evals)
                                     - evals0_dev)
                        else:
                            evals = (log.grad_evals_total
                                     + (t + 1) * evals_per_step)
                        log.step.append(step0 + t + 1)
                        log.loss.append(float(loss))
                        log.consensus.append(
                            float(consensus_error(self.opt.params_of(state))))
                        log.comm_mb.append(comm_mb)
                        log.wall_s.append(log.wall_s_total
                                          + time.perf_counter() - t0)
                        log.grad_evals.append(evals)
                        self._maybe_decay_lr()
        log.steps_total = step0 + steps
        log.comm_rounds_total = comm_rounds
        log.comm_mb_total = comm_mb
        log.wall_s_total += time.perf_counter() - t0
        if steps:
            if self._damping is not None:
                log.grad_evals_total += (int(self.damp_state.evals)
                                         - evals0_dev)
            else:
                log.grad_evals_total += steps * evals_per_step
        return state, log

    def averaged_params(self, state) -> PyTree:
        return mean_params(self.opt.params_of(state))
