"""The gradient pipeline: one dispatch for how per-worker gradients are
computed, shared by the trainer and the dry-run launcher.

``make_grad_pipeline(loss, opt, ...)`` inspects the optimizer's config and
returns a :class:`GradPipeline` in one of three modes:

* **reference** — pytree state: ``vmap(value_and_grad(loss))`` over the
  stacked worker dim, with optional microbatch gradient accumulation
  (a lax.scan that divides activation memory by the microbatch count).
* **packed** — packed-resident state (``backend='pallas'``): the stacked
  per-worker losses are differentiated THROUGH ``packing.unpack`` w.r.t.
  the resident ``(K, rows, 128)`` buffer, so AD's transpose deposits the
  grads straight into the buffer — grads arrive packed with zero explicit
  pack/unpack. On a 2D (worker × model) mesh a ``plan`` threads
  ``launch.shardings.make_plan(mode='axis')``'s head-aware ``param_pspec``
  rules into the loss as sharding constraints, so GSPMD keeps matmul
  operands ``P(..., 'model')`` instead of replicating whole leaves per
  worker.
* **sharded-packed** — the 2D mesh with an explicitly model-parallel loss:
  the loss is evaluated INSIDE the optimizer's 2D shard_map, directly from
  each device's local ``(1, rows/M, 128)`` row-shard block via
  ``packing.unpack_local``. No collective can appear that the loss does
  not spell out — the compiled step provably contains **no full-parameter
  all-gather**, only the neighbor gossip and whatever psums the loss
  performs over the model axis (``analysis.hlo.collective_summary`` is
  the regression instrument; see ``tests/test_grad_pipeline.py``).

A model-parallel loss has the signature ``sharded_loss(chunks, batch,
ctx)`` where ``chunks`` are this shard's flat per-leaf slices (spec leaf
order, padding slots kept), ``batch`` is this worker's batch (replicated
over the model axis) and ``ctx`` is a :class:`ShardCtx` carrying the pack
spec plus the model-axis helpers: ``ctx.psum`` for activations that tie
shards together, ``ctx.mirror`` to slice congruent full-shape data into
the chunk layout, ``row_parallel_dot`` for matmuls whose weight rows live
in the chunk, and ``ctx.full_leaf`` to assemble a *small* leaf (a bias, a
scale vector) via one psum. It must return the worker's full loss
(replicated across its model group).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import pack as packing
from repro.tracing import scoped

PyTree = Any


# ------------------------- per-worker value+grad ----------------------------


def make_worker_grad(loss: Callable[[PyTree, PyTree], jax.Array],
                     microbatch: int = 1) -> Callable[[PyTree, PyTree],
                                                      PyTree]:
    """Per-worker gradient function: plain ``jax.grad`` for microbatch=1,
    or a lax.scan of gradient-accumulation steps that divides activation
    memory by the microbatch count (EXPERIMENTS.md §Perf iteration 9)."""
    if microbatch <= 1:
        return jax.grad(loss)
    vag = make_worker_value_and_grad(loss, microbatch)

    def worker_grad(params: PyTree, batch: PyTree) -> PyTree:
        return vag(params, batch)[1]

    return worker_grad


def make_worker_value_and_grad(loss: Callable[[PyTree, PyTree], jax.Array],
                               microbatch: int = 1) -> Callable:
    """(loss, grads) per worker, averaging both over the microbatches."""
    if microbatch <= 1:
        return jax.value_and_grad(loss)

    def worker_vag(params: PyTree, batch: PyTree):
        micro = _split_micro(batch, microbatch, batch_dim=0)
        zeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)

        def body(carry, mb):
            lsum, acc = carry
            l, g = jax.value_and_grad(loss)(params, mb)
            acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype), acc, g)
            return (lsum + l, acc), ()

        (lsum, acc), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), micro)
        return lsum / microbatch, jax.tree_util.tree_map(
            lambda g: g / microbatch, acc)

    return worker_vag


def _split_micro(batch: PyTree, microbatch: int, batch_dim: int) -> PyTree:
    """Reshape every leaf's batch dim b into a leading scan dim:
    (..., b, ...) -> (microbatch, ..., b/microbatch, ...)."""
    def split(path, x):
        b = x.shape[batch_dim]
        if b % microbatch:
            divisors = [d for d in range(1, b + 1) if b % d == 0]
            nearest = min(divisors, key=lambda d: (abs(d - microbatch), -d))
            raise ValueError(
                f"batch leaf {jax.tree_util.keystr(path) or '<root>'}: "
                f"per-worker batch dim {b} is not divisible into "
                f"{microbatch} accumulation chunks (microbatch / damping "
                f"max_chunks); nearest valid count is {nearest}")
        shape = (x.shape[:batch_dim] + (microbatch, b // microbatch)
                 + x.shape[batch_dim + 1:])
        return jnp.moveaxis(x.reshape(shape), batch_dim, 0)

    return jax.tree_util.tree_map_with_path(split, batch)


# ------------------------------ shard context -------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_replicated(x: jax.Array, axis_name: str) -> jax.Array:
    """``lax.psum`` whose transpose assumes a REPLICATED cotangent — the
    invariant of a sharded loss, whose final scalar is identical on every
    shard of the model group.

    Under ``shard_map(check_vma=False)`` replication is untracked, so the
    transpose of a plain ``lax.psum`` is another psum: with the replicated
    cotangent of a loss that silently multiplies every gradient by the
    model-group size M. This wrapper's backward pass is the identity
    (each shard keeps its own cotangent), which is the correct adjoint for
    the replicated-loss pattern — it is what ``ShardCtx.psum`` uses, and
    what every sharded loss must reduce with."""
    return jax.lax.psum(x, axis_name)


def _psum_rep_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _psum_rep_bwd(axis_name, _, ct):
    return (ct,)


psum_replicated.defvjp(_psum_rep_fwd, _psum_rep_bwd)


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """What a model-parallel loss gets to know about its shard: the pack
    spec (leaf layout), the model mesh axis and its size. Built by the
    pipeline; only meaningful inside the 2D shard_map."""

    spec: packing.PackSpec
    axis_name: str           # the model mesh axis ('model')
    n_shards: int            # M

    @property
    def index(self) -> jax.Array:
        """This device's model-shard index (traced)."""
        return jax.lax.axis_index(self.axis_name)

    def psum(self, x: jax.Array) -> jax.Array:
        """Reduce over the model axis — the ONLY way shards may be tied
        together inside a sharded loss. Backward pass is the identity
        (see :func:`psum_replicated`); a raw ``lax.psum`` here would
        over-count every gradient by the model-group size."""
        return psum_replicated(x, self.axis_name)

    def mirror(self, tree: PyTree) -> PyTree:
        """Slice a congruent per-worker full-shape pytree (targets,
        anchors) into this shard's chunk layout — elementwise losses then
        work chunk-against-chunk with one final ``psum``."""
        return packing.mirror_local(tree, self.spec, self.index)

    def full_leaf(self, chunk: jax.Array, leaf_idx: int) -> jax.Array:
        """Assemble leaf ``leaf_idx``'s full per-worker value from this
        shard's chunk via ONE psum of the leaf's TRUE element count — for
        *small* leaves only (biases, norms, scales): the psum bytes are
        the leaf size, so using this on a big matrix would re-create the
        all-gather the pipeline exists to remove."""
        spec = self.spec
        sz = spec.sizes[leaf_idx]
        c = int(chunk.size)
        flat = chunk.reshape(-1)
        # each global element i lives on shard i // c at local offset
        # i % c; gather this shard's overlap with the true range and psum
        local = jnp.arange(sz) - self.index * c
        mine = (local >= 0) & (local < c)
        vals = jnp.where(mine, flat[jnp.clip(local, 0, c - 1)], 0)
        return self.psum(vals).reshape(spec.shapes[leaf_idx][1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _slice_replicated(x: jax.Array, rows_local: int, axis_name: str
                      ) -> jax.Array:
    """This shard's ``rows_local`` slice of a REPLICATED activation's last
    dim. Backward pass scatters the cotangent into the full width and
    psums it over the model axis, so the cotangent leaving this op is
    replicated again — the invariant :func:`psum_replicated`'s identity
    transpose relies on. With a raw ``dynamic_slice`` instead, stacking
    two row-parallel layers would feed a partial (slice-shaped) cotangent
    into the lower layer and silently zero most of its weight grads."""
    idx = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(x, idx * rows_local, rows_local,
                                        axis=x.ndim - 1)


def _slice_rep_fwd(x, rows_local, axis_name):
    return _slice_replicated(x, rows_local, axis_name), x.shape


def _slice_rep_bwd(rows_local, axis_name, x_shape, ct):
    idx = jax.lax.axis_index(axis_name)
    full = jnp.zeros(x_shape, ct.dtype)
    full = jax.lax.dynamic_update_slice_in_dim(full, ct, idx * rows_local,
                                               axis=len(x_shape) - 1)
    return (jax.lax.psum(full, axis_name),)


_slice_replicated.defvjp(_slice_rep_fwd, _slice_rep_bwd)


def row_parallel_dot(x: jax.Array, w_chunk: jax.Array, d_out: int,
                     ctx: ShardCtx) -> jax.Array:
    """``x @ W`` with W's rows living in this shard's flat chunk — the
    Megatron row-parallel linear over the packed layout.

    The chunk is a contiguous slice of the flattened (d_in, d_out) matrix;
    when the per-shard chunk is a whole number of rows (any power-of-two
    ``d_out`` up to the tile quantum, since chunks are multiples of
    BLOCK_ROWS*LANE elements) it reshapes to a (rows_local, d_out)
    operand — effectively ``P('model', None)`` — and the activation psums
    over the model axis. Padding rows are zero, so the columns of ``x``
    beyond d_in contribute nothing.

    ``x`` must be replicated over the model axis (a batch, or a previous
    layer's psum'd activation); the output is replicated again, so
    row-parallel layers COMPOSE — the input slice re-replicates its
    cotangent (one activation-sized psum in backward, mirroring the
    forward psum; see :func:`_slice_replicated`)."""
    c = int(w_chunk.size)
    if c % d_out:
        raise ValueError(
            f"chunk of {c} elements is not whole rows of d_out={d_out}; "
            "pick a d_out dividing the tile quantum "
            f"({packing.BLOCK_ROWS * packing.LANE} elements)")
    rows_local = c // d_out
    W = w_chunk.reshape(rows_local, d_out)
    rows_total = rows_local * ctx.n_shards
    d_in = x.shape[-1]
    if rows_total < d_in:
        raise ValueError(f"chunked rows {rows_total} < d_in {d_in}")
    pad = [(0, 0)] * (x.ndim - 1) + [(0, rows_total - d_in)]
    xl = _slice_replicated(jnp.pad(x, pad), rows_local, ctx.axis_name)
    return ctx.psum(xl @ W.astype(x.dtype))


# ------------------------------- the pipeline -------------------------------


@dataclasses.dataclass(frozen=True)
class GradPipeline:
    """A ``value_and_grad(state, batch) -> (losses (K,), grads)`` where
    ``grads`` is in the optimizer's native form: a stacked pytree
    (reference), a packed ``(K, rows, 128)`` buffer (packed), or a buffer
    sharded ``P('worker', 'model')`` (sharded-packed).

    With ``damping_chunks`` > 0 the signature grows a third argument:
    ``value_and_grad(state, batch, n)`` where ``n`` is a traced ``(K,)``
    int32 of per-worker live-chunk counts — the pipeline always scans
    over ``damping_chunks`` fixed-shape chunks and masks the tail beyond
    each worker's ``n[k]``, so every damping level shares ONE compiled
    program (see ``train.damping``)."""

    mode: str                 # 'reference' | 'packed' | 'sharded-packed'
    value_and_grad: Callable[..., Any]
    microbatch: int = 1
    damping_chunks: int = 0   # 0 = undamped 2-arg pipeline


def make_grad_pipeline(loss: Callable[[PyTree, PyTree], jax.Array],
                       opt: Any, *, microbatch: int = 1,
                       sharded_loss: Optional[Callable] = None,
                       plan: Any = None,
                       damping_chunks: int = 0) -> GradPipeline:
    """Build the gradient pipeline for ``opt`` (a DecentralizedOptimizer).

    Dispatch: ``backend='pallas'`` states are packed-resident → the
    differentiate-through-unpack path; with a 2D (worker × model) mesh AND
    a ``sharded_loss``, the loss instead runs model-parallel inside the
    shard_map on local row shards (no full-param all-gather). Everything
    else takes the reference vmap path. ``plan`` (a
    ``launch.shardings.ShardingPlan``) only affects the packed-GSPMD 2D
    fallback: the plan's ``param_pspec`` rules are applied to the unpacked
    leaves as sharding constraints.

    Args:
      loss: per-worker scalar loss ``(params, batch) -> float`` (no K
        dim on either argument; the pipeline adds the worker dim).
      opt: a ``DecentralizedOptimizer``; its config decides the mode.
      microbatch: gradient-accumulation chunks per step (>= 1).
      sharded_loss: ``(local_block, batch) -> scalar`` evaluated inside
        the shard_map on each device's ``(1, rows/M, 128)`` row shard;
        selects the ``'sharded-packed'`` mode on a 2D mesh.
      plan: sharding constraints for the 2D GSPMD fallback only.
      damping_chunks: > 0 builds the adaptive-batch-damping variant of
        the mode: a 3-arg ``value_and_grad(state, batch, n)`` that scans
        over this many fixed-shape chunks and masks chunks past each
        worker's traced live count ``n[k]`` (``train.damping``). One
        compiled program serves every damping level. Mutually exclusive
        with ``microbatch`` > 1 (damping owns the accumulation loop).

    Returns:
      A :class:`GradPipeline` — ``mode`` in ``('reference', 'packed',
      'sharded-packed')`` and ``value_and_grad(state, batch) ->
      (losses (K,), grads)`` with ``grads`` in the optimizer's native
      form (stacked pytree / packed buffer / sharded packed buffer).

    Raises:
      ValueError: ``microbatch < 1``, or ``sharded_loss`` given without
        a 2D comm='axis' optimizer to host it.

    Example:
      >>> import jax.numpy as jnp
      >>> from repro.core import make_optimizer
      >>> from repro.train.grad import make_grad_pipeline
      >>> opt = make_optimizer("d-adam", K=2, eta=1e-2)
      >>> pipe = make_grad_pipeline(
      ...     lambda p, b: jnp.mean((p["w"] - b) ** 2), opt)
      >>> pipe.mode
      'reference'
      >>> losses, grads = pipe.value_and_grad(
      ...     opt.init({"w": jnp.zeros((2, 3))}), jnp.ones((2, 3)))
      >>> losses.shape, grads["w"].shape
      ((2,), (2, 3))
    """
    cfg = opt.cfg
    packed = getattr(cfg, "backend", "reference") == "pallas"
    M = int(getattr(cfg, "model_parallel", 1))
    # every mode below differentiates these, so their ops carry the
    # 'model' scope forward and (as transpose(jvp(model))) backward
    loss = scoped("model")(loss)
    if sharded_loss is not None:
        sharded_loss = scoped("model")(sharded_loss)
    if microbatch < 1:
        raise ValueError(f"microbatch must be >= 1, got {microbatch}")
    if damping_chunks:
        if damping_chunks < 1:
            raise ValueError(
                f"damping_chunks must be >= 1, got {damping_chunks}")
        if microbatch > 1:
            raise ValueError(
                "damping owns the accumulation loop (its max_chunks IS "
                "the chunk count); microbatch > 1 alongside "
                "damping_chunks is ambiguous — set one, not both")

    if packed and M > 1 and sharded_loss is not None:
        if opt.sharded_value_and_grad is None:
            raise ValueError(
                "sharded_loss needs a 2D comm='axis' optimizer (mesh with "
                "a 'model' axis); this one has no sharded execution hook")
        if damping_chunks:
            vag = _sharded_packed_damped_vag(sharded_loss, opt,
                                             damping_chunks)
            return GradPipeline("sharded-packed", vag, 1, damping_chunks)
        vag = _sharded_packed_vag(sharded_loss, opt, microbatch)
        return GradPipeline("sharded-packed", vag, microbatch)
    if packed:
        if damping_chunks:
            vag = _packed_damped_vag(loss, opt, damping_chunks, plan)
            return GradPipeline("packed", vag, 1, damping_chunks)
        vag = _packed_vag(loss, opt, microbatch, plan)
        return GradPipeline("packed", vag, microbatch)
    if damping_chunks:
        worker_vag = _damped_worker_vag(loss, damping_chunks)

        def reference_damped_vag(state, batch, n):
            return jax.vmap(worker_vag)(opt.params_of(state), batch, n)

        return GradPipeline("reference", reference_damped_vag, 1,
                            damping_chunks)
    worker_vag = make_worker_value_and_grad(loss, microbatch)

    def reference_vag(state, batch):
        return jax.vmap(worker_vag)(opt.params_of(state), batch)

    return GradPipeline("reference", reference_vag, microbatch)


def _loss_constraints(plan: Any, tree: PyTree) -> PyTree:
    """Thread the plan's head-aware ``param_pspec`` rules into the loss
    (lazy import: the launch layer depends on configs the core trainer
    users may not touch)."""
    from repro.launch.shardings import loss_param_constraints

    return loss_param_constraints(plan, tree)


def _packed_one(loss, spec: packing.PackSpec, plan: Any):
    """``(buf, batch) -> (losses (K,), grads buffer)``: the stacked
    per-worker losses differentiated through ``packing.unpack`` w.r.t.
    the resident buffer. The workers run under ``packing.worker_map``, so
    a lookup into a narrow leaf reads the buffer's own rows."""

    def one(buf, b):
        def stacked_loss(bf):
            params = packing.unpack(bf, spec)
            if plan is not None:
                params = _loss_constraints(plan, params)
            losses = packing.worker_map(loss, bf, spec, params, b)
            return jnp.sum(losses), losses

        (_, losses), g = jax.value_and_grad(
            stacked_loss, has_aux=True)(buf)
        return losses, g

    return one


def _packed_vag(loss, opt, microbatch: int, plan: Any):
    """Differentiate-through-unpack, w.r.t. the resident buffer."""

    def vag(state, batch):
        one = _packed_one(loss, state.spec, plan)
        if microbatch <= 1:
            return one(state.buf, batch)
        micro = _split_micro(batch, microbatch, batch_dim=1)
        K = state.buf.shape[0]

        def body(carry, mb):
            lsum, acc = carry
            losses, g = one(state.buf, mb)
            return (lsum + losses, acc + g), ()

        init = (jnp.zeros((K,)), jnp.zeros_like(state.buf))
        (lsum, acc), _ = jax.lax.scan(body, init, micro)
        return lsum / microbatch, acc / microbatch

    return vag


# --------------------- adaptive-batch-damped variants ------------------------
#
# Same three modes, scanning over ``C = damping_chunks`` FIXED-shape
# chunks with a mask ``i < n[k]`` on each worker's contribution — the
# chunk count is a traced int, the shapes are static, so one compiled
# program serves every damping level. Masking is ``jnp.where`` (not a
# multiply) so a NaN in an unused chunk's loss/grads cannot poison the
# sum through ``0 * nan``; loss and grads divide by the LIVE count.


def _damped_worker_vag(loss, C: int):
    """Per-worker damped value+grad: ``(params, batch, n_k) ->
    (loss, grads)`` averaged over the first ``n_k`` of ``C`` chunks."""

    def worker_vag(params: PyTree, batch: PyTree, n_k: jax.Array):
        micro = _split_micro(batch, C, batch_dim=0)
        zeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)

        def body(carry, xs):
            mb, i = xs
            lsum, acc = carry
            l, g = jax.value_and_grad(loss)(params, mb)
            use = i < n_k
            acc = jax.tree_util.tree_map(
                lambda a, b: a + jnp.where(use, b.astype(a.dtype), 0), acc,
                g)
            return (lsum + jnp.where(use, l, 0.0), acc), ()

        (lsum, acc), _ = jax.lax.scan(body, (jnp.zeros(()), zeros),
                                      (micro, jnp.arange(C)))
        nf = n_k.astype(jnp.float32)
        return lsum / nf, jax.tree_util.tree_map(lambda g: g / nf, acc)

    return worker_vag


def _packed_damped_vag(loss, opt, C: int, plan: Any):
    """Damped differentiate-through-unpack: the per-worker mask
    ``i < n (K,)`` zeroes whole workers' chunk contributions."""

    def vag(state, batch, n):
        one = _packed_one(loss, state.spec, plan)
        micro = _split_micro(batch, C, batch_dim=1)
        K = state.buf.shape[0]

        def body(carry, xs):
            mb, i = xs
            lsum, acc = carry
            losses, g = one(state.buf, mb)
            use = i < n  # (K,) bool
            losses = jnp.where(use, losses, 0.0)
            g = jnp.where(use[:, None, None], g, 0.0)
            return (lsum + losses, acc + g), ()

        init = (jnp.zeros((K,)), jnp.zeros_like(state.buf))
        (lsum, acc), _ = jax.lax.scan(body, init,
                                      (micro, jnp.arange(C)))
        nf = n.astype(jnp.float32)
        return lsum / nf, acc / nf[:, None, None]

    return vag


def _sharded_packed_damped_vag(sharded_loss, opt, C: int):
    """Damped model-parallel path. The per-worker count ``n (K,)`` rides
    INTO the 2D shard_map as part of the batch argument —
    ``worker_pspec_tree`` gives any leading-K leaf ``P('worker')``, so
    each worker's shard sees its own ``(1,)`` slice. The mask lives
    inside the shard_map; no new collectives, the zero-all-gather
    property is untouched (``analysis.check``'s 'damping' variant pins
    it)."""
    cfg = opt.cfg
    ctx_axis = cfg.model_axis_name
    M = int(cfg.model_parallel)

    def vag(state, batch, n):
        spec = state.spec
        ctx = ShardCtx(spec=spec, axis_name=ctx_axis, n_shards=M)

        def local_vag(buf_local, batch_n):
            batch_local, n_local = batch_n
            n_k = n_local[0]
            one_batch = jax.tree_util.tree_map(lambda x: x[0], batch_local)

            def local_loss(bl, b):
                chunks = jax.tree_util.tree_map(
                    lambda x: x[0], packing.unpack_local(bl, spec))
                return sharded_loss(chunks, b, ctx)

            micro = _split_micro(one_batch, C, batch_dim=0)

            def body(carry, xs):
                mb, i = xs
                lsum, acc = carry
                l, g = jax.value_and_grad(local_loss)(buf_local, mb)
                use = i < n_k
                lsum = lsum + jnp.where(use, l, 0.0)
                acc = acc + jnp.where(use, g, 0.0)
                return (lsum, acc), ()

            init = (jnp.zeros(()), jnp.zeros_like(buf_local))
            (lsum, acc), _ = jax.lax.scan(body, init,
                                          (micro, jnp.arange(C)))
            nf = n_k.astype(jnp.float32)
            return (lsum / nf)[None], acc / nf

        return opt.sharded_value_and_grad(local_vag, state,
                                          (batch, n))

    return vag


def _sharded_packed_vag(sharded_loss, opt, microbatch: int):
    """The model-parallel path: evaluate the loss inside the 2D shard_map
    from each device's local row-shard block (``packing.unpack_local``);
    AD's transpose of the local slicing deposits the grads straight into
    the local block, so the grads buffer comes out sharded exactly like
    the state — zero resharding, zero all-gather."""
    cfg = opt.cfg
    ctx_axis = cfg.model_axis_name
    M = int(cfg.model_parallel)

    def vag(state, batch):
        spec = state.spec  # static pytree aux — fixed per trace
        ctx = ShardCtx(spec=spec, axis_name=ctx_axis, n_shards=M)

        def local_vag(buf_local, batch_local):
            # buf_local: (1, rows/M, LANE); batch_local leaves: (1, b, ...)
            one_batch = jax.tree_util.tree_map(lambda x: x[0], batch_local)

            def local_loss(bl, b):
                chunks = jax.tree_util.tree_map(
                    lambda x: x[0], packing.unpack_local(bl, spec))
                return sharded_loss(chunks, b, ctx)

            def one(b):
                return jax.value_and_grad(local_loss)(buf_local, b)

            if microbatch <= 1:
                l, g = one(one_batch)
                return l[None], g
            micro = _split_micro(one_batch, microbatch, batch_dim=0)

            def body(carry, mb):
                lsum, acc = carry
                l, g = one(mb)
                return (lsum + l, acc + g), ()

            init = (jnp.zeros(()), jnp.zeros_like(buf_local))
            (lsum, acc), _ = jax.lax.scan(body, init, micro)
            return (lsum / microbatch)[None], acc / microbatch

        return opt.sharded_value_and_grad(local_vag, state, batch)

    return vag


def sharded_loss_probe(sharded_loss, opt):
    """Forward-only twin of the sharded-packed pipeline, for the static
    analyzer (``repro.analysis.jaxpr_lint``).

    AD *inlines* custom_vjp bodies, so a grad trace of a protected and a
    raw-psum loss are structurally indistinguishable. This probe evaluates
    ``sharded_loss`` inside the SAME 2D shard_map the pipeline uses but
    without differentiating, so the ``psum_replicated`` /
    ``_slice_replicated`` boundaries stay visible as
    ``custom_vjp_call_jaxpr`` equations — the forward JXL001 rule and the
    backward psum-count check both key off this trace."""
    cfg = opt.cfg
    ctx_axis = cfg.model_axis_name
    M = int(cfg.model_parallel)
    if opt.sharded_value_and_grad is None:
        raise ValueError(
            "sharded_loss_probe needs a 2D comm='axis' optimizer (mesh "
            "with a 'model' axis); this one has no sharded execution hook")

    def fwd(state, batch):
        spec = state.spec
        ctx = ShardCtx(spec=spec, axis_name=ctx_axis, n_shards=M)

        def local_fwd(buf_local, batch_local):
            b = jax.tree_util.tree_map(lambda x: x[0], batch_local)
            chunks = jax.tree_util.tree_map(
                lambda x: x[0], packing.unpack_local(buf_local, spec))
            # identity second output satisfies the (losses, grads-buffer)
            # out_specs contract of the sharded execution hook
            return sharded_loss(chunks, b, ctx)[None], buf_local

        return opt.sharded_value_and_grad(local_fwd, state, batch)

    return fwd
