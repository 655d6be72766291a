"""The sharded-gradient pipeline (train/grad.py).

Pins the PR's tentpole: on a 2D (worker × model) mesh the trainer can
evaluate the loss model-parallel DIRECTLY from each device's local packed
row-shard block (``packing.unpack_local`` + a ``sharded_loss``), with

* loss/param parity against the PR-4 differentiate-through-full-unpack
  path and against the reference backend (10-step trainer runs, both
  optimizers, K×M = 4×2 and 2×4), and
* a compiled 2D step whose collectives contain **zero all-gathers** (and
  zero all-to-alls): nothing crosses the wire but the neighbor gossip
  ppermutes and the small per-shard activation psums —
  ``analysis.hlo.collective_summary`` is the regression instrument.

Also pins the pipeline's building blocks: ``unpack_local`` /
``mirror_local`` layout round-trips, the replicated-cotangent ``psum``
(a raw psum transpose would silently scale every gradient by M), the
dispatch modes, and microbatch gradient accumulation parity in every
mode.

The model is a real matmul (d_in=1600 × d_out=64 + bias), sized so the
weight leaf genuinely spans every model shard at both factorizations —
small single-shard leaves would let GSPMD dodge the gather this test
exists to rule out.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import collective_summary
from repro.analysis.invariants import InvariantSpec, evaluate_hlo
from repro.core import make_optimizer
from repro.kernels import pack as packing
from repro.launch.mesh import make_worker_mesh
from repro.train import (DecentralizedTrainer, make_grad_pipeline,
                         row_parallel_dot)

KEY = jax.random.PRNGKey(0)
KINDS = ["d-adam", "cd-adam"]
FACTORIZATIONS = [(4, 2), (2, 4)]  # K x M — both run on tier1.sh's 8 devices

DIN, DOUT, B = 1600, 64, 8  # w spans all shards at M=2 AND M=4


def skip_unless_devices(n):
    if jax.device_count() < n:
        pytest.skip(f"needs >= {n} devices, have {jax.device_count()}")


def mlp_params():
    return {"bias": jnp.zeros((DOUT,)),
            "w": jax.random.normal(KEY, (DIN, DOUT)) * 0.02}


def mlp_loss(p, batch):
    pred = batch["x"] @ p["w"] + p["bias"]
    return jnp.mean((pred - batch["y"]) ** 2)


def sharded_mlp_loss(chunks, batch, ctx):
    """The model-parallel spelling: the weight chunk feeds a row-parallel
    matmul (operand P('model', None), activation psum over 'model'), the
    bias — leaf 0 in spec order — assembles via one small psum."""
    h = row_parallel_dot(batch["x"], chunks["w"], DOUT, ctx)
    pred = h + ctx.full_leaf(chunks["bias"], 0)
    return jnp.mean((pred - batch["y"]) ** 2)


def quad_loss(p, batch):
    return jnp.mean((p["x"] - batch) ** 2)


def sharded_quad_loss(chunks, batch, ctx):
    """The elementwise spelling: mirror the target into the chunk layout
    and psum the partial sums (padding slots subtract 0 - 0)."""
    bl = ctx.mirror({"x": batch})
    d = batch.size
    return ctx.psum(jnp.sum((chunks["x"] - bl["x"]) ** 2)) / d


def mlp_batches(K):
    t = 0
    while True:
        kt = jax.random.fold_in(KEY, t)
        yield {"x": jax.random.normal(kt, (K, B, DIN)),
               "y": jax.random.normal(jax.random.fold_in(kt, 1),
                                      (K, B, DOUT))}
        t += 1


# ------------------------- layout building blocks ----------------------------


class TestUnpackLocal:
    def ragged_spec(self, M):
        tree = {"w": jax.random.normal(KEY, (4, 13, 7)),
                "b": jax.random.normal(KEY, (4, 5)),
                "n": {"u": jax.random.normal(KEY, (4, 3, 11, 2))}}
        spec = packing.make_spec(tree, stacked=True,
                                 block_rows=packing.BLOCK_ROWS,
                                 leaf_align=True, row_shards=M)
        return tree, spec, packing.pack(tree, spec)

    @pytest.mark.parametrize("M", [1, 2, 4])
    def test_chunks_concat_to_unpack(self, M):
        """Concatenating every shard's local slices reproduces the full
        leaves — the shard-invariant layout contract."""
        tree, spec, buf = self.ragged_spec(M)
        lr = spec.local_rows
        per_shard = [packing.unpack_local(buf[:, j * lr:(j + 1) * lr], spec)
                     for j in range(M)]
        leaves = jax.tree_util.tree_leaves(tree)
        for i, (lv, sz, shape) in enumerate(
                zip(leaves, spec.sizes, spec.shapes)):
            cat = jnp.concatenate(
                [jax.tree_util.tree_leaves(c)[i] for c in per_shard],
                axis=1)
            np.testing.assert_array_equal(
                np.asarray(cat[:, :sz].reshape(shape)), np.asarray(lv))

    @pytest.mark.parametrize("M", [2, 4])
    def test_mirror_local_matches_packed_slices(self, M):
        """mirror_local of a replicated per-worker tree lands exactly on
        the packed chunk layout, shard by shard."""
        tree, spec, buf = self.ragged_spec(M)
        per_worker = jax.tree_util.tree_map(lambda x: x[0], tree)
        lr = spec.local_rows
        for j in range(M):
            mirr = packing.mirror_local(per_worker, spec, j)
            loc = packing.unpack_local(buf[:1, j * lr:(j + 1) * lr], spec)
            for a, b in zip(jax.tree_util.tree_leaves(mirr),
                            jax.tree_util.tree_leaves(loc)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b[0]),
                                           rtol=1e-6)

    def test_rejections(self):
        tree = {"w": jnp.ones((4, 13, 7)), "b": jnp.ones((4, 5))}
        flat_spec = packing.make_spec(tree, stacked=True)
        with pytest.raises(ValueError, match="leaf_align"):
            packing.unpack_local(jnp.zeros((1, 1, 128)), flat_spec)
        _, spec, buf = self.ragged_spec(2)
        with pytest.raises(ValueError, match="row-shard block"):
            packing.unpack_local(buf, spec)  # full buffer, not one block
        with pytest.raises(ValueError, match="per-worker leaf shapes"):
            packing.mirror_local({"w": jnp.ones((4, 13, 7)),
                                  "b": jnp.ones((4, 5)),
                                  "n": {"u": jnp.ones((4, 3, 11, 2))}},
                                 spec, 0)


# ------------------------------ mode dispatch --------------------------------


class TestDispatch:
    def test_modes(self):
        K = 4
        ref = make_optimizer("d-adam", K=K, backend="reference")
        assert make_grad_pipeline(quad_loss, ref).mode == "reference"
        packed = make_optimizer("d-adam", K=K, backend="pallas")
        assert make_grad_pipeline(quad_loss, packed).mode == "packed"
        # sharded_loss without a 2D optimizer: graceful fallback
        assert make_grad_pipeline(
            quad_loss, packed, sharded_loss=sharded_quad_loss
        ).mode == "packed"
        skip_unless_devices(8)
        mesh2d = make_worker_mesh(4, model_parallel=2)
        ax2 = make_optimizer("d-adam", K=K, backend="pallas", comm="axis",
                             mesh=mesh2d)
        assert make_grad_pipeline(quad_loss, ax2).mode == "packed"
        assert make_grad_pipeline(
            quad_loss, ax2, sharded_loss=sharded_quad_loss
        ).mode == "sharded-packed"

    def test_bad_microbatch(self):
        opt = make_optimizer("d-adam", K=2, backend="reference")
        with pytest.raises(ValueError, match="microbatch"):
            make_grad_pipeline(quad_loss, opt, microbatch=0)


# --------------------------- microbatch parity -------------------------------


class TestMicrobatch:
    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_trainer_parity_vs_microbatch_1(self, backend):
        """microbatch=4 gradient accumulation == one full-batch step, in
        both the reference and the packed (AD-through-unpack) paths."""
        K = 4
        finals, losses = {}, {}
        for mb in (1, 4):
            opt = make_optimizer("d-adam", K=K, eta=1e-2, period=2,
                                 backend=backend)
            tr = DecentralizedTrainer(mlp_loss, opt, microbatch=mb)
            assert tr.pipeline.microbatch == mb
            state = tr.init(mlp_params())
            state, log = tr.fit(state, mlp_batches(K), 6, log_every=3)
            finals[mb] = np.asarray(opt.params_of(state)["w"])
            losses[mb] = log.loss
        np.testing.assert_allclose(losses[1], losses[4], rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(finals[1], finals[4], rtol=1e-4,
                                   atol=1e-6)

    def test_sharded_mode_microbatch(self):
        """Gradient accumulation inside the 2D shard_map: microbatch=2 ==
        microbatch=1 on the sharded-packed path."""
        skip_unless_devices(8)
        K, M = 4, 2
        mesh = make_worker_mesh(K, model_parallel=M)
        finals = {}
        for mb in (1, 2):
            opt = make_optimizer("d-adam", K=K, eta=1e-2, period=2,
                                 backend="pallas", comm="axis", mesh=mesh)
            tr = DecentralizedTrainer(mlp_loss, opt, microbatch=mb,
                                      sharded_loss=sharded_mlp_loss)
            assert tr.pipeline.mode == "sharded-packed"
            state = tr.init(mlp_params())
            state, _ = tr.fit(state, mlp_batches(K), 4, log_every=2)
            finals[mb] = np.asarray(opt.params_of(state)["w"])
        np.testing.assert_allclose(finals[1], finals[2], rtol=1e-4,
                                   atol=1e-6)

    def test_batch_not_divisible_raises(self):
        opt = make_optimizer("d-adam", K=2, backend="reference")
        tr = DecentralizedTrainer(mlp_loss, opt, microbatch=3)
        state = tr.init(mlp_params())
        with pytest.raises(Exception, match="divisible|reshape"):
            tr._step(state, next(mlp_batches(2)))  # B=8, mb=3


# --------------------- acceptance: parity + collectives ----------------------


def _trainer_for(kind, k, kw, extra):
    opt = make_optimizer(kind, K=k, eta=1e-2, period=2, **kw)
    return opt, DecentralizedTrainer(mlp_loss, opt, **extra)


class TestShardedParityChain:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("factor", FACTORIZATIONS,
                             ids=lambda f: f"K{f[0]}xM{f[1]}")
    def test_sharded_equals_unpack_equals_reference(self, kind, factor):
        """10-step trainer run: the sharded-packed pipeline ≡ the PR-4
        differentiate-through-unpack path ≡ reference, losses and final
        params, under both optimizers and both mesh factorizations."""
        k, m = factor
        skip_unless_devices(k * m)
        mesh = make_worker_mesh(k, model_parallel=m)
        configs = {
            "reference": (dict(backend="reference"), {}),
            "unpack2d": (dict(backend="pallas", comm="axis", mesh=mesh),
                         {}),
            "sharded2d": (dict(backend="pallas", comm="axis", mesh=mesh),
                          dict(sharded_loss=sharded_mlp_loss)),
        }
        logs, finals = {}, {}
        for name, (kw, extra) in configs.items():
            opt, tr = _trainer_for(kind, k, kw, extra)
            state = tr.init(mlp_params())
            state, log = tr.fit(state, mlp_batches(k), 10, log_every=5)
            logs[name] = log.loss
            finals[name] = np.asarray(opt.params_of(state)["w"])
        # the unpack path reproduces the reference trajectory tightly for
        # both optimizers (same grads up to GSPMD scheduling)
        np.testing.assert_allclose(logs["reference"], logs["unpack2d"],
                                   rtol=2e-4, atol=1e-5)
        np.testing.assert_allclose(finals["reference"], finals["unpack2d"],
                                   rtol=2e-4, atol=2e-5)
        if kind == "d-adam":
            np.testing.assert_allclose(logs["reference"], logs["sharded2d"],
                                       rtol=2e-4, atol=1e-5)
            np.testing.assert_allclose(finals["reference"],
                                       finals["sharded2d"],
                                       rtol=2e-4, atol=2e-5)
        else:
            # CD-Adam's sign compressor amplifies the sharded matmul's
            # ~1e-8 reduction-order differences into isolated sign flips
            # of delta elements near zero (each worth ~2*gamma*scale);
            # the trajectories track — pin losses plus a flip budget
            # instead of elementwise equality.
            np.testing.assert_allclose(logs["reference"], logs["sharded2d"],
                                       rtol=5e-3, atol=5e-3)
            d = np.abs(finals["reference"] - finals["sharded2d"])
            assert d.mean() < 1e-4, f"mean drift {d.mean():.2e}"
            assert (d > 1e-3).mean() < 0.01, \
                f"sign-flip fraction {(d > 1e-3).mean():.4f}"
            assert d.max() < 0.1

    def test_two_layer_row_parallel_grads_compose(self):
        """Stacked row-parallel layers: the lower layer's weight grads
        flow through the upper layer's input slice. Pins
        _slice_replicated's psum'd backward — with a raw dynamic_slice
        the cotangent entering layer 1 would be slice-shaped and most of
        W1's gradient would silently vanish."""
        skip_unless_devices(8)
        K, M = 4, 2
        d_h = 128  # hidden width: W1 is (DIN, d_h), W2 is (d_h, DOUT)
        mesh = make_worker_mesh(K, model_parallel=M)

        def two_layer_loss(p, batch):
            h = jnp.tanh(batch["x"] @ p["w1"])
            pred = h @ p["w2"]
            return jnp.mean((pred - batch["y"]) ** 2)

        def sharded_two_layer(chunks, batch, ctx):
            h = jnp.tanh(row_parallel_dot(batch["x"], chunks["w1"], d_h,
                                          ctx))
            pred = row_parallel_dot(h, chunks["w2"], DOUT, ctx)
            return jnp.mean((pred - batch["y"]) ** 2)

        params = {"w1": jax.random.normal(KEY, (DIN, d_h)) * 0.02,
                  "w2": jax.random.normal(jax.random.fold_in(KEY, 1),
                                          (d_h, DOUT)) * 0.05}
        opt_r = make_optimizer("d-adam", K=K, eta=1e-2, period=2,
                               backend="reference")
        tr_r = DecentralizedTrainer(two_layer_loss, opt_r)
        opt_s = make_optimizer("d-adam", K=K, eta=1e-2, period=2,
                               backend="pallas", comm="axis", mesh=mesh)
        tr_s = DecentralizedTrainer(two_layer_loss, opt_s,
                                    sharded_loss=sharded_two_layer)
        s_r = tr_r.init(jax.tree_util.tree_map(jnp.copy, params))
        s_s = tr_s.init(jax.tree_util.tree_map(jnp.copy, params))
        s_r, log_r = tr_r.fit(s_r, mlp_batches(K), 6, log_every=3)
        s_s, log_s = tr_s.fit(s_s, mlp_batches(K), 6, log_every=3)
        np.testing.assert_allclose(log_r.loss, log_s.loss, rtol=2e-4,
                                   atol=1e-5)
        for leaf in ("w1", "w2"):
            np.testing.assert_allclose(
                np.asarray(opt_r.params_of(s_r)[leaf]),
                np.asarray(opt_s.params_of(s_s)[leaf]),
                rtol=2e-4, atol=2e-5)

    def test_quadratic_sharded_loss_parity(self):
        """The elementwise (mirror + psum) spelling on the quadratic toy:
        pins ctx.mirror and the replicated-cotangent psum (a raw psum
        would run M× gradients through Adam)."""
        skip_unless_devices(8)
        K, M, d = 4, 2, 37
        mesh = make_worker_mesh(K, model_parallel=M)
        centers = jax.random.normal(KEY, (K, d))

        def batches():
            t = 0
            while True:
                yield centers + 0.01 * t
                t += 1

        finals = {}
        for name, kw, extra in [
            ("reference", dict(backend="reference"), {}),
            ("sharded2d", dict(backend="pallas", comm="axis", mesh=mesh),
             dict(sharded_loss=sharded_quad_loss)),
        ]:
            opt = make_optimizer("d-adam", K=K, eta=5e-2, period=2, **kw)
            tr = DecentralizedTrainer(quad_loss, opt, **extra)
            state = tr.init({"x": jnp.zeros((d,))})
            state, _ = tr.fit(state, batches(), 10, log_every=5)
            finals[name] = np.asarray(opt.params_of(state)["x"])
        np.testing.assert_allclose(finals["reference"], finals["sharded2d"],
                                   rtol=2e-4, atol=2e-5)


class TestNoFullParamAllGather:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("factor", FACTORIZATIONS,
                             ids=lambda f: f"K{f[0]}xM{f[1]}")
    def test_compiled_2d_step_collectives(self, kind, factor):
        """THE acceptance instrument: the compiled sharded-packed 2D step
        contains zero all-gathers (and zero all-to-alls) of any size; the
        only collectives are the neighbor-gossip permutes (bounded by one
        device's row-shard block per hop) and the per-shard activation
        psums (bounded by the activation size, orders of magnitude under
        the full per-worker parameter bytes)."""
        k, m = factor
        skip_unless_devices(k * m)
        mesh = make_worker_mesh(k, model_parallel=m)
        opt, tr = _trainer_for(
            kind, k, dict(backend="pallas", comm="axis", mesh=mesh),
            dict(sharded_loss=sharded_mlp_loss))
        assert tr.pipeline.mode == "sharded-packed"
        state = tr.init(mlp_params())
        batch = tr._place_batch(next(mlp_batches(k)))
        hlo = tr._step.lower(state, batch).compile().as_text()

        param_bytes = 4 * (DIN * DOUT + DOUT)      # full per-worker params
        block_bytes = state.buf.nbytes // (k * m)  # one device's row shard

        # Declarative form of the acceptance gate (shared with
        # scripts/check_invariants.py): no gather/reshard of parameters of
        # any size; gossip permutes bounded by one device's packed block;
        # the activation psums bounded by B×DOUT f32 (+ slack for bias
        # assembly and CD-Adam per-leaf scales), far below parameter size.
        # XLA's all-reduce combiner merges the activation psum with the
        # bias assembly into one variadic all-reduce; the bound holds for
        # each of its operands.
        spec = InvariantSpec(
            name=f"sharded2d/{kind}/K{k}xM{m}",
            collective_counts={"all-gather": 0, "all-to-all": 0,
                               "reduce-scatter": 0},
            min_collective_counts={"collective-permute": 1,
                                   "all-reduce": 1},
            single_collective_bytes={
                "all-gather": 0,
                "collective-permute": block_bytes,
                "all-reduce": min(4 * B * DOUT, param_bytes // 16 - 1)},
        )
        report = evaluate_hlo(hlo, spec)
        assert report.ok, report.format()

    def test_unpack_path_reshards_where_sharded_does_not(self):
        """Motivation pin (informational direction, robust assertion): the
        PR-4 GSPMD-through-unpack step moves strictly more reshard bytes
        (all-gather + all-to-all) than the sharded pipeline, whose total
        is exactly zero."""
        skip_unless_devices(8)
        k, m = 4, 2
        mesh = make_worker_mesh(k, model_parallel=m)
        totals = {}
        for name, extra in [("unpack2d", {}),
                            ("sharded2d",
                             dict(sharded_loss=sharded_mlp_loss))]:
            opt, tr = _trainer_for(
                "d-adam", k, dict(backend="pallas", comm="axis", mesh=mesh),
                extra)
            state = tr.init(mlp_params())
            batch = tr._place_batch(next(mlp_batches(k)))
            hlo = tr._step.lower(state, batch).compile().as_text()
            s = collective_summary(hlo)
            totals[name] = (s["all-gather"]["bytes"]
                            + s["all-to-all"]["bytes"])
            if name == "sharded2d":
                report = evaluate_hlo(hlo, InvariantSpec(
                    name="sharded2d-reshard",
                    collective_bytes={"all-gather": 0, "all-to-all": 0}))
                assert report.ok, report.format()
        assert totals["sharded2d"] == 0
        assert totals["unpack2d"] > totals["sharded2d"]
