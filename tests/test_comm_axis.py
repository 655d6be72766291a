"""comm='axis' device-parallel execution — in-process tests.

The unified comm dispatch runs the SAME optimizer step either stacked (one
program, worker shifts = rolls) or per-shard inside shard_map over a
'worker' mesh axis (worker shifts = ppermute). These tests pin the two
modes against each other for both backends and both optimizers.

Device-requiring tests skip when the process has fewer devices than
workers (plain ``pytest`` runs single-device; ``scripts/tier1.sh`` forces
8 host devices so the whole module executes there). Validation tests run
everywhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_optimizer
from repro.core.dadam import DAdamConfig

KEY = jax.random.PRNGKey(0)
K = 4


def ragged_tree(key, k):
    ks = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(ks[0], (k, 13, 7)),
        "b": jax.random.normal(ks[1], (k, 5)),
        "nest": {"u": jax.random.normal(ks[2], (k, 3, 11, 2))},
    }


def needs_devices(n):
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs >= {n} devices (tier1.sh forces 8 host devices)")


@pytest.fixture(scope="module")
def worker_mesh():
    if jax.device_count() < K:
        pytest.skip(f"needs >= {K} devices")
    from repro.launch.mesh import make_worker_mesh
    return make_worker_mesh(K)


# ------------------------------ validation ----------------------------------


class TestValidation:
    def test_axis_without_mesh_rejected(self):
        with pytest.raises(ValueError, match="mesh"):
            make_optimizer("d-adam", K=4, comm="axis")

    def test_mesh_without_axis_comm_rejected(self):
        with pytest.raises(ValueError, match="comm='axis'"):
            make_optimizer("d-adam", K=4, mesh=object())

    def test_unknown_comm_rejected(self):
        with pytest.raises(ValueError, match="comm"):
            DAdamConfig(comm="bogus").validate()

    def test_dense_mixing_under_axis_rejected(self):
        with pytest.raises(ValueError, match="dense"):
            DAdamConfig(comm="axis", mixing="dense").validate()

    def test_dpsgd_axis_rejected(self):
        with pytest.raises(ValueError, match="d-psgd"):
            make_optimizer("d-psgd", K=4, comm="axis")


@needs_devices(K)
class TestMeshValidation:
    def test_wrong_axis_size_rejected(self, worker_mesh):
        with pytest.raises(ValueError, match="size K"):
            make_optimizer("d-adam", K=K + 1, comm="axis", mesh=worker_mesh)

    def test_wrong_axis_name_rejected(self, worker_mesh):
        with pytest.raises(ValueError, match="axis"):
            make_optimizer("d-adam", K=K, comm="axis", mesh=worker_mesh,
                           axis_name="pod")

    def test_non_shift_topology_rejected_at_construction(self, worker_mesh):
        """A topology without shift offsets must fail in make_optimizer,
        not at first step trace inside shard_map. (torus no longer
        qualifies — its wrap-aware GridShift offsets made it
        shift-expressible, see test_torus_now_accepted_under_axis — so
        build an offsets-free graph directly.)"""
        from repro.core.topology import Topology
        W = np.full((K, K), 1.0 / K)
        no_offsets = Topology(name="dense-no-offsets", weights=W,
                              offsets=(), offset_weights=(),
                              self_weight=1.0 / K)
        with pytest.raises(ValueError, match="shift-invariant"):
            make_optimizer("d-adam", K=K, topology=no_offsets, comm="axis",
                           mesh=worker_mesh)

    def test_torus_now_accepted_under_axis(self, worker_mesh):
        """The wrap-aware torus offsets lower under comm='axis' too: the
        sharded run must match the stacked run exactly."""
        kw = dict(eta=1e-2, period=1, topology="torus")
        opt_ax = make_optimizer("d-adam", K=K, comm="axis",
                                mesh=worker_mesh, **kw)
        opt_st = make_optimizer("d-adam", K=K, **kw)
        p0 = {"w": jax.random.normal(jax.random.PRNGKey(0), (K, 5, 7))}
        g = jax.tree_util.tree_map(jnp.ones_like, p0)
        sa, ss = opt_ax.init(p0), opt_st.init(p0)
        for _ in range(4):
            sa, ss = opt_ax.step(sa, g), opt_st.step(ss, g)
        pa = jax.device_get(opt_ax.params_of(sa))
        ps = opt_st.params_of(ss)
        assert bool(jnp.allclose(pa["w"], ps["w"], atol=1e-6))


# ------------------------- axis == stacked parity ---------------------------


@needs_devices(K)
class TestAxisMatchesStacked:
    @pytest.mark.parametrize("kind", ["d-adam", "cd-adam"])
    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_multi_step_parity(self, kind, backend, worker_mesh):
        """4 steps with period=2 (both cond branches) under shard_map ==
        the stacked single-program run, for both backends."""
        params = ragged_tree(KEY, K)
        base = make_optimizer(kind, K=K, eta=1e-2, period=2,
                              weight_decay=0.01, backend=backend)
        axis = make_optimizer(kind, K=K, eta=1e-2, period=2,
                              weight_decay=0.01, backend=backend,
                              comm="axis", mesh=worker_mesh)
        s0 = base.init(jax.tree_util.tree_map(jnp.copy, params))
        s1 = axis.init(jax.tree_util.tree_map(jnp.copy, params))
        step0 = jax.jit(lambda s, g: base.step(s, g))
        step1 = jax.jit(lambda s, g: axis.step(s, g))
        for t in range(4):
            g = jax.tree_util.tree_map(
                lambda x: 0.5 * x + 0.01 * (t + 1), base.params_of(s0))
            if backend == "pallas":
                from repro.kernels import pack as packing
                gb = packing.pack(g, s0.spec, dtype=s0.buf.dtype)
                s0, s1 = step0(s0, gb), step1(s1, gb)
            else:
                s0, s1 = step0(s0, g), step1(s1, g)
        for a, b in zip(jax.tree_util.tree_leaves(base.params_of(s0)),
                        jax.tree_util.tree_leaves(axis.params_of(s1))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)

    def test_axis_state_is_sharded_over_workers(self, worker_mesh):
        """opt.init really partitions the resident buffer: one worker's
        (1, rows, 128) shard per mesh slot."""
        axis = make_optimizer("d-adam", K=K, eta=1e-2, backend="pallas",
                              comm="axis", mesh=worker_mesh)
        state = axis.init(ragged_tree(KEY, K))
        assert axis.mesh is worker_mesh
        shard_shapes = {s.data.shape for s in state.buf.addressable_shards}
        assert shard_shapes == {(1,) + state.buf.shape[1:]}
        # the scalar count stays replicated
        assert len(state.count.addressable_shards) == K

    @pytest.mark.parametrize("kind", ["d-adam", "cd-adam"])
    def test_round_step_parity_packed(self, kind, worker_mesh):
        """p local fused steps + one ppermute gossip inside shard_map ==
        the stacked round, with grad_fn on the resident buffer shard."""
        params = ragged_tree(KEY, K)
        base = make_optimizer(kind, K=K, eta=1e-2, period=3,
                              backend="pallas")
        axis = make_optimizer(kind, K=K, eta=1e-2, period=3,
                              backend="pallas", comm="axis",
                              mesh=worker_mesh)
        batches = jnp.zeros((3, K, 1))
        grad_fn = lambda buf, batch: 0.5 * buf
        s0 = base.round(base.init(jax.tree_util.tree_map(jnp.copy, params)),
                        grad_fn, batches)
        s1 = axis.round(axis.init(jax.tree_util.tree_map(jnp.copy, params)),
                        grad_fn, batches)
        assert int(s1.count) == 3
        for a, b in zip(jax.tree_util.tree_leaves(base.params_of(s0)),
                        jax.tree_util.tree_leaves(axis.params_of(s1))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)


# ------------------------ trainer + checkpoint ------------------------------


@needs_devices(K)
class TestAxisTrainerAndCheckpoint:
    def test_trainer_fit_matches_stacked(self, worker_mesh):
        """End to end: the trainer's differentiate-through-unpack path on
        the sharded resident state tracks the stacked run."""
        from repro.train import DecentralizedTrainer

        d = 37
        centers = jax.random.normal(KEY, (K, d))

        def loss_fn(params, batch):
            return jnp.sum((params["x"] - batch) ** 2)

        def batch_iter():
            t = 0
            while True:
                yield centers + 0.01 * t
                t += 1

        logs = {}
        for comm in ("stacked", "axis"):
            opt = make_optimizer(
                "cd-adam", K=K, eta=5e-2, period=2, backend="pallas",
                comm=comm, mesh=worker_mesh if comm == "axis" else None)
            trainer = DecentralizedTrainer(loss_fn, opt)
            state = trainer.init({"x": jnp.zeros((d,))})
            state, log = trainer.fit(state, batch_iter(), 4, log_every=2)
            logs[comm] = (log, opt.params_of(state))
        np.testing.assert_allclose(logs["stacked"][0].loss,
                                   logs["axis"][0].loss,
                                   rtol=2e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(logs["stacked"][1]["x"]),
                                   np.asarray(logs["axis"][1]["x"]),
                                   rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("kind", ["d-adam", "cd-adam"])
    def test_checkpoint_portable_across_comm_modes(self, kind, tmp_path,
                                                   worker_mesh):
        """stacked-pallas checkpoint -> axis-sharded state (placement of
        the like-state preserved) -> back to reference, bit-identically."""
        from repro.checkpoint import restore, save

        params = ragged_tree(KEY, K)
        stacked = make_optimizer(kind, K=K, eta=1e-2, backend="pallas")
        axis = make_optimizer(kind, K=K, eta=1e-2, backend="pallas",
                              comm="axis", mesh=worker_mesh)
        s = stacked.init(jax.tree_util.tree_map(jnp.copy, params))
        s = stacked.step(s, 0.3 * s.buf)
        path = str(tmp_path / "ck.npz")
        save(path, s, step=1)
        like = axis.init(jax.tree_util.tree_map(jnp.copy, params))
        restored, step = restore(path, like)
        assert step == 1
        assert restored.buf.sharding == like.buf.sharding
        np.testing.assert_array_equal(np.asarray(restored.buf),
                                      np.asarray(s.buf))
        # restored sharded state keeps stepping, in lockstep with stacked
        out_axis = axis.step(restored, 0.3 * restored.buf)
        out_stacked = stacked.step(s, 0.3 * s.buf)
        np.testing.assert_allclose(np.asarray(out_axis.buf),
                                   np.asarray(out_stacked.buf),
                                   rtol=2e-5, atol=1e-6)
