"""The train→serve publish path: unpack-once decode + lock-free store.

Pins the hot-swap acceptance criteria:

* ``kernels.pack.unpack_worker`` / ``unpack_mean`` match the full K-way
  ``unpack`` bit-for-bit (flat and row-sharded layouts) — the publish
  never needs the K-tree materialization it replaces,
* ``publish_params`` ≡ ``opt.params_of(state)`` for BOTH backends after
  real training steps (and under a worker mesh when devices allow),
* ``ParamStore`` versions are monotone and readers always see a complete
  snapshot — every leaf of a concurrent read comes from ONE publish,
  never a mix.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_optimizer
from repro.kernels import pack as packing
from repro.serve import ParamStore, publish_from_state, publish_hbm_bytes, \
    publish_params

KEY = jax.random.PRNGKey(0)
K = 4


def ragged_tree(key, k, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(ks[0], (k, 13, 7), dtype),
        "b": jax.random.normal(ks[1], (k, 5), dtype),
        "nest": {"u": jax.random.normal(ks[2], (k, 3, 11, 2), dtype)},
    }


def assert_trees_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


def grads_like(params, seed):
    leaves, treedef = jax.tree_util.tree_flatten(params)
    ks = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [jax.random.normal(k, l.shape, l.dtype)
                  for k, l in zip(ks, leaves)])


# --------------------------- unpack-once parity ------------------------------


class TestUnpackOnce:
    @pytest.mark.parametrize("layout", ["flat", "leaf_align", "sharded"])
    def test_unpack_worker_matches_full_unpack(self, layout):
        tree = ragged_tree(KEY, K)
        kw = {"flat": {},
              "leaf_align": {"leaf_align": True, "block_rows": 2},
              "sharded": {"leaf_align": True, "block_rows": 2,
                          "row_shards": 2}}[layout]
        spec = packing.make_spec(tree, stacked=True, **kw)
        buf = packing.pack(tree, spec)
        full = packing.unpack(buf, spec)
        for k in range(K):
            one = packing.unpack_worker(buf, spec, k)
            assert_trees_equal(
                one, jax.tree_util.tree_map(lambda x: x[k], full))

    def test_unpack_mean_matches_mean_of_full_unpack(self):
        tree = ragged_tree(KEY, K)
        spec = packing.make_spec(tree, stacked=True, leaf_align=True,
                                 block_rows=2)
        buf = packing.pack(tree, spec)
        full = packing.unpack(buf, spec)
        mean = packing.unpack_mean(buf, spec)
        # f32 throughout: the packed-domain mean is the same sum in the
        # same order, so bitwise equality holds
        assert_trees_equal(
            mean, jax.tree_util.tree_map(lambda x: x.mean(axis=0), full))

    def test_unpack_worker_validates(self):
        tree = ragged_tree(KEY, K)
        spec = packing.make_spec(tree, stacked=True)
        buf = packing.pack(tree, spec)
        with pytest.raises(ValueError, match="worker"):
            packing.unpack_worker(buf, spec, K)
        flat_spec = packing.make_spec(
            jax.tree_util.tree_map(lambda x: x[0], tree))
        flat_buf = packing.pack(
            jax.tree_util.tree_map(lambda x: x[0], tree), flat_spec)
        with pytest.raises(ValueError, match="stacked"):
            packing.unpack_worker(flat_buf, flat_spec, 0)


# ------------------------ publish_params ≡ params_of -------------------------


class TestPublishParity:
    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_worker_mode_matches_params_of(self, backend):
        opt = make_optimizer("d-adam", K=K, eta=1e-2, period=2,
                             backend=backend)
        state = opt.init(ragged_tree(KEY, K))
        for t in range(3):
            state = opt.step(state, grads_like(opt.params_of(state), t))
        ref = opt.params_of(state)
        for k in range(K):
            assert_trees_equal(
                publish_params(state, mode="worker", worker=k),
                jax.tree_util.tree_map(lambda x: x[k], ref))

    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_mean_mode_matches_mean_of_params_of(self, backend):
        opt = make_optimizer("d-adam", K=K, eta=1e-2, period=2,
                             backend=backend)
        state = opt.init(ragged_tree(KEY, K))
        for t in range(3):
            state = opt.step(state, grads_like(opt.params_of(state), t))
        ref = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32).mean(axis=0).astype(x.dtype),
            opt.params_of(state))
        assert_trees_equal(publish_params(state, mode="mean"), ref)

    @pytest.mark.skipif(jax.device_count() < K,
                        reason=f"needs >= {K} devices (tier1.sh forces 8)")
    def test_parity_under_worker_mesh(self):
        from repro.launch.mesh import make_worker_mesh
        mesh = make_worker_mesh(K)
        opt = make_optimizer("d-adam", K=K, eta=1e-2, period=2,
                             backend="pallas", comm="axis", mesh=mesh)
        state = opt.init(ragged_tree(KEY, K))
        for t in range(2):
            g = packing.pack(grads_like(opt.params_of(state), t),
                             state.spec, dtype=state.buf.dtype)
            state = opt.step(state, g)
        ref = opt.params_of(state)
        assert_trees_equal(
            publish_params(state, mode="worker", worker=1),
            jax.tree_util.tree_map(lambda x: x[1], ref))

    @pytest.mark.skipif(jax.device_count() < 4,
                        reason="needs >= 4 devices (tier1.sh forces 8)")
    def test_parity_under_2d_mesh(self):
        from repro.launch.mesh import make_worker_mesh
        mesh = make_worker_mesh(2, model_parallel=2)
        opt = make_optimizer("d-adam", K=2, eta=1e-2, period=2,
                             backend="pallas", comm="axis", mesh=mesh)
        state = opt.init(ragged_tree(KEY, 2))
        for t in range(2):
            g = packing.pack(grads_like(opt.params_of(state), t),
                             state.spec, dtype=state.buf.dtype)
            state = opt.step(state, g)
        ref = opt.params_of(state)
        assert_trees_equal(
            publish_params(state, mode="worker", worker=0),
            jax.tree_util.tree_map(lambda x: x[0], ref))

    def test_plain_stacked_tree_and_reference_state(self):
        tree = ragged_tree(KEY, K)
        assert_trees_equal(
            publish_params(tree, mode="worker", worker=2),
            jax.tree_util.tree_map(lambda x: x[2], tree))

    def test_mode_validated(self):
        with pytest.raises(ValueError, match="mode"):
            publish_params(ragged_tree(KEY, K), mode="median")

    def test_hbm_accounting(self):
        opt = make_optimizer("d-adam", K=K, backend="pallas")
        state = opt.init(ragged_tree(KEY, K))
        w = publish_hbm_bytes(state, mode="worker")
        m = publish_hbm_bytes(state, mode="mean")
        # worker mode reads exactly 1/K of the resident buffer
        assert w["read_bytes"] * K == w["full_unpack_read_bytes"]
        assert w["read_bytes"] == state.buf.nbytes // K
        # both modes write ONE tree, not K
        assert w["write_bytes"] * K == w["full_unpack_write_bytes"]
        assert m["write_bytes"] == w["write_bytes"]


# -------------------------------- ParamStore ---------------------------------


class TestParamStore:
    def test_versions_monotone(self):
        store = ParamStore()
        assert store.version == 0
        with pytest.raises(ValueError, match="empty"):
            store.snapshot()
        versions = [store.publish({"w": jnp.full((3,), float(i))})
                    for i in range(5)]
        assert versions == [1, 2, 3, 4, 5]
        v, params = store.snapshot()
        assert v == 5 and float(params["w"][0]) == 4.0

    def test_publish_from_state_bumps_version(self):
        opt = make_optimizer("d-adam", K=K, backend="pallas")
        state = opt.init(ragged_tree(KEY, K))
        store = ParamStore()
        assert publish_from_state(store, state, mode="worker") == 1
        assert publish_from_state(store, state, mode="mean") == 2
        assert_trees_equal(store.snapshot()[1],
                           publish_params(state, mode="mean"))

    def test_reader_always_sees_complete_snapshot(self):
        """Concurrency property: under a publisher storm, every snapshot
        a reader takes is internally consistent — all leaves encode the
        SAME version, and versions never run backwards per reader."""
        store = ParamStore()

        def tree_for(v):
            return {"a": np.full((4,), v), "n": {"b": np.full((2,), v)}}

        store.publish(tree_for(1))
        stop = threading.Event()
        torn, regressions = [], []

        def reader():
            last = 0
            while not stop.is_set():
                version, params = store.snapshot()
                vals = {float(x) for x in
                        np.concatenate([params["a"], params["n"]["b"]])}
                if len(vals) != 1 or vals != {float(version)}:
                    torn.append((version, vals))
                if version < last:
                    regressions.append((last, version))
                last = version

        threads = [threading.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        for v in range(2, 200):
            store.publish(tree_for(v))
        stop.set()
        for t in threads:
            t.join()
        assert not torn, f"torn snapshots: {torn[:3]}"
        assert not regressions, f"version regressions: {regressions[:3]}"
        assert store.version == 199

    def test_concurrent_publishers_never_lose_versions(self):
        store = ParamStore()
        seen = []
        lock = threading.Lock()

        def publisher(i):
            for _ in range(50):
                v = store.publish({"w": np.zeros((1,))})
                with lock:
                    seen.append(v)

        threads = [threading.Thread(target=publisher, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(seen) == list(range(1, 201))

    def test_previous_version_stays_resident(self):
        """Two-slot ring: the buffers behind version v stay untouched
        while v+1 lands — a decode holding v keeps valid arrays."""
        store = ParamStore()
        store.publish({"w": np.full((3,), 1.0)})
        _, held = store.snapshot()
        store.publish({"w": np.full((3,), 2.0)})
        np.testing.assert_array_equal(held["w"], np.full((3,), 1.0))
