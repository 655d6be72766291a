"""Multi-device EXECUTION tests (not just lowering): run in a subprocess
with 8 forced host devices so the main test process keeps 1 device.

Covers: stacked D-Adam train step really executing under a (4, 2) mesh with
the production sharding rules; gossip_axis (ppermute inside shard_map) ==
stacked roll gossip; numerical equality of the sharded step vs the
single-device step; and the comm='axis' packed runtime — the resident
(K, rows, 128) buffer sharded one worker per device — matching both the
single-device packed step and the reference backend.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert jax.device_count() == 8, jax.device_count()
    mesh = jax.make_mesh((4, 2), ("data", "model"))

    from repro.configs import get_reduced
    from repro.core import make_optimizer
    from repro.core.dadam import gossip_axis, gossip_roll
    from repro.core.topology import make_topology
    from repro.models import build_model

    # ---- 1. sharded stacked train step == single-device step -------------
    arch = get_reduced("llama3.2-1b")
    cfg = arch.model
    api = build_model(cfg)
    K = 4
    opt = make_optimizer("d-adam", K=K, eta=1e-3, period=2)
    params = api.init(jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (K,) + x.shape), params)
    state = opt.init(stacked)
    toks = jax.random.randint(jax.random.PRNGKey(1), (K, 2, 17), 0,
                              cfg.vocab_size)

    def step(state, toks):
        grads = jax.vmap(jax.grad(api.loss))(state.params,
                                             {"tokens": toks})
        return opt.step(state, grads)

    # single device reference
    ref = jax.jit(step)(state, toks)

    # sharded: worker dim on 'data', largest inner dim on 'model'
    def shard_rule(x):
        spec = [None] * x.ndim
        if x.ndim >= 1 and x.shape[0] % 4 == 0:
            spec[0] = "data"
        for d in range(x.ndim - 1, 0, -1):
            if x.shape[d] % 2 == 0 and x.shape[d] >= 2:
                spec[d] = "model"
                break
        return NamedSharding(mesh, P(*spec))

    state_sh = jax.tree_util.tree_map(shard_rule, state)
    state_dev = jax.device_put(state, state_sh)
    toks_dev = jax.device_put(toks, NamedSharding(mesh, P("data")))
    with mesh:
        out = jax.jit(step)(state_dev, toks_dev)
    for a, b in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(out.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=2e-2)
    print("OK sharded_step")

    # ---- 2. axis gossip (ppermute in shard_map) == stacked roll ----------
    topo = make_topology("ring", 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16))
    want = gossip_roll({"x": x}, topo)["x"]

    def gossip_fn(xs):
        return gossip_axis({"x": xs}, topo, "data")["x"]

    got = jax.shard_map(gossip_fn, mesh=mesh,
                        in_specs=P("data", None),
                        out_specs=P("data", None))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    print("OK axis_gossip")

    # ---- 3. CD-Adam sharded execution ------------------------------------
    copt = make_optimizer("cd-adam", K=K, eta=1e-3, period=1,
                          compressor="sign")
    cstate = copt.init(stacked)
    cref = jax.jit(lambda s: copt.step(s, jax.vmap(jax.grad(api.loss))(
        s.params, {"tokens": toks})))(cstate)
    cstate_sh = jax.tree_util.tree_map(shard_rule, cstate)
    cstate_dev = jax.device_put(cstate, cstate_sh)
    with mesh:
        cout = jax.jit(lambda s: copt.step(
            s, jax.vmap(jax.grad(api.loss))(
                s.params, {"tokens": toks_dev})))(cstate_dev)
    for a, b in zip(jax.tree_util.tree_leaves(cref.params),
                    jax.tree_util.tree_leaves(cout.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=2e-2)
    print("OK cdadam_sharded")
""")


@pytest.mark.slow
def test_multidevice_execution():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    for marker in ("OK sharded_step", "OK axis_gossip", "OK cdadam_sharded"):
        assert marker in proc.stdout, (marker, proc.stdout[-2000:])


_PACKED_AXIS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np

    assert jax.device_count() == 8, jax.device_count()
    from repro.core import make_optimizer
    from repro.kernels import pack as packing

    K = 8
    mesh = jax.make_mesh((K,), ("worker",))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {
        "w": jax.random.normal(ks[0], (K, 13, 7)),
        "b": jax.random.normal(ks[1], (K, 5)),
        "nest": {"u": jax.random.normal(ks[2], (K, 3, 11, 2))},
    }

    for kind in ("d-adam", "cd-adam"):
        # three runtimes, one trajectory: reference (pytree math),
        # single-device packed, and the packed state sharded one worker
        # per mesh slot (shard_map + ppermute gossip).
        ref = make_optimizer(kind, K=K, eta=1e-2, period=2,
                             weight_decay=0.01)
        pal = make_optimizer(kind, K=K, eta=1e-2, period=2,
                             weight_decay=0.01, backend="pallas")
        axs = make_optimizer(kind, K=K, eta=1e-2, period=2,
                             weight_decay=0.01, backend="pallas",
                             comm="axis", mesh=mesh)
        cp = lambda: jax.tree_util.tree_map(jnp.copy, params)
        s_ref, s_pal, s_axs = ref.init(cp()), pal.init(cp()), axs.init(cp())
        # the sharded state really is one (1, rows, 128) block per device
        assert {sh.data.shape for sh in s_axs.buf.addressable_shards} \\
            == {(1,) + s_axs.buf.shape[1:]}
        step_ref = jax.jit(lambda s, g: ref.step(s, g))
        step_pal = jax.jit(lambda s, g: pal.step(s, g))
        step_axs = jax.jit(lambda s, g: axs.step(s, g))
        for t in range(4):
            g = jax.tree_util.tree_map(
                lambda x: 0.5 * x + 0.01 * (t + 1), ref.params_of(s_ref))
            gbuf = packing.pack(g, s_pal.spec, dtype=s_pal.buf.dtype)
            s_ref = step_ref(s_ref, g)
            s_pal = step_pal(s_pal, gbuf)
            s_axs = step_axs(s_axs, gbuf)
        leaves = lambda o, s: jax.tree_util.tree_leaves(o.params_of(s))
        for a, b in zip(leaves(pal, s_pal), leaves(axs, s_axs)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)
        for a, b in zip(leaves(ref, s_ref), leaves(axs, s_axs)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)
        print(f"OK packed_axis_{kind}")
""")


@pytest.mark.slow
def test_packed_axis_matches_packed_and_reference():
    """Tentpole pin: shard_map-sharded backend='pallas' D-Adam and CD-Adam
    steps == the single-device packed step == the reference backend, under
    8 forced host devices (one worker per device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _PACKED_AXIS_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=1200,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    for marker in ("OK packed_axis_d-adam", "OK packed_axis_cd-adam"):
        assert marker in proc.stdout, (marker, proc.stdout[-2000:])
