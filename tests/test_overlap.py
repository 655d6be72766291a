"""The comm/compute-overlap machinery (``overlap=True``) and its pins.

Parity pins (the acceptance bar for the overlap wiring):

* CD-Adam ``overlap=True`` is BITWISE the explicit ``staleness=1`` path
  with an all-ones delay table — overlap IS the tau=1 wire schedule,
  over a 10-step trainer run, both backends, period 1 and 3;
* D-Adam overlap implements the uniform delay-1 schedule exactly: round
  r mixes the payloads issued at round r-1 (pure-gossip trace pinned
  against a hand-rolled two-round expectation), and the COLD first round
  is bitwise the synchronous step;
* the fused ``gossip_adam_mix`` kernel is BITWISE the two-pass
  ``fused_adam`` -> ``gossip_mix`` composition across the topology zoo
  (incl. bf16 moments, tau=0, weight decay), and the D-Adam stacked
  dispatch through it changes nothing vs. the two-pass step.

Behavioral pins: overlap composes with time-varying topology schedules
and elastic resize (cold buffers after a membership change), config
validation rejects the ambiguous/unsupported combinations, and
``repro.launch.env`` keeps its append-never-clobber contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cdadam, dadam, make_optimizer
from repro.train.loop import DecentralizedTrainer

K = 8


def loss_fn(p, batch):
    pred = batch["x"] @ p["w"] + p["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def init_params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w": jax.random.normal(k1, (6, 1)) * 0.3,
            "b": jax.random.normal(k2, (1,)) * 0.1}


def batches(K, seed=0):
    key = jax.random.PRNGKey(seed)
    while True:
        key, k1 = jax.random.split(key)
        x = jax.random.normal(k1, (K, 8, 6))
        y = jnp.sum(x, axis=-1, keepdims=True)
        yield {"x": x, "y": y}


def params_equal(a, b):
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    return all(bool((x == y).all()) for x, y in zip(flat_a, flat_b))


def fit_params(opt, steps=10, seed=0):
    tr = DecentralizedTrainer(loss_fn, opt)
    state = tr.init(init_params())
    state, _ = tr.fit(state, batches(opt.K, seed), steps, log_every=steps)
    return tr.opt.params_of(state)


def all_late_seed(K, deg, tries=512):
    """A straggler seed whose tau=1 delay table is all-ones — the exact
    wire schedule overlap implements. Deterministic, found by search so
    the test never depends on a magic constant staying lucky."""
    for seed in range(tries):
        cfg = cdadam.CDAdamConfig(eta=1e-2, staleness=1,
                                  straggler_rate=0.97, straggler_seed=seed)
        if (cdadam._payload_delays(cfg, K, deg) == 1).all():
            return seed
    raise AssertionError(f"no all-late seed in {tries} tries")


# ------------------------- CD-Adam: overlap == tau=1 -------------------------


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("period", [1, 3])
def test_cdadam_overlap_is_bitwise_tau1(backend, period):
    """overlap=True must be bit-for-bit the explicit staleness=1 path
    when every edge is exactly one round late — the tau=1 wire schedule
    is the overlap schedule, not an approximation of it."""
    kw = dict(eta=1e-2, period=period, backend=backend, topology="ring")
    seed = all_late_seed(K, deg=2)
    p_overlap = fit_params(make_optimizer("cd-adam", K, overlap=True, **kw))
    p_tau1 = fit_params(make_optimizer("cd-adam", K, staleness=1,
                                       straggler_rate=0.97,
                                       straggler_seed=seed, **kw))
    assert params_equal(p_overlap, p_tau1)


def test_cdadam_overlap_delay_table_is_all_ones():
    """The table the rings consume under overlap: every edge delayed by
    exactly one round, regardless of straggler knobs."""
    cfg = cdadam.CDAdamConfig(eta=1e-2, overlap=True)
    assert (cdadam._payload_delays(cfg, K, 2) == 1).all()
    assert cdadam._wire_tau(cfg) == 1


# ----------------------- D-Adam: delay-1 semantics ---------------------------


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_dadam_overlap_first_round_is_synchronous(backend):
    """Cold buffers fold the fresh payload, so a run containing exactly
    one comm round mixes the same payloads as the non-overlap run. The
    comparison is allclose, not bitwise: routing payloads through the
    cold-mask select perturbs XLA's FMA fusion by ~1 ulp (the same
    reason gossip_shift_stale short-circuits tau=0 to the literal
    synchronous mix)."""
    kw = dict(eta=1e-2, period=1, backend=backend, topology="ring")
    p_plain = fit_params(make_optimizer("d-adam", K, **kw), steps=1)
    p_over = fit_params(make_optimizer("d-adam", K, overlap=True, **kw),
                        steps=1)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: jnp.allclose(a, b, rtol=1e-6, atol=1e-7),
        p_plain, p_over))


def test_dadam_overlap_mixes_previous_round_payloads():
    """The delay-1 pin: with zero grads (Adam moves nothing) and period
    1, round 2 must mix the SELF params of round 1 with the neighbor
    payloads ISSUED at round 1 — i.e. shifts of the round-0 params."""
    opt = make_optimizer("d-adam", K, eta=1e-2, period=1, overlap=True,
                         topology="ring", backend="reference")
    topo = opt.topo
    p0 = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.PRNGKey(3), (K,) + x.shape),
        init_params())
    state = opt.init(p0)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    step = jax.jit(opt.step)
    state = step(state, zeros)
    p1 = opt.params_of(state)
    state = step(state, zeros)
    p2 = opt.params_of(state)

    def mix(x, nbrs):
        acc = topo.self_weight * x.astype(jnp.float32)
        for w, nb in zip(topo.offset_weights, nbrs):
            acc = acc + w * nb.astype(jnp.float32)
        return acc.astype(x.dtype)

    def shifts(p):
        return [jax.tree_util.tree_map(
            lambda x, s=s: dadam.shift_worker(x, s, K, None), p)
            for s in topo.offsets]

    def close(a, b, tol=1e-6):
        return jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda x, y: jnp.allclose(x, y, rtol=tol, atol=tol), a, b))

    # round 1 is cold -> synchronous mix of p0 (up to jit FMA fusion)
    want1 = jax.tree_util.tree_map(
        lambda x, *nbrs: mix(x, nbrs), p0, *shifts(p0))
    assert close(p1, want1)
    # round 2 mixes p1 with the shifts issued at round 1 (of p0!), not
    # fresh shifts of p1 — that is the whole point of the eager schedule
    want2 = jax.tree_util.tree_map(
        lambda x, *nbrs: mix(x, nbrs), p1, *shifts(p0))
    assert close(p2, want2)
    # negative control: the synchronous schedule (fresh shifts of p1)
    # is measurably different, so the pin above really discriminates
    sync2 = jax.tree_util.tree_map(
        lambda x, *nbrs: mix(x, nbrs), p1, *shifts(p1))
    assert not close(p2, sync2)


@pytest.mark.parametrize("kind", ["d-adam", "cd-adam"])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_overlap_consensus_contracts(kind, backend):
    """Pure gossip rounds under the delay-1 schedule: consensus error
    must still contract by orders of magnitude — one round of payload
    lag must not destabilize the mixing contraction."""
    opt = make_optimizer(kind, K, topology="ring", eta=1e-2, period=1,
                         backend=backend, overlap=True)
    p0 = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (K,) + x.shape).copy() +
        jax.random.normal(jax.random.PRNGKey(1), (K,) + x.shape),
        init_params())
    state = opt.init(p0)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    e0 = float(dadam.consensus_error(opt.params_of(state)))
    step = jax.jit(opt.step)
    for _ in range(60):
        state = step(state, zeros)
    e1 = float(dadam.consensus_error(opt.params_of(state)))
    assert np.isfinite(e1)
    tol = 1e-4 if kind == "d-adam" else 5e-1
    assert e1 < tol * max(e0, 1.0)


@pytest.mark.skipif(jax.device_count() < K,
                    reason="comm='axis' needs one device per worker "
                           "(tier1.sh forces 8 host devices)")
@pytest.mark.parametrize("kind", ["d-adam", "cd-adam"])
def test_overlap_axis_matches_stacked(kind):
    """The sharded comm='axis' execution of the overlap schedule must
    track the stacked simulation."""
    from repro.launch.mesh import make_worker_mesh
    mesh = make_worker_mesh(K)
    kw = dict(eta=1e-2, period=2, topology="ring", overlap=True,
              backend="pallas")
    p_stacked = fit_params(make_optimizer(kind, K, **kw))
    p_axis = fit_params(make_optimizer(kind, K, comm="axis", mesh=mesh,
                                       **kw))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: jnp.allclose(a, b, atol=1e-6), p_stacked,
        jax.device_get(p_axis)))


# ----------------------- fused gossip+Adam kernel ----------------------------


ZOO = [("ring", 8), ("torus", 8), ("exponential", 8),
       ("fully_connected", 8)]


@pytest.mark.parametrize("name,zk", ZOO)
@pytest.mark.parametrize("weight_decay", [0.0, 1e-4])
def test_gossip_adam_mix_bitwise_two_pass(name, zk, weight_decay):
    """The single-VMEM-pass kernel must match fused_adam followed by
    gossip_mix: m and v bit for bit, and the mixed params within the
    few-ulp bound of :func:`_assert_mix_close` (the neighbor half-steps
    it recomputes in-VMEM round through the param dtype like the
    two-pass composition's HBM round-trip)."""
    from repro.core.topology import make_topology
    from repro.kernels import ops

    topo = make_topology(name, zk)
    rows = 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    p = jax.random.normal(ks[0], (zk, rows, 128), jnp.float32)
    g = jax.random.normal(ks[1], (zk, rows, 128), jnp.float32) * 0.1
    m = jax.random.normal(ks[2], (zk, rows, 128), jnp.float32) * 0.01
    v = jnp.abs(jax.random.normal(ks[3], (zk, rows, 128), jnp.float32)
                ) * 0.01
    kw = dict(eta=1e-2, beta1=0.9, beta2=0.999, tau=1e-6,
              weight_decay=weight_decay)
    p2, m2, v2 = ops.fused_adam(p, g, m, v, **kw)
    want = ops.gossip_mix(p2, topo.offsets, topo.offset_weights,
                          topo.self_weight, block_rows=rows)
    got_p, got_m, got_v = ops.gossip_adam_mix(
        p, g, m, v, topo.offsets, topo.offset_weights, topo.self_weight,
        block_rows=rows, **kw)
    _assert_mix_close(got_p, want, p2, topo)
    assert bool((got_m == m2).all())
    assert bool((got_v == v2).all())


def _assert_mix_close(got_p, want, p2, topo):
    """Fused vs two-pass params: |got - want| <= 2 eps * sum_j |w_j p2_j|.

    The fused kernel recomputes every half-step in a different compiled
    body than fused_adam, and the compiler may contract the Adam update's
    multiply-adds differently there. Each half-step p2_j then differs by
    at most one rounding (<= eps |p2_j|), which the mix weights by w_j;
    the factor 2 covers the mix's own final rounding. Elements of m and v
    never pass through the mix and stay bit for bit."""
    from repro.kernels import ops

    mag = ops.gossip_mix(jnp.abs(p2), topo.offsets,
                         [abs(w) for w in topo.offset_weights],
                         abs(topo.self_weight), block_rows=p2.shape[1])
    eps = float(jnp.finfo(jnp.float32).eps)
    err = np.abs(np.asarray(got_p) - np.asarray(want))
    bound = 2 * eps * np.asarray(mag)
    assert (err <= bound).all(), float((err / bound).max())


def test_gossip_adam_mix_bf16_moments_tau0():
    """bf16 moment buffers + the tau=0 rsqrt step variant round-trip the
    kernel's internal f32 math like the two-pass path: m and v bit for
    bit, params within the bound of :func:`_assert_mix_close`."""
    from repro.core.topology import make_topology
    from repro.kernels import ops

    topo = make_topology("ring", 8)
    rows = 8
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    p = jax.random.normal(ks[0], (8, rows, 128), jnp.float32)
    g = jax.random.normal(ks[1], (8, rows, 128), jnp.float32) * 0.1
    m = (jax.random.normal(ks[2], (8, rows, 128)) * 0.01).astype(
        jnp.bfloat16)
    v = jnp.abs(jax.random.normal(ks[3], (8, rows, 128)) * 0.01).astype(
        jnp.bfloat16)
    kw = dict(eta=1e-2, tau=0.0)
    p2, m2, v2 = ops.fused_adam(p, g, m, v, **kw)
    want = ops.gossip_mix(p2, topo.offsets, topo.offset_weights,
                          topo.self_weight, block_rows=rows)
    got_p, got_m, got_v = ops.gossip_adam_mix(
        p, g, m, v, topo.offsets, topo.offset_weights, topo.self_weight,
        block_rows=rows, **kw)
    assert got_m.dtype == jnp.bfloat16 and got_v.dtype == jnp.bfloat16
    _assert_mix_close(got_p, want, p2, topo)
    assert bool((got_m == m2).all())
    assert bool((got_v == v2).all())


def test_gossip_adam_mix_degree_cap():
    from repro.kernels import gossip as gk

    p = jnp.zeros((16, 8, 128))
    too_many = tuple(range(1, gk.MAX_GOSSIP_ADAM_DEGREE + 2))
    with pytest.raises(ValueError, match="degree"):
        gk.gossip_adam_mix(p, p, p, p, too_many,
                           (0.05,) * len(too_many), 0.2, eta=1e-2,
                           block_rows=8, interpret=True)


@pytest.mark.parametrize("period", [1, 3])
def test_dadam_stacked_dispatch_through_fused_kernel(period, monkeypatch):
    """The D-Adam comm='stacked' pallas step dispatches through
    gossip_adam_mix when eligible; forcing the two-pass dispatch instead
    must not change a single bit of a 10-step run."""
    kw = dict(eta=1e-2, period=period, backend="pallas", topology="ring")
    opt = make_optimizer("d-adam", K, **kw)
    assert dadam._gossip_adam_eligible(opt.topo, opt.cfg)
    p_fused = fit_params(opt)
    monkeypatch.setattr(dadam, "_gossip_adam_eligible",
                        lambda topo, cfg: False)
    p_two_pass = fit_params(make_optimizer("d-adam", K, **kw))
    assert params_equal(p_fused, p_two_pass)


def test_fused_dispatch_ineligible_under_overlap_and_schedule():
    """Overlap, staleness, and schedules route through the payload-buffer
    machinery — the fused gossip+Adam shortcut must stand down."""
    opt = make_optimizer("d-adam", K, eta=1e-2, backend="pallas",
                         topology="ring", overlap=True)
    assert not dadam._gossip_adam_eligible(opt.topo, opt.cfg)
    opt = make_optimizer("d-adam", K, eta=1e-2, backend="pallas",
                         topology="one-peer-exponential")
    assert not dadam._gossip_adam_eligible(opt.topo, opt.cfg)
    opt = make_optimizer("d-adam", K, eta=1e-2, backend="pallas",
                         topology="ring", staleness=1, straggler_rate=0.1)
    assert not dadam._gossip_adam_eligible(opt.topo, opt.cfg)


# --------------------------- composition pins --------------------------------


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_overlap_with_schedule_runs_and_contracts(backend):
    opt = make_optimizer("d-adam", K, topology="one-peer-exponential",
                         eta=1e-2, period=1, backend=backend, overlap=True)
    p0 = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.PRNGKey(2),
                                    (K,) + x.shape), init_params())
    state = opt.init(p0)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    e0 = float(dadam.consensus_error(opt.params_of(state)))
    step = jax.jit(opt.step)
    for _ in range(40):
        state = step(state, zeros)
    e1 = float(dadam.consensus_error(opt.params_of(state)))
    assert e1 < 1e-3 * max(e0, 1.0)


@pytest.mark.parametrize("kind", ["d-adam", "cd-adam"])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_overlap_elastic_resize(kind, backend):
    """Membership changes under overlap: params/moments carry over, the
    rebuilt payload buffers start COLD (first post-resize round folds
    fresh), and training continues with one recompile."""
    from repro.core import resize_state
    kw = dict(topology="one-peer-exponential", eta=1e-2, period=1,
              backend=backend, overlap=True)
    opt = make_optimizer(kind, K, **kw)
    tr = DecentralizedTrainer(loss_fn, opt)
    state = tr.init(init_params())
    state, _ = tr.fit(state, batches(K), 5, log_every=5)
    p_old = np.asarray(tr.opt.params_of(state)["w"])

    grown = make_optimizer(kind, K + 4, **kw)
    st2 = resize_state(state, grown, strategy="clone")
    p_new = np.asarray(grown.params_of(st2)["w"])
    assert (p_new[:K] == p_old).all()
    assert (p_new[K:] == p_old[:4]).all()

    tr2 = DecentralizedTrainer(loss_fn, grown)
    st2, log = tr2.fit(st2, batches(K + 4), 4, log_every=4)
    assert tr2._step._cache_size() == 1
    assert np.isfinite(log.loss[-1])


# ------------------------------ validation -----------------------------------


def test_overlap_rejects_explicit_staleness():
    with pytest.raises(ValueError, match="tau=1 wire schedule"):
        make_optimizer("d-adam", K, eta=1e-2, overlap=True, staleness=2,
                       straggler_rate=0.1)
    with pytest.raises(ValueError):
        make_optimizer("cd-adam", K, eta=1e-2, overlap=True, staleness=1,
                       straggler_rate=0.1)


def test_overlap_rejects_dense_mixing_and_dpsgd():
    with pytest.raises(ValueError, match="shift lowering"):
        make_optimizer("d-adam", K, eta=1e-2, overlap=True, mixing="dense")
    with pytest.raises(ValueError, match="d-adam / cd-adam"):
        make_optimizer("d-psgd", K, eta=1e-2, overlap=True)


# --------------------------- repro.launch.env --------------------------------


def test_env_appends_never_clobbers():
    from repro.launch import env as lenv
    e = {"XLA_FLAGS": "--xla_foo=1"}
    out = lenv.ensure_xla_flags(["--xla_bar=2"], env=e)
    assert out == "--xla_foo=1 --xla_bar=2"
    assert e["XLA_FLAGS"] == out


def test_env_preset_flag_wins():
    from repro.launch import env as lenv
    e = {"XLA_FLAGS": f"{lenv.HOST_DEVICE_FLAG}=4"}
    assert lenv.ensure_host_devices(16, env=e) == 4
    assert e["XLA_FLAGS"] == f"{lenv.HOST_DEVICE_FLAG}=4"
    e2 = {}
    assert lenv.ensure_host_devices(16, env=e2) == 16
    assert lenv.host_device_count(e2) == 16
    e3 = {"REPRO_HOST_DEVICES": "12"}
    assert lenv.ensure_host_devices(env=e3) == 12


def test_env_async_flags_gated_on_gpu_support():
    from repro.launch import env as lenv
    # forced off: never installed (a CPU-only jaxlib ABORTS on unknown
    # --xla_gpu_* names, so the gate is load-bearing, not cosmetic)
    e = {"REPRO_ASYNC_COLLECTIVES": "0"}
    lenv.setup(8, env=e)
    assert "xla_gpu" not in e["XLA_FLAGS"]
    # forced on: all three flags appended after the host-device flag
    e2 = {"REPRO_ASYNC_COLLECTIVES": "1"}
    lenv.setup(8, env=e2)
    for flag in lenv.ASYNC_COLLECTIVE_FLAGS:
        assert flag in e2["XLA_FLAGS"]
    assert e2["XLA_FLAGS"].startswith(f"{lenv.HOST_DEVICE_FLAG}=8")
    # idempotent: a second setup adds nothing
    before = e2["XLA_FLAGS"]
    lenv.setup(8, env=e2)
    assert e2["XLA_FLAGS"] == before


def test_env_setup_platform_setdefault():
    from repro.launch import env as lenv
    e = {"JAX_PLATFORMS": "tpu", "REPRO_ASYNC_COLLECTIVES": "0"}
    lenv.setup(2, platform="cpu", env=e)
    assert e["JAX_PLATFORMS"] == "tpu"
    e2 = {"REPRO_ASYNC_COLLECTIVES": "0"}
    lenv.setup(2, platform="cpu", env=e2)
    assert e2["JAX_PLATFORMS"] == "cpu"


def test_env_compile_cache_rule():
    """A preset JAX_COMPILATION_CACHE_DIR is left alone; otherwise the
    cache goes to the fixed <checkout>/.jax_cache."""
    import pathlib

    from repro.launch import env as lenv
    e = {"JAX_COMPILATION_CACHE_DIR": "/elsewhere",
         "REPRO_ASYNC_COLLECTIVES": "0"}
    lenv.setup(2, env=e)
    assert e["JAX_COMPILATION_CACHE_DIR"] == "/elsewhere"
    e2 = {"REPRO_ASYNC_COLLECTIVES": "0"}
    lenv.setup(2, env=e2)
    cache = pathlib.Path(e2["JAX_COMPILATION_CACHE_DIR"])
    assert cache == pathlib.Path(lenv.DEFAULT_CACHE_DIR)
    assert cache.name == ".jax_cache"
    assert (cache.parent / "src" / "repro" / "launch" / "env.py").is_file()


def test_env_compile_cache_entries_land_in_set_dir(tmp_path):
    """End to end in a fresh process: setup() keeps the exported cache
    directory, and a compile lands there."""
    import os
    import subprocess
    import sys

    code = (
        "from repro.launch import env; env.setup(1)\n"
        "import jax, jax.numpy as jnp\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    child_env = dict(os.environ, JAX_PLATFORMS="cpu",
                     JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                     PYTHONPATH=os.pathsep.join(
                         [os.path.join(os.path.dirname(__file__), "..",
                                       "src")]
                         + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    out = subprocess.run([sys.executable, "-c", code], env=child_env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir())
