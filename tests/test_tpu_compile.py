"""The optimizer kernels and packed steps compile for a TPU v5e chip.

Interpret-mode tests run the kernel bodies on the CPU and cannot see what
the TPU compiler refuses: block shapes off the (8, 128) tiling, blocked
SMEM operands, more VMEM than a kernel may use, or a program that does
not fit the chip. Here each kernel of the training path is compiled, at
row counts of real llama3.2-1b leaves, for a *described* ``v5e:2x2``
topology — the TPU compiler runs, no chip is attached and nothing
executes. Every compile must contain a ``tpu_custom_call`` (the Mosaic
kernel itself, not its interpreted body).

The topology is described inside a module fixture, never at import, and
the module is one xdist group: one test worker loads the TPU library and
keeps it until it exits. The tests skip only where no TPU compiler
(``libtpu``) is installed.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import make_optimizer
from repro.core.topology import make_topology
from repro.kernels import ops

# llama3.2-1b per-worker leaves as packed (rows, 128) segments
MLP_ROWS = 2048 * 8192 // 128        # one swiglu projection: 131072 rows
EMBED_ROWS = 128256 * 2048 // 128    # the tied embedding: 2052096 rows
NORM = 2048                          # one RMSNorm weight: 2048 elements
NORM_ROWS = 256                      # its leaf-aligned segment (one tile)
K = 2

pytestmark = pytest.mark.xdist_group("tpu_compile")


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache
    # but can never be read back without one: keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


ADAM = dict(eta=1e-3, beta1=0.9, beta2=0.999, tau=1e-6)


@pytest.mark.parametrize("moments", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows", [MLP_ROWS, NORM_ROWS])
def test_fused_adam_compiles(one_chip, moments, rows):
    buf = _sds((K, rows, 128), jnp.float32, one_chip)
    mom = _sds((K, rows, 128), moments, one_chip)
    _compiled_text(lambda p, g, m, v: ops.fused_adam(
        p, g, m, v, interpret=False, **ADAM), buf, buf, mom, mom)


@pytest.mark.parametrize("rows", [MLP_ROWS, NORM_ROWS])
def test_gossip_mix_compiles(one_chip, rows):
    t = make_topology("ring", 4)
    buf = _sds((4, rows, 128), jnp.float32, one_chip)
    _compiled_text(lambda x: ops.gossip_mix(
        x, t.offsets, t.offset_weights, t.self_weight, interpret=False), buf)


@pytest.mark.parametrize("name,k", [("ring", 4), ("fully_connected", 9)],
                         ids=["degree2", "degree8"])
def test_gossip_adam_mix_compiles(one_chip, name, k):
    t = make_topology(name, k)
    buf = _sds((k, MLP_ROWS // 8, 128), jnp.float32, one_chip)
    _compiled_text(lambda p, g, m, v: ops.gossip_adam_mix(
        p, g, m, v, t.offsets, t.offset_weights, t.self_weight,
        interpret=False, **ADAM), buf, buf, buf, buf)


def test_consensus_mix_compiles(one_chip):
    t = make_topology("ring", 4)
    buf = _sds((4, MLP_ROWS, 128), jnp.float32, one_chip)
    _compiled_text(lambda x, hs, h1, h2: ops.consensus_mix(
        x, hs, (h1, h2), t.offset_weights, 0.4, interpret=False),
        buf, buf, buf, buf)


def test_payload_mix_compiles(one_chip):
    t = make_topology("ring", 4)
    buf = _sds((4, MLP_ROWS, 128), jnp.float32, one_chip)
    _compiled_text(lambda x, a, b: ops.payload_mix(
        x, (a, b), t.offset_weights, t.self_weight, interpret=False),
        buf, buf, buf)


@pytest.mark.parametrize("shape", [(K, EMBED_ROWS, 128), (K, MLP_ROWS, 128),
                                   (K, NORM)],
                         ids=["embed", "mlp", "norm"])
def test_sign_compress_stacked_compiles(one_chip, shape):
    x = _sds(shape, jnp.float32, one_chip)
    _compiled_text(lambda a, b: ops.sign_compress_stacked(
        a, b, interpret=False), x, x)


@pytest.mark.parametrize("kind", ["d-adam", "cd-adam"])
def test_packed_step_compiles(one_chip, kind, monkeypatch):
    """The whole packed optimizer step, with the kernels lowered for the
    chip: ``ops`` picks interpret mode from the CPU backend this test runs
    on, so the test steers it to compiled kernels itself."""
    monkeypatch.setattr(ops, "_interpret",
                        lambda override=None: bool(override))
    opt = make_optimizer(kind, K=K, eta=1e-3, period=2, topology="ring",
                         backend="pallas")
    params = {"norm": jax.ShapeDtypeStruct((K, NORM), jnp.float32),
              "w_up": jax.ShapeDtypeStruct((K, 2048, 8192), jnp.float32)}
    state = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(opt.init, params))
    grads = _sds(state.buf.shape, state.buf.dtype, one_chip)
    text = _compiled_text(opt.step, state, grads)
    # period 2: the comm branch's kernels sit beside the local Adam's
    assert text.count("tpu_custom_call") >= 2
