"""``models.common.lookup`` (``kernels.pack.take_rows``): ``table[ids]``
for a plain table, and for a narrow leaf of a packed buffer inside
``kernels.pack.worker_map`` the same values and gradients read from the
buffer's lane-dense rows.

The packed cases differentiate w.r.t. the whole buffer, as the packed
gradient pipeline does, against ``table[ids]`` on the same views. Values
match exactly; gradients up to float32 round-off, as repeated ids'
cotangents may be summed in another order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import op_scopes
from repro.core import make_optimizer
from repro.kernels import pack as packing
from repro.models import common
from repro.models.deepfm import (deepfm_loss, init_deepfm, init_widedeep,
                                 widedeep_loss)
from repro.train.grad import make_grad_pipeline
from repro.train.loop import DecentralizedTrainer

K = 2


def _ids(n: int, e: int, key) -> jax.Array:
    """(K, 96) ids: the first and last rows, rows whose E elements
    straddle two buffer rows, one row repeated many times, random rows,
    and ids out of range on both sides."""
    straddle = [i for i in range(n) if (i * e) % 128 > 128 - e][:8]
    fixed = jnp.array([0, n - 1, n - 1, 0] + straddle + [3] * 20
                      + [-1, -n, n, n + 7, -n - 3], jnp.int32)
    rest = jax.random.randint(key, (K, 96 - fixed.size), 0, n)
    return jnp.concatenate([jnp.broadcast_to(fixed, (K, fixed.size)), rest],
                           axis=1)


def _packed(n: int, e: int, block_rows: int):
    """A packed stacked tree with the table between two other leaves, so
    its rows start inside the buffer."""
    ks = jax.random.split(jax.random.PRNGKey(e), 3)
    tree = {"a": jax.random.normal(ks[0], (K, 5)),
            "t": jax.random.normal(ks[1], (K, n) + ((e,) if e > 1 else ())),
            "z": jax.random.normal(ks[2], (K, 3, 7))}
    spec = packing.make_spec(tree, stacked=True, block_rows=block_rows,
                             leaf_align=True)
    return packing.pack(tree, spec), spec


def _value_and_grad(buf, spec, ids, ct, take):
    def total(bf):
        def loss(p, i, c):
            return jnp.sum(take(p["t"], i) * c) + jnp.sum(p["z"])
        params = packing.unpack(bf, spec)
        return jnp.sum(packing.worker_map(loss, bf, spec, params, ids, ct))
    return jax.jit(jax.value_and_grad(total))(buf)


# n * e fills its segment of 8-row tiles exactly for (512, 10) and
# (1024, 1), so the last rows' windows are clamped to the segment's end
# rows 128 wide or wider (a whole lane, or three buffer rows' span for
# 130 and 200) keep table[ids]
@pytest.mark.parametrize("n,e", [(1024, 1), (777, 1), (512, 10), (1000, 10),
                                 (300, 16), (50, 128), (90, 127), (64, 130),
                                 (40, 200)])
def test_packed_lookup_matches_take(n, e):
    buf, spec = _packed(n, e, block_rows=8)
    ids = _ids(n, e, jax.random.PRNGKey(n))
    ct = jax.random.normal(jax.random.PRNGKey(1),
                           ids.shape + ((e,) if e > 1 else ()))
    v, g = _value_and_grad(buf, spec, ids, ct, common.lookup)
    v_ref, g_ref = _value_and_grad(buf, spec, ids, ct, lambda t, i: t[i])
    np.testing.assert_array_equal(v, v_ref)
    np.testing.assert_allclose(g, g_ref, rtol=1e-6, atol=1e-6)


def _hot_ids(n: int, e: int, key) -> jax.Array:
    """(K, 3096) ids: :func:`_ids`, and three rows hit a thousand times
    each (as the numeric fields' few buckets are), one of them the last
    and one, where the table has one, straddling two buffer rows."""
    straddle = [i for i in range(n) if (i * e) % 128 > 128 - e][:1]
    hot = jnp.array(([n - 1, 1] + straddle + [2])[:3] * 1000, jnp.int32)
    return jnp.concatenate(
        [_ids(n, e, key), jnp.broadcast_to(hot, (K, hot.size))], axis=1)


# (12, 10) and (25, 10) fill one and exactly two buffer rows of 1-row
# tiles, so a window cannot be two rows or is clamped to the block's two;
# widths 2 and 64 divide a lane, 3, 10 and 127 do not
@pytest.mark.parametrize("n,e,block_rows", [(12, 10, 1), (25, 10, 1),
                                            (700, 2, 8), (500, 3, 1),
                                            (4096, 10, 8), (50, 64, 8),
                                            (90, 127, 1)])
def test_packed_lookup_gradient_sums_repeats_exactly(n, e, block_rows):
    """With cotangents of small integers every sum is exact in float32,
    whatever its order, so the gradient equals ``table[ids]``'s to the
    bit: each float lands once at its own lane, repeated ids included."""
    buf, spec = _packed(n, e, block_rows)
    ids = _hot_ids(n, e, jax.random.PRNGKey(n))
    ct = jax.random.randint(jax.random.PRNGKey(2), ids.shape + (e,), -4,
                            5).astype(jnp.float32)
    v, g = _value_and_grad(buf, spec, ids, ct, common.lookup)
    v_ref, g_ref = _value_and_grad(buf, spec, ids, ct, lambda t, i: t[i])
    np.testing.assert_array_equal(v, v_ref)
    np.testing.assert_array_equal(g, g_ref)


@pytest.mark.parametrize("e", [1, 10, 127, 128, 200])
def test_packed_lookup_reads_the_rows_of_the_buffer(e, monkeypatch):
    """Inside worker_map the lookup of rows narrower than a lane takes the
    lane-dense path, which never reads the leaf's own view; wider rows
    keep table[ids]."""
    calls = []
    lane_rows = packing._lane_rows
    monkeypatch.setattr(packing, "_lane_rows",
                        lambda *a: calls.append(a[3]) or lane_rows(*a))
    buf, spec = _packed(100, e, block_rows=8)

    def loss(p, i):
        return jnp.sum(common.lookup(p["t"], i))

    out = packing.worker_map(loss, buf, spec, packing.unpack(buf, spec),
                             jnp.zeros((K, 4), jnp.int32))
    assert calls == ([e] if e < packing.LANE else []) and out.shape == (K,)


@pytest.mark.parametrize("shape", [(60,), (60, 10), (60, 128)])
def test_plain_lookup_is_take_under_vmap_and_jit(shape):
    table = jax.random.normal(jax.random.PRNGKey(0), (K,) + shape)
    ids = jnp.array([[0, 59, 59, -1, 70, 3]] * K, jnp.int32)
    ct = jax.random.normal(jax.random.PRNGKey(1), ids.shape + shape[1:])

    def f(take):
        def one(t, i, c):
            return jnp.sum(take(t, i) * c)
        return jax.jit(jax.vmap(jax.value_and_grad(one)))(table, ids, ct)

    (v, g), (v_ref, g_ref) = f(common.lookup), f(lambda t, i: t[i])
    np.testing.assert_array_equal(v, v_ref)
    np.testing.assert_array_equal(g, g_ref)


def _ctr_batch(key, n: int, fields: int, b: int):
    k1, k2 = jax.random.split(key)
    # a few hot ids, so most of a worker's ids repeat
    ids = jax.random.randint(k1, (K, b, fields), 0, n) % jnp.array(
        [3, 40, n] * (fields // 3) + [n] * (fields % 3))
    return {"feat_ids": ids.astype(jnp.int32),
            "label": jax.random.bernoulli(k2, 0.3, (K, b)).astype(jnp.int32)}


@pytest.mark.parametrize("damping", [0, 2], ids=["plain", "damped"])
@pytest.mark.parametrize("model", ["deepfm", "widedeep"])
def test_packed_ctr_gradients_match_reference(model, damping):
    """The packed pipeline's losses and per-leaf gradients equal the
    reference backend's within float32 round-off."""
    init, loss = {"deepfm": (init_deepfm, deepfm_loss),
                  "widedeep": (init_widedeep, widedeep_loss)}[model]
    n, fields = 700, 6
    params = jax.vmap(lambda k: init(k, n, fields, 10, (16, 16)))(
        jax.random.split(jax.random.PRNGKey(0), K))
    batch = _ctr_batch(jax.random.PRNGKey(1), n, fields, 32)
    out = {}
    for backend in ("reference", "pallas"):
        opt = make_optimizer("d-adam", K=K, eta=1e-3, backend=backend)
        pipe = make_grad_pipeline(loss, opt, damping_chunks=damping)
        args = (opt.init(params), batch)
        if damping:
            args += (jnp.array([2, 1], jnp.int32),)
        losses, grads = jax.jit(pipe.value_and_grad)(*args)
        if backend == "pallas":
            grads = packing.unpack(grads, args[0].spec)
        out[backend] = (losses, grads)
    (l_ref, g_ref), (l_pk, g_pk) = out["reference"], out["pallas"]
    np.testing.assert_allclose(l_pk, l_ref, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_pk),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_compiled_step_scopes_the_lookup_inside_the_model():
    """The packed DeepFM step's lookup ops carry the ``lookup`` scope
    under ``model``, forward and backward: ``lookup_ms`` reads them, and
    ``fwd_ms`` and ``bwd_ms`` count them as the model's."""
    opt = make_optimizer("d-adam", K=K, eta=1e-3, period=2, backend="pallas")
    trainer = DecentralizedTrainer(deepfm_loss, opt)
    state = trainer.init(init_deepfm(jax.random.PRNGKey(0), 300, 6, 10,
                                     (16,)))
    batch = _ctr_batch(jax.random.PRNGKey(1), 300, 6, 8)
    text = trainer.lower_step(state, batch).compile().as_text()
    paths = [n.split(";")[0].split("/") for n in op_scopes(text).values()]
    lookups = [p for p in paths if any(q.endswith("lookup") for q in p)]
    assert lookups and all(
        any("model" in q for q in p[:p.index("lookup")]) for p in lookups)
    assert any(any("transpose" in q for q in p) for p in lookups)
    assert any(not any("transpose" in q for q in p) for p in lookups)
