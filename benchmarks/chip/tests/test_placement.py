"""Where the reference runs: worker k on ``devices[k % len(devices)]``.

Placement changes no reading: the four-chip cell's reference on the four
host devices reads what it reads with every worker on the default device,
for the sound reference, the control and both planted faults, under
CD-Adam and under a D-Adam copy of the cell. Each worker's gradient runs
on its own device. A worker's norms, taken alone on its device, are the
rows of the norms of the K-stacked trees that the program's readings take.
"""
from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, spec, traffic
from reference import cdadam, dadam
from reference.weights import seed_key
from small import small_cell

SEED = 2 ** 34 + 29
CELL = "deepfm-criteo.cdadam-k4-axis"
VARIANTS = {"sound": {}, "control": {"mode": "control"},
            "half": {"fault": "half"}, "no_gossip": {"fault": "no_gossip"}}


def _cell(optimizer: str) -> spec.Cell:
    """The small four-chip cell, or its copy under D-Adam."""
    cell = small_cell(CELL)
    if optimizer == "d-adam":
        o = {k: v for k, v in cell.traffic["optimizer"].items()
             if k not in ("gamma", "compressor")}
        cell.traffic = dict(cell.traffic, optimizer=dict(o, name="d-adam"))
    return cell


def _params(cell):
    return spec.family(cell.config["family"]).make_params(cell.config)(
        seed_key(SEED))


def _readings(cell: spec.Cell, devices, **kw) -> check.Readings:
    fam = spec.family(cell.config["family"])
    reference = types.SimpleNamespace(make_params=fam.make_params(cell.config),
                                      ref_loss=fam.ref_loss(cell.config))
    if kw.get("mode") == "control":
        kw["mode"] = check.control_mode(cell.config)
    key_w, key_d = jax.random.split(seed_key(SEED))
    batches = list(traffic.make_pool(key_d, cell.config, cell.traffic)
                   )[:check.steps_checked(cell.traffic)]
    return check.reference_readings(reference, cell.config, cell.traffic,
                                    key_w, batches, devices=devices, **kw)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("optimizer", ["cd-adam", "d-adam"])
def test_readings_do_not_depend_on_placement(optimizer, variant):
    cell = _cell(optimizer)
    assert cell.traffic["optimizer"]["workers"] == 4
    kw = VARIANTS[variant]
    here = _readings(cell, None, **kw)
    spread = _readings(cell, jax.devices()[:4], **kw)
    assert here.losses == spread.losses
    np.testing.assert_array_equal(here.change, spread.change)
    np.testing.assert_allclose(here.grad, spread.grad, rtol=1e-7, atol=0)
    assert here.grad.shape == here.change.shape == (
        len(jax.tree_util.tree_leaves(_params(cell))), 4)


def _toy_loss(p, b):
    return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)


@pytest.mark.parametrize("n_devices", [4, 1])
@pytest.mark.parametrize("optimizer", ["d-adam", "cd-adam"])
def test_worker_k_runs_on_device_k(optimizer, n_devices):
    K, steps = 4, 4
    devices = jax.devices()[:n_devices]
    want = [devices[k % n_devices] for k in range(K)]
    hp = dict(eta=1e-2, beta1=0.9, beta2=0.999, tau=1e-6, period=2)
    keys = jax.random.split(jax.random.PRNGKey(7), 2 * K * steps)
    batches = [[{"x": jax.random.normal(keys[2 * (K * t + k)], (16, 8)),
                 "y": jax.random.normal(keys[2 * (K * t + k) + 1], (16,))}
                for k in range(K)] for t in range(steps)]
    params0 = {"w": jnp.zeros((8,)), "b": jnp.zeros(())}
    inner = jax.jit(jax.value_and_grad(_toy_loss))
    ran = []

    def grad_fn(p, b):
        devs = {d for x in jax.tree_util.tree_leaves((p, b))
                for d in x.devices()}
        out = inner(p, b)
        devs |= {d for x in jax.tree_util.tree_leaves(out)
                 for d in x.devices()}
        ran.append(devs)
        return out

    kw = dict(first_norms=functools.partial(check.worker_norms, K=K),
              devices=devices)
    if optimizer == "d-adam":
        _, first, xs = dadam.run(params0, batches, grad_fn, hp, **kw)
    else:
        _, first, xs = cdadam.run(params0, batches, grad_fn, hp, gamma=0.4,
                                  **kw)
    assert ran == [{want[k]} for _ in range(steps) for k in range(K)]
    assert [f.shape for f in first] == [(2,)] * K
    for k in range(K):
        assert {d for x in jax.tree_util.tree_leaves(xs[k])
                for d in x.devices()} == {want[k]}


def test_worker_norms_are_rows_of_the_stacked_norms():
    cell = _cell("cd-adam")
    p0 = _params(cell)
    leaves, tree = jax.tree_util.tree_flatten(p0)
    keys = jax.random.split(jax.random.PRNGKey(3), 4 * len(leaves))
    xs = [jax.tree_util.tree_unflatten(tree, [
        x + 1e-3 * jax.random.normal(keys[len(leaves) * k + i], x.shape)
        for i, x in enumerate(leaves)]) for k in range(4)]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *xs)
    worker = [jax.device_put(x, d) for x, d in zip(xs, jax.devices()[:4])]
    np.testing.assert_array_equal(
        np.stack([check.worker_norms(x, k, 4) for k, x in enumerate(worker)],
                 axis=1),
        check.stacked_norms(stacked))
    np.testing.assert_array_equal(
        np.stack([check.worker_norms(x, k, 4, jax.device_put(p0, d))
                  for k, (x, d) in enumerate(zip(worker, jax.devices()[:4]))],
                 axis=1),
        check.change_norms(stacked, p0))
