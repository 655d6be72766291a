"""A run with the timed path broken underneath comes out not correct:
once for each fault a training cell can have. A sound run of the same
small cell comes out correct."""
from __future__ import annotations

import json

import pytest

import run
from chipbench.check import _half
from small import cell_names, small_cell

SEED = 2 ** 33 + 17


def _unchanged(step):
    return lambda state, grads: state


def _half_batch(loss):
    import jax
    return lambda p, b: loss(p, jax.tree_util.tree_map(_half, b))


def _run(cell, capsys, **build_kw):
    rc = run.main(["--workload", cell.name, "--seed", str(SEED),
                   "--seconds", "0.5"], require_tpu=False, cell=cell,
                  build_kw=build_kw)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", cell_names())
def test_sound_run_is_correct(name, capsys):
    out = _run(small_cell(name), capsys)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", cell_names())
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "exchange_left_out"])
def test_fault_is_not_correct(name, fault, capsys, monkeypatch):
    kw = {}
    if fault == "state_unchanged":
        kw["step_wrap"] = _unchanged
    elif fault == "half_batch":
        kw["loss_wrap"] = _half_batch
    else:
        # worker k receives its own payload in place of its neighbours':
        # the roll or ppermute of every exchange, and the fused
        # Adam-and-mix kernel of the stacked D-Adam step
        from repro.core import dadam
        from repro.kernels import ops

        def adam_only(p, g, m, v, offsets, weights, self_weight, **hp):
            return ops.fused_adam(p, g, m, v, **{
                k: hp[k] for k in ("eta", "beta1", "beta2", "tau",
                                   "weight_decay")})
        monkeypatch.setattr(ops, "gossip_adam_mix", adam_only)
        monkeypatch.setattr(dadam, "shift_worker",
                            lambda x, s, K, axis_name=None: x)
    out = _run(small_cell(name), capsys, **kw)
    assert not out["correct"], out["checks"]
