"""The launch layer: sizing cuts, in-process entry points, donation and
comm accounting across ``fit`` calls."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import cut_arch, get_arch, sized_arch
from repro.core import make_optimizer
from repro.launch import serve, train
from repro.train import DecentralizedTrainer


class TestCutArch:
    def test_depth_and_vocab_only(self):
        full = get_arch("llama3.2-1b")
        cut, cuts = cut_arch(full, layers=1, vocab=16032)
        m, f = cut.model, full.model
        assert (m.n_layers, m.vocab_size) == (1, 16032)
        for width in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                      "tie_embeddings", "rope_theta"):
            assert getattr(m, width) == getattr(f, width)
        assert cuts == ("n_layers 16 -> 1", "vocab_size 128256 -> 16032")

    def test_no_cut_is_identity(self):
        full = get_arch("llama3.2-1b")
        assert cut_arch(full) == (full, ())
        assert cut_arch(full, layers=16, vocab=128256) == (full, ())

    @pytest.mark.parametrize("kw", [dict(layers=0), dict(layers=17),
                                    dict(vocab=16031), dict(vocab=128257)])
    def test_out_of_range_rejected(self, kw):
        with pytest.raises(ValueError):
            cut_arch(get_arch("llama3.2-1b"), **kw)

    def test_cuts_need_full(self):
        with pytest.raises(ValueError, match="--full"):
            sized_arch("llama3.2-1b", False, 1, None)
        with pytest.raises(SystemExit):
            train.main(["--layers", "1"])


def test_train_main_in_process(capsys):
    run = train.main(["--workers", "2", "--steps", "3", "--batch", "1",
                      "--seq", "8", "--period", "2", "--log-every", "2"])
    assert run.log.step == [1, 3]
    assert all(np.isfinite(run.log.loss))
    assert run.cuts == () and run.n_params > 0 and run.steady_ms > 0
    params = run.trainer.opt.params_of(run.state)
    assert jax.tree_util.tree_leaves(params)[0].shape[0] == 2
    out = capsys.readouterr().out
    assert "cuts: none" in out and "first step" in out


def test_serve_main_in_process():
    run = serve.main(["--requests", "3", "--buckets", "1x8,2x8",
                      "--prompt-len", "8", "--new-tokens", "3"])
    assert len(run.outputs) == 3
    assert all(o.shape == (3,) for o in run.outputs)
    assert all(0 <= int(t) < run.cfg.vocab_size
               for o in run.outputs for t in o)
    # 3 requests -> buckets (2, 8) then (1, 8), each compiled once
    assert run.compile_counts == {"prefill": 2, "decode": 2}


def _toy(K=2, period=2, donate=False):
    opt = make_optimizer("d-adam", K=K, eta=1e-2, period=period)
    tr = DecentralizedTrainer(
        lambda p, b: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2), opt,
        donate=donate)

    def batches():
        t = 0
        while True:
            k = jax.random.fold_in(jax.random.PRNGKey(0), t)
            yield {"x": jax.random.normal(k, (K, 4, 3)),
                   "y": jnp.ones((K, 4, 1))}
            t += 1
    return tr, batches()


def test_donated_step_matches_and_consumes_state():
    tr, it = _toy()
    s0 = tr.init({"w": jnp.zeros((3, 1))})
    want, _ = tr.fit(s0, it, 3)
    trd, itd = _toy(donate=True)
    s0d = trd.init({"w": jnp.zeros((3, 1))})
    got, _ = trd.fit(s0d, itd, 3)
    np.testing.assert_array_equal(np.asarray(got.params["w"]),
                                  np.asarray(want.params["w"]))
    assert s0d.params["w"].is_deleted()


def test_comm_rounds_counted_across_single_step_fits():
    """Communication rounds follow the global step, so fitting one step
    at a time bills the same rounds as one fit over all the steps."""
    tr, it = _toy(period=2)
    s = tr.init({"w": jnp.zeros((3, 1))})
    log = None
    for _ in range(4):
        s, log = tr.fit(s, it, 1, log=log)
    tr2, it2 = _toy(period=2)
    _, log2 = tr2.fit(tr2.init({"w": jnp.zeros((3, 1))}), it2, 4)
    assert log.comm_rounds_total == log2.comm_rounds_total == 2
    assert log.comm_mb[-1] == pytest.approx(log2.comm_mb[-1])
    assert log.comm_mb[-1] > 0
