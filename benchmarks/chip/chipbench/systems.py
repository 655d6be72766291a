"""The system under test, built from a cell's configuration and traffic
files: the program's model loss, its optimizer and its
``DecentralizedTrainer``, as a user of the program builds them. Also the
configuration's plain reference loss and its model FLOPs per step.

This is the one module of the benchmark that imports the program.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Optional

import jax

from reference import deepfm as ref_deepfm
from reference import transformer as ref_lm
from reference import weights
from chipbench.traffic import field_rows


def lm_sizes(config: dict) -> dict:
    """The sizes of an ``lm`` configuration file (Hugging Face keys)."""
    d, H = config["hidden_size"], config["num_attention_heads"]
    return {"n_layers": config["num_hidden_layers"], "d_model": d,
            "n_heads": H, "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config.get("head_dim", d // H),
            "d_ff": config["intermediate_size"],
            "vocab_size": config["vocab_size"],
            "rope_theta": float(config["rope_theta"]),
            "norm_eps": float(config["rms_norm_eps"])}


def model_flops_per_example(config: dict, traffic: dict) -> float:
    """Forward and backward FLOPs of one worker's example as the model
    requires them: 6 per matmul parameter per token (the LM head included,
    the embedding gather not), plus causal attention; for DeepFM, 6 per
    MLP parameter and per active FM and linear weight. Nothing recomputed
    and no optimizer arithmetic is counted."""
    if config["family"] == "lm":
        c = lm_sizes(config)
        d, hd, S = c["d_model"], c["head_dim"], traffic["seq_len"]
        per_layer = (d * hd * (c["n_heads"] + 2 * c["n_kv_heads"])
                     + c["n_heads"] * hd * d + 3 * d * c["d_ff"])
        n = c["n_layers"] * per_layer + d * c["vocab_size"]
        # QK^T and PV: 2 * 2 * S^2 * H * hd forward, half of it causal
        attn = c["n_layers"] * 2 * S * S * c["n_heads"] * hd
        return 6.0 * n * S + 3.0 * attn
    fields = config["n_numeric"] + len(config["table_rows"])
    E, widths = config["embed_dim"], list(config["hidden"]) + [1]
    mlp, d_in = 0, fields * E
    for h in widths:
        mlp += d_in * h + h
        d_in = h
    return 6.0 * (mlp + fields * E + fields)


@dataclasses.dataclass
class System:
    trainer: Any
    make_params: Callable[[jax.Array], Any]
    ref_loss: Callable[..., jax.Array]
    precision: Optional[str]
    flops_per_step: float        # model FLOPs of one step, all workers

    def context(self):
        """The matmul precision the configuration states, around every
        trace of the program's step."""
        if self.precision is None:
            return contextlib.nullcontext()
        return jax.default_matmul_precision(self.precision)


def build(config: dict, traffic: dict, *, mesh: Any = None,
          loss_wrap: Optional[Callable] = None,
          step_wrap: Optional[Callable] = None) -> System:
    """``loss_wrap(loss) -> loss`` and ``step_wrap(opt.step) -> step``
    plant a fault in the program's model loss or optimizer step (the
    harness's own tests use them)."""
    from repro.core import make_optimizer
    from repro.train import DecentralizedTrainer

    o = traffic["optimizer"]
    K = o["workers"]
    fam = config["family"]
    if fam == "deepfm":
        from repro.models.deepfm import deepfm_loss
        rows = sum(field_rows(config))
        fields = config["n_numeric"] + len(config["table_rows"])
        loss = deepfm_loss
        E, hidden = config["embed_dim"], tuple(config["hidden"])
        make_params = lambda key: weights.deepfm(  # noqa: E731
            key, rows, fields, E, hidden)
        ref_loss = ref_deepfm.loss
    elif fam == "lm":
        import jax.numpy as jnp

        from repro.configs.base import ModelConfig
        from repro.models import build_model
        c = lm_sizes(config)
        cfg = ModelConfig(
            arch_id=config["name"], family="dense", n_layers=c["n_layers"],
            d_model=c["d_model"], n_heads=c["n_heads"],
            n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"],
            d_ff=c["d_ff"], vocab_size=c["vocab_size"],
            rope_theta=c["rope_theta"], norm_eps=c["norm_eps"],
            tie_embeddings=config["tie_word_embeddings"],
            param_dtype=jnp.dtype(config["param_dtype"]),
            compute_dtype=jnp.dtype(config["compute_dtype"]))
        api = build_model(cfg)
        loss = api.loss
        make_params = functools.partial(
            weights.lm, sizes=tuple(sorted(c.items())))
        ref_loss = functools.partial(ref_lm.loss, cfg=c)
    else:
        raise ValueError(f"unknown configuration family {fam!r}")
    if loss_wrap is not None:
        loss = loss_wrap(loss)
    opt_kw = {k: o[k] for k in ("eta", "beta1", "beta2", "tau", "period")}
    if o["name"] == "cd-adam":
        opt_kw.update(gamma=o["gamma"], compressor=o["compressor"])
    opt = make_optimizer(o["name"], K=K, topology=o["topology"],
                         backend=o["backend"], comm=o["comm"], mesh=mesh,
                         **opt_kw)
    if step_wrap is not None:
        opt = dataclasses.replace(opt, step=step_wrap(opt.step))
    trainer = DecentralizedTrainer(lambda p, b: loss(p, b), opt,
                                   donate=True)
    batch = traffic["batch_per_worker"]
    return System(trainer, make_params, ref_loss,
                  config.get("matmul_precision"),
                  model_flops_per_example(config, traffic) * batch * K)
