"""Family ``lm``: a LLaMA-style dense decoder given by Hugging Face
``config.json`` keys; the program's ``family="dense"`` transformer, the
plain reference ``reference/transformer.py`` and the next-token traffic
generator."""
from __future__ import annotations

import functools

from chipbench.traffic import lm_pool
from reference import transformer as reference
from reference import weights

# Vocabulary, width and heads shrunk for the CPU; the 128-wide heads and
# a 512-token sequence are kept, since the precision of the products sets
# the compared numbers.
SMALL_CONFIG = dict(hidden_size=512, intermediate_size=1376,
                    num_attention_heads=4, num_key_value_heads=1,
                    vocab_size=2048)
SMALL_TRAFFIC = dict(seq_len=512, pool=8, log_every=5)


def sizes(config: dict) -> dict:
    """The sizes of the configuration file, as the reference names them."""
    d, H = config["hidden_size"], config["num_attention_heads"]
    return {"n_layers": config["num_hidden_layers"], "d_model": d,
            "n_heads": H, "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config.get("head_dim", d // H),
            "d_ff": config["intermediate_size"],
            "vocab_size": config["vocab_size"],
            "rope_theta": float(config["rope_theta"]),
            "norm_eps": float(config["rms_norm_eps"])}


def program_loss(config: dict):
    import jax.numpy as jnp

    from repro.configs.base import ModelConfig
    from repro.models import build_model

    c = sizes(config)
    cfg = ModelConfig(
        arch_id=config["name"], family="dense", n_layers=c["n_layers"],
        d_model=c["d_model"], n_heads=c["n_heads"],
        n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"],
        d_ff=c["d_ff"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"], norm_eps=c["norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        param_dtype=jnp.dtype(config["param_dtype"]),
        compute_dtype=jnp.dtype(config["compute_dtype"]))
    return build_model(cfg).loss


def make_params(config: dict):
    return functools.partial(weights.lm,
                             sizes=tuple(sorted(sizes(config).items())))


def ref_loss(config: dict):
    return functools.partial(reference.loss, cfg=sizes(config))


def flops_per_example(config: dict, traffic: dict) -> float:
    """Forward and backward of one ``seq_len`` sequence: 6 per matmul
    parameter per token (the LM head included, the embedding gather not),
    plus causal attention."""
    c = sizes(config)
    d, hd, S = c["d_model"], c["head_dim"], traffic["seq_len"]
    per_layer = (d * hd * (c["n_heads"] + 2 * c["n_kv_heads"])
                 + c["n_heads"] * hd * d + 3 * d * c["d_ff"])
    n = c["n_layers"] * per_layer + d * c["vocab_size"]
    # QK^T and PV: 2 * 2 * S^2 * H * hd forward, half of it causal
    attn = c["n_layers"] * 2 * S * S * c["n_heads"] * hd
    return 6.0 * n * S + 3.0 * attn


make_pool = lm_pool
