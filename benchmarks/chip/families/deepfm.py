"""Family ``deepfm``: DeepFM (arXiv:1703.04247) over categorical fields
with one id each, numeric fields bucketized first; the program's
``repro.models.deepfm``, the plain reference ``reference/deepfm.py`` and
the CTR traffic generator."""
from __future__ import annotations

from chipbench.traffic import ctr_pool, field_rows
from reference import deepfm as reference
from reference import weights

# Tables and the batch shrunk for the CPU; the MLP is kept whole, since
# the precision of its products sets the compared numbers.
SMALL_CONFIG = dict(table_rows=[50, 50, 1000, 800, 30, 24, 500, 60, 3, 900,
                                200, 1000, 300, 27, 600, 1000, 10, 500, 200,
                                4, 1000, 18, 15, 700, 105, 900],
                    max_ind_range=512)
SMALL_TRAFFIC = dict(batch_per_worker=512, pool=8, log_every=5)


def _fields(config: dict) -> int:
    return config["n_numeric"] + len(config["table_rows"])


def program_loss(config: dict):
    from repro.models.deepfm import deepfm_loss

    return deepfm_loss


def make_params(config: dict):
    rows, fields = sum(field_rows(config)), _fields(config)
    E, hidden = config["embed_dim"], tuple(config["hidden"])
    return lambda key: weights.deepfm(key, rows, fields, E, hidden)


def ref_loss(config: dict):
    return reference.loss


def flops_per_example(config: dict, traffic: dict) -> float:
    """Forward and backward: 6 per MLP parameter and per active FM and
    linear weight; the embedding gather is not counted."""
    fields, E = _fields(config), config["embed_dim"]
    mlp, d_in = 0, fields * E
    for h in list(config["hidden"]) + [1]:
        mlp += d_in * h + h
        d_in = h
    return 6.0 * (mlp + fields * E + fields)


make_pool = ctr_pool
