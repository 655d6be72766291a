"""The configuration families (``families/<family>.py``): each cell's
weights, batches and model FLOPs as the harness made them before the
families were files of their own, and the message for a family that has
no file."""
from __future__ import annotations

import hashlib

import jax
import numpy as np
import pytest

from chipbench import spec, systems, traffic
from reference.weights import seed_key
from small import small_cell

SEED = 2 ** 33 + 17
API = ("program_loss", "make_params", "ref_loss", "flops_per_example",
       "make_pool", "SMALL_CONFIG", "SMALL_TRAFFIC")
# sha256 (first 16 hex digits) of every leaf's dtype, shape and bytes at
# the small size, made by the harness before this layout
SUMS = {"deepfm-criteo.dadam-k2": ("eca843a335c1fe5c", "9c5114d1678b28ff"),
        "yi-6b.dadam-k2": ("504904b3ab8beb8f", "4aac06f0c7beb2dc"),
        "deepfm-criteo.cdadam-k4-axis": ("eca843a335c1fe5c",
                                         "95b9487532d01bc9")}
# model FLOPs of one worker's example at the published sizes, as above
FLOPS = {"deepfm-criteo.dadam-k2": 2868180.0,
         "yi-6b.dadam-k2": 2631741210624.0,
         "deepfm-criteo.cdadam-k4-axis": 2868180.0}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for x in jax.tree_util.tree_leaves(tree):
        a = np.asarray(x)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("path", sorted((spec.HERE / "families")
                                        .glob("*.py")), ids=lambda p: p.stem)
def test_family_file_gives_what_the_harness_reads(path):
    fam = spec.family(path.stem)
    assert [n for n in API if not hasattr(fam, n)] == []


@pytest.mark.parametrize("name", sorted(SUMS))
def test_weights_and_batches_from_the_seed(name):
    cell = small_cell(name)
    fam = spec.family(cell.config["family"])
    key_w, key_d = jax.random.split(seed_key(SEED))
    params = _digest(fam.make_params(cell.config)(key_w))
    pool = _digest(traffic.make_pool(key_d, cell.config, cell.traffic))
    assert (params, pool) == SUMS[name]


@pytest.mark.parametrize("name", sorted(FLOPS))
def test_flops_per_example_at_the_published_sizes(name):
    cell = spec.load_cell(name)
    fam = spec.family(cell.config["family"])
    assert fam.flops_per_example(cell.config, cell.traffic) == FLOPS[name]


def test_unknown_family_lists_the_family_files():
    cell = small_cell("deepfm-criteo.dadam-k2")
    config = dict(cell.config, family="moe")
    have = sorted(p.name for p in (spec.HERE / "families").glob("*.py"))
    assert "deepfm.py" in have and "lm.py" in have
    for call in (lambda: systems.build(config, cell.traffic),
                 lambda: traffic.make_pool(seed_key(SEED), config,
                                           cell.traffic)):
        with pytest.raises(ValueError) as e:
            call()
        assert "'moe'" in str(e.value) and str(have) in str(e.value)
