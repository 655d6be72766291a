"""Small copies of the benchmark's cells for the CPU: tables, vocabulary,
depth of the batch and the pool shrunk, and the cell's own limits kept."""
from __future__ import annotations

from chipbench import spec

# Widths stay near the published ones where the precision of the product
# sets the compared numbers (DeepFM's MLP is kept whole; the LM keeps
# 128-wide heads and a 512-token sequence), so that the chip's limits
# tell a sound run from the control at this size too.
SMALL_CONFIG = {
    "deepfm": dict(table_rows=[50, 50, 1000, 800, 30, 24, 500, 60, 3, 900,
                               200, 1000, 300, 27, 600, 1000, 10, 500, 200,
                               4, 1000, 18, 15, 700, 105, 900],
                   max_ind_range=512),
    "lm": dict(hidden_size=512, intermediate_size=1376,
               num_attention_heads=4, num_key_value_heads=1,
               vocab_size=2048),
}
SMALL_TRAFFIC = {
    "ctr": dict(batch_per_worker=512, pool=8, log_every=5),
    "lm": dict(seq_len=512, pool=8, log_every=5),
}


def small_cell(name: str) -> spec.Cell:
    c = spec.load_cell(name)
    c.config = dict(c.config, **SMALL_CONFIG[c.config["family"]])
    c.traffic = dict(c.traffic, **SMALL_TRAFFIC[c.traffic["kind"]])
    return c


# A cell whose files are ready and that is not in BENCHMARK.json yet: it
# has not been measured on four chips (PERF.md, Open questions).
PENDING = {"deepfm-criteo.cdadam-k4-axis": ("deepfm-criteo",
                                           "ctr-cdadam-k4-axis", 4)}


def pending_cell(name: str) -> spec.Cell:
    config, traffic, chips = PENDING[name]
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    c = spec.Cell(name, chips,
                  spec.load_json(spec.HERE / "configs" / f"{config}.json"),
                  spec.load_json(spec.HERE / "traffic" / f"{traffic}.json"),
                  None, bench["end_to_end"], [])
    c.config = dict(c.config, **SMALL_CONFIG["deepfm"])
    c.traffic = dict(c.traffic, **SMALL_TRAFFIC["ctr"])
    return c


def cell_names():
    """Every cell with a limits file (a cell is judged only with one). A
    four-chip cell runs on four of the host devices that the program's
    ``env.setup`` gives the CPU backend."""
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    return [w["name"] for w in bench["workloads"]
            if (spec.HERE / "limits" / f"{w['name']}.json").exists()]
