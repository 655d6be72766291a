"""Small copies of the benchmark's cells for the CPU: each configuration
and traffic mix overlaid with its family's ``SMALL_CONFIG`` and
``SMALL_TRAFFIC``, and the cell's own limits kept."""
from __future__ import annotations

from chipbench import spec


def small_cell(name: str) -> spec.Cell:
    c = spec.load_cell(name)
    fam = spec.family(c.config["family"])
    c.config = dict(c.config, **fam.SMALL_CONFIG)
    c.traffic = dict(c.traffic, **fam.SMALL_TRAFFIC)
    return c


def cell_names():
    """Every cell with a limits file (a cell is judged only with one). A
    four-chip cell runs on four of the host devices that the program's
    ``env.setup`` gives the CPU backend."""
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    return [w["name"] for w in bench["workloads"]
            if (spec.HERE / "limits" / f"{w['name']}.json").exists()]
