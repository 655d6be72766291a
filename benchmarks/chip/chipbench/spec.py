"""Finding a cell's files by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
from typing import Any, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent.parent      # benchmarks/chip
ROOT = HERE.parent.parent                                    # the checkout


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Optional[Dict[str, Any]]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """A metric with ``workloads`` is reported in those cells; an
    end-to-end one without it in every cell, a per-layer one without it in
    every cell that reports the end-to-end metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, bench: Optional[dict] = None,
              root: pathlib.Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    limits_path = HERE / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.exists() else None
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name, w["chips"], config, traffic, limits, e2e, per_layer)


def load_module(path: pathlib.Path):
    """Import one reader, cost or family file by its path."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def family(name: str):
    """The module of a configuration family, ``families/<name>.py``: the
    program's loss, the seeded weights, the plain reference, the model
    FLOPs, the traffic generator and the small CPU sizes of every
    configuration whose file says ``"family": name``."""
    path = HERE / "families" / f"{name}.py"
    if not path.is_file():
        have = sorted(p.name for p in (HERE / "families").glob("*.py"))
        raise ValueError(f"unknown configuration family {name!r}: no "
                         f"benchmarks/chip/families/{name}.py (have "
                         f"{have})")
    return load_module(path)


def peaks_for(device_kind: str) -> Dict[str, Any]:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmarks/chip/peaks.json (have "
                       f"{sorted(table['devices'])}); add its published "
                       f"peaks with their source")
    return table["devices"][device_kind]
