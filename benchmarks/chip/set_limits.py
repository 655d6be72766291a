#!/usr/bin/env python3
"""Set a cell's correctness limits from ``calibrate.py``'s readings.

    python3 benchmarks/chip/set_limits.py CALIBRATION.json [RUN.out ...]

For each compared number: the lower reading is the largest that sound
runs of the program gave over the calibration's seeds and over the seeds
of any ``run.py`` outputs given (their last line's ``checks``). The upper reading
is the least of the control's smallest reading, where that is three
times the lower or more, of each planted fault's smallest reading, where
that is ten times the lower or more, and of 1, which a state left
unchanged reads on the gradient and change numbers, where that is three
times the lower or more. The limit lies between, nearer the upper:
``lower^(1/3) * upper^(2/3)``, so fresh seeds have room above the lower
reading. A number with no upper reading gets no limit and the file says
so (a cell whose control and faults fail none of its numbers cannot be
judged). Writes ``benchmarks/chip/limits/<workload>.json``.
"""
from __future__ import annotations

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
UNCHANGED_STATE = {"grad_norm_gap": 1.0, "change_norm_gap": 1.0}


def limits_from(cal: dict, runs=()) -> dict:
    """``runs``: the result lines of sound ``run.py`` runs."""
    out, readings = {}, {}
    for name, r in cal["summary"].items():
        lower = max([r["lower"]] + [x["checks"][name]["value"]
                                     for x in runs])
        r = dict(r, lower=lower)
        cands = {}
        if r["control"] >= 3 * lower:
            cands["control"] = r["control"]
        for tag, v in r.items():
            if tag.startswith("fault_") and v >= 10 * lower:
                cands[tag] = v
        if name in UNCHANGED_STATE and UNCHANGED_STATE[name] >= 3 * lower:
            cands["state_unchanged"] = UNCHANGED_STATE[name]
        upper_by = min(cands, key=cands.get) if cands else None
        upper = cands[upper_by] if cands else None
        readings[name] = {**r, "upper": upper, "upper_from": upper_by}
        if upper is not None and lower > 0:
            out[name] = lower ** (1 / 3) * upper ** (2 / 3)
        elif upper is not None:
            out[name] = upper / 10.0
    return {"workload": cal["workload"], "limits": out,
            "sound_runs": len(cal["program"]) + len(runs),
            "control_mode": cal["control_mode"], "readings": readings,
            "rule": "lower^(1/3) * upper^(2/3); see set_limits.py"}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    cal = json.loads(pathlib.Path(argv[0]).read_text())
    runs = [json.loads(pathlib.Path(p).read_text().strip().splitlines()[-1])
            for p in argv[1:]]
    lim = limits_from(cal, runs)
    missing = [n for n in cal["summary"] if n not in lim["limits"]]
    path = HERE / "limits" / f"{cal['workload']}.json"
    path.write_text(json.dumps(lim, indent=1) + "\n")
    print(json.dumps(lim["limits"]), "no upper reading:", missing or "none")
    return 0


if __name__ == "__main__":
    sys.exit(main())
