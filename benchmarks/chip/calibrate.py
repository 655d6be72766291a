#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python3 benchmarks/chip/calibrate.py --workload NAME --seeds 12 \
        --control-seeds 3 --out chiprun_out/calibrate-NAME.json

On the chip, at the cell's own size, in one process: for each of
``--seeds`` seeds the program's first steps against the reference (the
lower readings); for each of ``--control-seeds`` seeds the control (the
reference one precision step below the configuration's, put in the
program's place) and each planted fault (half of the batch left out, the
gossip exchange left out), against the reference (the upper readings).
A state left unchanged reads 1 on the gradient and change numbers by
their definition and needs no run. Training's readings need no window.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import itertools
import json
import pathlib
import sys
import time

import run

FAULTS = ("half", "no_gossip")


def main(argv=None, *, require_tpu: bool = True, cell=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_011)
    ap.add_argument("--out", type=pathlib.Path, required=True)
    args = ap.parse_args(argv)
    opened = run.open_cell(args.workload, require_tpu=require_tpu,
                           cell=cell)
    if isinstance(opened, int):
        return opened
    cell, devs = opened[0], opened[1]
    import jax

    from chipbench import check, traffic
    from reference.weights import seed_key

    system = run.build_system(cell)
    trainer = system.trainer
    o = cell.traffic["optimizer"]
    T = check.steps_checked(cell.traffic)
    mode = check.control_mode(cell.config)
    out = {"workload": cell.name, "control_mode": mode, "program": [],
           "control": [], **{f"fault_{f}": [] for f in FAULTS}}
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    # the reference on the cell's chips, as run.py judges it
    used = devs[:cell.chips]

    def key_pair(seed):
        return jax.random.split(seed_key(seed))

    def first_batches(key_d):
        return list(traffic.make_pool(key_d, cell.config, cell.traffic))[:T]

    with system.context():
        for seed in seeds:
            t0 = time.perf_counter()
            key_w, key_d = key_pair(seed)
            state = trainer.init(system.make_params(key_w))
            feed = itertools.cycle(
                [trainer._place_batch(b) for b in first_batches(key_d)])

            def fit(state, steps, log_):
                return trainer.fit(state, feed, steps, log_every=steps,
                                   log=log_)

            state, _, prog = check.drive_first_steps(
                trainer, state, fit, T, o["beta1"],
                system.make_params(key_w))
            del state, feed
            gc.collect()
            ref = check.reference_readings(system, cell.config, cell.traffic,
                                           key_w, first_batches(key_d),
                                           devices=used)
            vals = check.compare(prog, ref)
            out["program"].append({"seed": seed, **vals})
            print(f"[calibrate] program seed {seed}: {vals} "
                  f"({time.perf_counter() - t0:.1f} s)", flush=True)
        for seed in seeds[:args.control_seeds]:
            key_w, key_d = key_pair(seed)
            ref = check.reference_readings(system, cell.config, cell.traffic,
                                           key_w, first_batches(key_d),
                                           devices=used)
            for tag, kw in [("control", {"mode": mode})] + [
                    (f"fault_{f}", {"fault": f}) for f in FAULTS]:
                got = check.reference_readings(
                    system, cell.config, cell.traffic, key_w,
                    first_batches(key_d), devices=used, **kw)
                vals = check.compare(got, ref)
                out[tag].append({"seed": seed, **vals})
                print(f"[calibrate] {tag} seed {seed}: {vals}", flush=True)
    summary = {}
    for n in check.NAMES:
        summary[n] = {
            "lower": max(r[n] for r in out["program"]),
            **{tag: min(r[n] for r in out[tag])
               for tag in ["control"] + [f"fault_{f}" for f in FAULTS]}}
    out["summary"] = summary
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(out, indent=1))
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
