#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload NAME --seed N --seconds S \
        --trace 0|1

From the root of a checkout, on a machine holding the chips the cell asks
for. The run builds the program's trainer as a user does, makes its
weights and a pool of batches on the device from the seed, drives the
compiled step through its first ``period`` steps (the readings the
correctness check compares), then measures ``--seconds`` of
``DecentralizedTrainer.fit`` in chunks of ``log_every`` steps, each
ending on a logged (synced) step. ``--trace 1`` records that window with
the profiler and reports the per-layer metrics instead of the end-to-end
ones. After the window, with the program's state freed, the plain
reference follows the same first steps and decides ``correct``.

The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error
and the last key of that object. Without a TPU, or with fewer chips than
the cell asks for, the run prints no result and exits 1.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse             # noqa: E402
import contextlib           # noqa: E402
import gc                   # noqa: E402
import itertools            # noqa: E402
import json                 # noqa: E402
import math                 # noqa: E402
import os                   # noqa: E402
import pathlib              # noqa: E402
import sys                  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Persistent-cache hits and misses, and backend compiles, from JAX's
    monitoring events."""

    def __init__(self):
        import jax

        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def snapshot(self):
        return self.hits, self.misses, self.compiles


def open_cell(workload: str, *, require_tpu: bool = True, cell=None):
    """Environment, compile cache, the device check and the cell's files.
    Returns (cell, devices, peaks, CompileCounter), or an exit code with
    the reason on standard error."""
    if not (ROOT / "src" / "repro" / "__init__.py").is_file():
        print(f"run.py: no program next to the benchmark (expected "
              f"{ROOT / 'src' / 'repro'}); run it from a checkout",
              file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # libtpu otherwise logs under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(HERE), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.launch import env
    env.setup()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()

    from chipbench import spec

    cell = cell or spec.load_cell(workload)
    devs = jax.devices()
    dev = devs[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__} cache={CACHE_DIR}")
    if require_tpu and dev.platform != "tpu":
        print(f"run.py: no TPU (JAX platform {dev.platform!r}); the "
              f"benchmark measures only on the chip", file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"run.py: {workload} needs {cell.chips} chips, JAX finds "
              f"{len(devs)}", file=sys.stderr)
        return 1
    try:
        peaks = spec.peaks_for(dev.device_kind if require_tpu
                               else "TPU v5 lite")
    except KeyError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    return cell, devs, peaks, counter


def _memory(device) -> str:
    stats = device.memory_stats()
    if not stats:
        return f"device {device.id} not reported"
    return (f"device {device.id} {stats.get('bytes_in_use')} / "
            f"{stats.get('peak_bytes_in_use')}")


def build_system(cell, **build_kw):
    """The program's trainer for the cell (on a worker mesh for comm
    'axis')."""
    from chipbench import systems

    o = cell.traffic["optimizer"]
    mesh = None
    if o["comm"] == "axis":
        from repro.launch.mesh import make_worker_mesh
        mesh = make_worker_mesh(o["workers"])
    return systems.build(cell.config, cell.traffic, mesh=mesh, **build_kw)


def main(argv=None, *, require_tpu: bool = True, build_kw=None,
         cell=None) -> int:
    """``require_tpu=False``, ``build_kw`` (passed to ``systems.build``)
    and ``cell`` (a ``spec.Cell`` in place of the named one) are for the
    harness's own tests, which drive a small cell on the CPU and plant
    faults in the timed path."""
    args = parse(argv)
    opened = open_cell(args.workload, require_tpu=require_tpu, cell=cell)
    if isinstance(opened, int):
        return opened
    cell, devs, peaks, counter = opened
    dev = devs[0]
    import jax

    from chipbench import check, layers, trace, traffic
    from reference.weights import seed_key

    used = devs[:cell.chips]

    o = cell.traffic["optimizer"]
    system = build_system(cell, **(build_kw or {}))
    trainer = system.trainer
    key_w, key_d = jax.random.split(seed_key(args.seed))
    first_steps = check.steps_checked(cell.traffic)
    every = cell.traffic["log_every"]

    with system.context():
        marks = [("start", time.perf_counter())]
        state = trainer.init(system.make_params(key_w))
        jax.block_until_ready(state)
        marks.append(("weights and init", time.perf_counter()))
        pool = list(traffic.make_pool(key_d, cell.config, cell.traffic))
        pool = jax.block_until_ready(
            [trainer._place_batch(b) for b in pool])
        marks.append(("batch pool", time.perf_counter()))
        feed = itertools.cycle(pool)

        def fit(state, steps, log_):
            return trainer.fit(state, feed, steps, log_every=steps, log=log_)

        # the check's readings, through the window's own call and feed
        state, run_log, prog = check.drive_first_steps(
            trainer, state, fit, first_steps, o["beta1"],
            system.make_params(key_w))
        jax.block_until_ready(state)
        marks.append(("first steps", time.perf_counter()))
        before = counter.snapshot()
        setup_s = time.perf_counter() - T0
        phases = ", ".join(f"{name} {t - t_prev:.1f} s" for (name, t), (_, t_prev)
                           in zip(marks[1:], marks))
        log(f"set-up {setup_s:.3f} s (to the cell's start "
            f"{marks[0][1] - T0:.1f} s, {phases}); persistent cache hits "
            f"{before[0]}, misses {before[1]}; backend compiles {before[2]}")

        window = (trace.capture(TRACE_DIR / args.workload) if args.trace
                  else contextlib.nullcontext())
        steps, failed = 0, 0
        with window:
            t_w0 = time.perf_counter()
            while True:
                state, run_log = fit(state, every, run_log)
                steps += every
                if not math.isfinite(run_log.loss[-1]):
                    failed += every
                if time.perf_counter() - t_w0 >= args.seconds:
                    break
            t_w1 = time.perf_counter()
        after = counter.snapshot()
        window_s = t_w1 - t_w0
        log(f"window {window_s:.3f} s, {steps} steps; compiles inside it "
            f"{after[2] - before[2]}, cache misses {after[1] - before[1]}; "
            f"last loss {run_log.loss[-1]:.6f}")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)

        metrics, extra = {}, {}
        if args.trace:
            tr = trace.load(TRACE_DIR / args.workload)
            tr.devices = [d for d in tr.devices if d.id in
                          {u.id for u in used}]
            hlo = trainer.lower_step(state, pool[0]).compile().as_text()
            costs = layers.kernel_modules()
            ctx = layers.Context(tr, steps, peaks, system.flops_per_step,
                                 trace.kernel_calls(hlo, costs), costs)
            units = {m["name"]: m["unit"] for m in cell.per_layer}
            for name, v in layers.read_all(list(units), ctx).items():
                if v is not None:
                    metrics[name] = {"value": v, "unit": units[name]}
            busy = [trace.union_ns((op.start, op.end) for op in d.ops)
                    for d in tr.devices]
            extra = {"busy_s": sum(busy) / len(busy) / 1e9,
                     "window_s": tr.window_ns / 1e9}
            breakdown = layers.breakdown(ctx)
        else:
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
            e2e = {"step_ms": window_s / steps * 1e3, "setup_s": setup_s,
                   "peak_hbm_gb": peak / 1e9}
            metrics = {n: {"value": e2e[n], "unit": units[n]}
                       for n in units}
        del state, pool, feed, trainer
        gc.collect()

        t_ref = time.perf_counter()
        batches = list(traffic.make_pool(key_d, cell.config, cell.traffic)
                       )[:first_steps]
        ref = check.reference_readings(system, cell.config, cell.traffic,
                                       key_w, batches, devices=used)
        values = check.compare(prog, ref)
        correct, checks = check.judge(values, cell.limits)
        log(f"reference {time.perf_counter() - t_ref:.1f} s; program "
            f"losses {prog.losses}; reference losses {ref.losses}")
        log("after the reference, bytes in use / peak since start: " +
            ", ".join(_memory(d) for d in used))

    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": int(peak),
                         **extra}}
    if args.trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
