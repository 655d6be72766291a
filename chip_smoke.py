#!/usr/bin/env python3
"""Bring-up check: the decentralized trainer and its serving path, run once
on a TPU at llama3.2-1b widths through the normal entry points.

    python chip_smoke.py             # one chip: phases 0-3
    python chip_smoke.py --chips 4   # one four-chip host: the mesh paths

Everything runs in this one process (a chip belongs to one process).

Phase 0  environment: platform, device kind and count, versions, compile
         cache. Exits non-zero, printing no result, when JAX finds no TPU.
Phase 1  D-Adam: ``repro.launch.train.main`` with the Pallas kernels
         (``--backend pallas``), then the same seed and batches with
         ``--backend reference``; losses finite, the compiled step holds
         Mosaic kernels, params agree within the stated tolerances.
Phase 2  CD-Adam: the same, with the sign-compressed gossip.
Phase 3  serving: publish worker 0 of the Phase 1 state into a ParamStore
         and answer 8 requests through ``repro.launch.serve.main``
         (DecodeEngine, two buckets); outputs in range, one compile per
         bucket.

``--chips 4`` runs only the paths that exist across chips, each with what
it is compared with: K=4 ``--comm axis`` D-Adam and CD-Adam against
``--backend reference`` on the same mesh, and K=2 x M=2
``--model-parallel 2`` against the K=2 stacked run of the same size.

Each cut of the published config is printed: every width (d_model 2048,
32 query / 8 KV heads of 64, d_ff 8192) stays as published; depth and
vocabulary are cut so that two workers' replicas, Adam moments and
gradients fit one chip. The last line of output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

# Sizing (rehearsed with compiled.memory_analysis() for a described v5e):
# one layer, and the vocabulary sliced to what the step's peak allows.
# D-Adam at K=2 holds p, m, v, grads and the kernels' fresh p, m, v
# (~28 B/param/worker): the full 128,256-row vocabulary needs 16.9 GB of
# the chip's 15.75 GB, half of it 10.8 GB. CD-Adam adds the hat copies.
LAYERS = 1
VOCAB = {"d-adam": 64128,     # 1/2 of 128,256
         "cd-adam": 48096}    # 3/8 of 128,256
WORKERS = 2
STEPS = 6
RUN_ARGS = ["--topology", "ring", "--period", "2", "--steps", str(STEPS),
            "--batch", "2", "--seq", "512", "--log-every", "1"]


def size_args(kind: str) -> list:
    """The published config with this smoke's depth and vocabulary cuts."""
    return ["--full", "--layers", str(LAYERS), "--vocab", str(VOCAB[kind])]


# Parity: the pallas run against its comparison run on the same seed and
# batches. Both runs of a pair compute the model in f32 with f32 matmuls
# (compute dtype float32 under jax.default_matmul_precision("highest")), so
# what differs is the optimizer's arithmetic: the Pallas kernels and the
# XLA reference fuse and order their f32 operations differently. In bf16
# compute the two step programs also round activations at different
# points, and Adam, whose first steps are close to sign(g) * 3.2 * eta,
# turns that into visible drift (a CPU run of the reduced preset: update
# rel-L2 1.1e-2 in bf16, 1.4e-6 in f32), which would hide a kernel bug.
PARITY_ARGS = ["--compute-dtype", "float32"]
# relative L2 norm of (pallas - reference) over the reference's update
# (final - init params); an Adam or gossip kernel computing in bf16 would
# be ~4e-3 off
UPDATE_RTOL = {"d-adam": 1e-4, "cd-adam": 1e-3}
LOSS_RTOL = 1e-5          # every logged loss
# CD-Adam sign-compresses delta = x - hat; an element whose delta is
# within rounding of zero can take the other sign in one run, which moves
# it by ~2 * gamma * w * scale. Such "flips" (|pallas - reference| above
# FLIP_ATOL) are counted against a budget, a fraction of all elements.
FLIP_ATOL = 1e-4
FLIP_BUDGET = {"d-adam": 1e-5, "cd-adam": 1e-4}


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def hlo_counts(compiled) -> dict:
    from repro.analysis.hlo import collective_counts

    text = compiled.as_text()
    return {"kernels": text.count('custom_call_target="tpu_custom_call"'),
            "collective_permutes": collective_counts(text)[
                "collective-permute"]}


def train(tag: str, argv: list, *, kernels: bool = True, inspect=None,
          publish_to=None):
    """One ``repro.launch.train.main(argv)`` run: losses finite, the step
    program's kernel and collective counts, the facts to print, and the
    final params, left on the device. The rest of the run's device state
    is freed on return."""
    import jax

    from repro.launch import train as train_mod

    print(f"[{tag}] run: {' '.join(argv)}", flush=True)
    t0 = time.perf_counter()
    run = train_mod.main(argv)
    losses = list(run.log.loss)
    check(len(losses) == STEPS and all(math.isfinite(x) for x in losses),
          f"{tag}: non-finite or missing losses {losses}")
    a = run.args
    batch = next(train_mod.make_batch_iter(run.cfg, a.workers, a.batch,
                                           a.seq, a.skew))
    # the program fit ran (a persistent-cache hit when the cache is on)
    compiled = run.trainer.lower_step(run.state, batch).compile()
    info = dict(hlo_counts(compiled), cuts=run.cuts, n_params=run.n_params,
                losses=losses, first_step_s=run.first_step_s,
                steady_ms=run.steady_ms, cfg=run.cfg,
                state_gb=sum(x.nbytes for x in
                             jax.tree_util.tree_leaves(run.state)) / 1e9,
                temp_gb=compiled.memory_analysis().temp_size_in_bytes / 1e9)
    del compiled
    if kernels:
        check(info["kernels"] >= 1,
              f"{tag}: the compiled step holds no tpu_custom_call")
    if inspect is not None:
        inspect(run, info)
    if publish_to is not None:
        from repro.serve import publish_from_state
        publish_from_state(publish_to, run.state, mode="worker")
    info["params"] = jax.block_until_ready(
        run.trainer.opt.params_of(run.state))
    del run
    gc.collect()
    steady = (f"{info['steady_ms']:.1f} ms/step after step 1"
              if info["steady_ms"] is not None else "one step")
    print(f"[{tag}] cuts: {', '.join(info['cuts']) or 'none'}; params "
          f"{info['n_params'] / 1e6:.1f}M per worker; resident state "
          f"{info['state_gb']:.2f} GB; step temp {info['temp_gb']:.2f} GB; "
          f"first step {info['first_step_s']:.1f} s incl. compile; {steady}; "
          f"tpu_custom_call x{info['kernels']}; collective-permute "
          f"x{info['collective_permutes']}; losses "
          f"{[round(x, 5) for x in losses]}; run "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    return info


def _parity_sums(got, want, init):
    """sum (got - want)^2, sum (want - init)^2, the count of |got - want|
    above FLIP_ATOL and max |got - want|, over every leaf, in f32 on the
    devices that hold ``want``."""
    import jax.numpy as jnp

    diff2 = upd2 = max_abs = jnp.float32(0)
    flips = jnp.int32(0)
    for g, w, i in zip(got, want, init):
        w = w.astype(jnp.float32)
        d = jnp.abs(g.astype(jnp.float32) - w)
        diff2 += jnp.sum(d * d)
        upd2 += jnp.sum(jnp.square(w - i.astype(jnp.float32)[None]))
        flips += jnp.sum(d > FLIP_ATOL, dtype=jnp.int32)
        max_abs = jnp.maximum(max_abs, jnp.max(d))
    return diff2, upd2, flips, max_abs


def parity(tag: str, kind: str, got: dict, want: dict) -> list:
    """Compare two runs' final params (K stacked, on the device) and
    losses; print each measure against its tolerance; return the
    failures. Both runs' params and the initial ones meet on the devices
    that hold ``want``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.train import SEED
    from repro.models import build_model

    t0 = time.perf_counter()
    leaves = jax.tree_util.tree_leaves
    wl = leaves(want["params"])

    def replicated(w):
        s = w.sharding
        return (NamedSharding(s.mesh, PartitionSpec())
                if isinstance(s, NamedSharding) else s)

    gl = [jax.device_put(g, w.sharding)
          for g, w in zip(leaves(got["params"]), wl)]
    il = [jax.device_put(i, replicated(w)) for i, w in zip(
        leaves(build_model(got["cfg"]).init(jax.random.PRNGKey(SEED))), wl)]
    diff2, upd2, flips, max_abs = (
        x.item() for x in jax.device_get(jax.jit(_parity_sums)(gl, wl, il)))
    del gl, il
    n = sum(w.size for w in wl)
    loss_rel = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], want["losses"]))
    fails = []
    for name, val, tol in (
            ("loss rel", loss_rel, LOSS_RTOL),
            ("update rel-L2", math.sqrt(diff2 / max(upd2, 1e-300)),
             UPDATE_RTOL[kind]),
            (f"flip fraction (|d| > {FLIP_ATOL:g})", flips / n,
             FLIP_BUDGET[kind])):
        ok = val <= tol
        print(f"[{tag}] parity {name} = {val:.3e} (tolerance {tol:.0e}) "
              f"{'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            fails.append(f"{tag} {name} {val:.3e} > {tol:.0e}")
    print(f"[{tag}] parity max |diff| = {max_abs:.3e} over {n} elements; "
          f"{flips} flips; compared in {time.perf_counter() - t0:.1f} s",
          flush=True)
    return fails


def compare(tag: str, kind: str, run_args: list, cmp_args: list, *,
            inspect=None) -> list:
    """The pallas run (``run_args``) and its comparison run (``cmp_args``),
    one after the other, at f32 compute (``PARITY_ARGS``)."""
    import jax

    base = size_args(kind) + RUN_ARGS + ["--optimizer", kind] + PARITY_ARGS
    with jax.default_matmul_precision("highest"):
        got = train(f"{tag} f32", base + run_args, inspect=inspect)
        want = train(f"{tag} f32 comparison", base + cmp_args,
                     kernels="pallas" in cmp_args)
    return parity(tag, kind, got, want)


def one_chip() -> list:
    from repro.launch import serve
    from repro.serve import ParamStore

    fails = []
    store = ParamStore()
    stacked = ["--comm", "stacked", "--workers", str(WORKERS)]
    for tag, kind in (("phase1 d-adam", "d-adam"),
                      ("phase2 cd-adam", "cd-adam")):
        # the configuration as users run it (bf16 compute) ...
        train(tag, size_args(kind) + RUN_ARGS + ["--optimizer", kind,
                                                 "--backend", "pallas"]
              + stacked, publish_to=store if kind == "d-adam" else None)
        # ... and its parity against the reference optimizer
        fails += compare(tag, kind, stacked + ["--backend", "pallas"],
                         stacked + ["--backend", "reference"])

    n_req, new = 8, 16
    argv = size_args("d-adam") + [
        "--buckets", "2x128,6x128", "--requests", str(n_req),
        "--prompt-len", "128", "--new-tokens", str(new)]
    print(f"[phase3 serve] run: {' '.join(argv)} (params: worker 0 of the "
          f"phase 1 D-Adam state, store v{store.version})", flush=True)
    run = serve.main(argv, store=store)
    outs = [o.tolist() for o in run.outputs]
    check(len(outs) == n_req and all(len(o) == new for o in outs),
          f"serve: expected {n_req} x {new} tokens, got "
          f"{[len(o) for o in outs]}")
    check(all(0 <= t < run.cfg.vocab_size for o in outs for t in o),
          "serve: token id outside the vocabulary slice")
    want = {"prefill": len(run.buckets), "decode": len(run.buckets)}
    check(run.compile_counts == want,
          f"serve: compile counts {run.compile_counts} != {want}")
    print(f"[phase3 serve] {n_req} requests x {new} tokens answered; "
          f"compiles {run.compile_counts} == buckets {run.buckets}; warm "
          f"pass {run.warm_s:.1f} s, steady pass {run.steady_s * 1e3:.0f} "
          f"ms; request 0 -> {outs[0]}", flush=True)
    return fails


def worker_devices(state, K: int) -> dict:
    """Device ids holding each worker's shard of the packed buffer."""
    held = {k: set() for k in range(K)}
    for shard in state.buf.addressable_shards:
        rows = shard.index[0]
        for k in range(*rows.indices(K)):
            held[k].add(shard.device.id)
    return held


def placed_apart(tag: str, K: int, per_worker: int):
    """An ``inspect`` hook: print which devices hold each worker's shard
    and fail unless every worker has its own ``per_worker`` devices and
    the step gossips with collective-permutes."""
    def inspect(run, info):
        held = worker_devices(run.state, K)
        print(f"[{tag}] worker -> device ids "
              f"{ {k: sorted(v) for k, v in held.items()} }", flush=True)
        every = [d for v in held.values() for d in v]
        check(all(len(v) == per_worker for v in held.values())
              and len(set(every)) == len(every),
              f"{tag}: workers do not each hold their own devices: {held}")
        check(info["collective_permutes"] >= 1,
              f"{tag}: no collective-permute in the compiled step")
    return inspect


def four_chips() -> list:
    fails = []
    K = 4
    axis = ["--comm", "axis", "--workers", str(K)]
    for kind in ("d-adam", "cd-adam"):
        tag = f"axis K=4 {kind}"
        fails += compare(tag, kind, axis + ["--backend", "pallas"],
                         axis + ["--backend", "reference"],
                         inspect=placed_apart(tag, K, 1))
    tag = "2D K=2xM=2 d-adam"
    fails += compare(
        tag, "d-adam",
        ["--comm", "axis", "--workers", str(WORKERS), "--model-parallel", "2",
         "--backend", "pallas"],
        ["--comm", "stacked", "--workers", str(WORKERS), "--backend", "pallas"],
        inspect=placed_apart(tag, WORKERS, 2))
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train + serve phases on one chip; 4: only the "
                         "comm='axis' and model-parallel mesh paths")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "__init__.py").is_file():
        print(f"chip_smoke: no repository next to {__file__} "
              "(expected src/repro/); run it from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import env
    env.setup()                       # before jax is imported
    import jax

    devs = jax.devices()
    dev = devs[0]
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed"
    print(f"[phase0] platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__} libtpu={libtpu} "
          f"compile_cache={jax.config.jax_compilation_cache_dir}",
          flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{dev.platform!r}); nothing is run on the CPU",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    fails = four_chips() if args.chips == 4 else one_chip()
    print(f"[done] {time.perf_counter() - t0:.0f} s", flush=True)
    if fails:
        print("chip_smoke: FAILED: " + "; ".join(fails), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
