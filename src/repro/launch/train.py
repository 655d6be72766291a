"""Training driver: decentralized D-Adam / CD-Adam training of any
registered architecture.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --workers 4 --steps 50 --optimizer cd-adam --period 4

Without ``--full`` it trains the arch's reduced preset (the CPU test
size: ``JAX_PLATFORMS=cpu``, Pallas kernels in interpret mode). ``--full``
takes the published widths; ``--layers N`` and ``--vocab V`` then cut
depth and vocabulary to what one chip holds, leaving every width as
published, and the run prints the cuts it made. ``main(argv)`` runs
in-process and returns a :class:`TrainRun` (what ``chip_smoke.py``
drives). Checkpoints every --ckpt-every steps via repro.checkpoint.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Optional, Sequence, Tuple

if __name__ == "__main__":
    # host-device count, compile cache and async-collective XLA flags
    # must land BEFORE jax initializes; repro.launch.env appends to any
    # pre-set XLA_FLAGS
    from repro.launch import env as _env
    _env.setup()

import jax
import jax.numpy as jnp

from repro.checkpoint import save
from repro.configs import list_archs, sized_arch
from repro.core import make_optimizer
from repro.data import lm_batch
from repro.launch.mesh import make_worker_mesh
from repro.launch.shardings import make_plan
from repro.models import build_model
from repro.train import DecentralizedTrainer


# seed of the random init; the batch stream draws from BATCH_SEED
SEED = 0
BATCH_SEED = 42


def make_batch_iter(cfg, K: int, per_worker: int, seq: int, skew: float):
    key = jax.random.PRNGKey(BATCH_SEED)
    t = 0
    while True:
        kt = jax.random.fold_in(key, t)
        toks = jnp.stack([
            lm_batch(kt, per_worker, seq, cfg.vocab_size, k, K, skew)
            for k in range(K)])
        batch = {"tokens": toks}
        if cfg.family == "vlm":
            batch["patches"] = jax.random.normal(
                kt, (K, per_worker, cfg.n_patches, 1024), jnp.float32)
        if cfg.family == "audio":
            batch["audio_embeds"] = jax.random.normal(
                kt, (K, per_worker, cfg.n_audio_ctx, cfg.d_model),
                jnp.float32)
        yield batch
        t += 1


@dataclasses.dataclass
class TrainRun:
    """What one ``main(argv)`` run leaves behind, for in-process callers.

    ``state`` is the live optimizer state (the trainer donates the one
    it was given each step). ``log.wall_s[0]`` is the wall time of step
    1, compilation included; the run logs step 1 alone, then every
    ``--log-every`` steps."""
    args: argparse.Namespace
    cfg: Any
    cuts: Tuple[str, ...]
    trainer: DecentralizedTrainer
    state: Any
    log: Any
    n_params: int


def main(argv: Optional[Sequence[str]] = None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=list_archs())
    ap.add_argument("--full", action="store_true",
                    help="published widths (needs a chip)")
    ap.add_argument("--layers", type=int, default=None,
                    help="with --full: keep the first N layers (depth cut)")
    ap.add_argument("--vocab", type=int, default=None,
                    help="with --full: keep the first V vocabulary rows "
                         "(>= 1/8 of the published count); batches draw "
                         "ids from the slice and the loss is over it")
    ap.add_argument("--compute-dtype", default=None,
                    choices=["bfloat16", "float32"],
                    help="dtype of the model's activations and matmul "
                         "inputs (default: the config's)")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=2, help="per worker")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--optimizer", default="d-adam",
                    choices=["d-adam", "cd-adam", "d-psgd"])
    ap.add_argument("--period", type=int, default=4)
    ap.add_argument("--compressor", default="sign")
    ap.add_argument("--gamma", type=float, default=0.4)
    ap.add_argument("--eta", type=float, default=1e-3)
    ap.add_argument("--topology", default="ring",
                    help="static graph (ring/torus/full/...) or a "
                         "time-varying schedule spec: "
                         "'one-peer-exponential', 'randomized-rings:N'")
    ap.add_argument("--staleness", type=int, default=None,
                    help="straggler tolerance tau: gossip may consume "
                         "payloads up to tau rounds old before blocking "
                         "on a fresh exchange (0 = synchronous semantics "
                         "with the buffers wired in)")
    ap.add_argument("--straggler-rate", type=float, default=0.0,
                    help="simulated straggler probability per edge per "
                         "round (requires --staleness >= 1)")
    ap.add_argument("--straggler-seed", type=int, default=0)
    ap.add_argument("--overlap", action="store_true",
                    help="overlap gossip with the local Adam steps: round "
                         "r's exchange is issued eagerly and folded in at "
                         "round r+1 (a delay-1 wire schedule, i.e. "
                         "staleness tau=1 on the wire with every edge "
                         "exactly one round late); mutually exclusive "
                         "with --staleness")
    ap.add_argument("--backend", default="reference",
                    choices=["reference", "pallas"],
                    help="optimizer execution backend (pallas = fused "
                         "kernels; interpret mode off-TPU)")
    ap.add_argument("--comm", default="stacked",
                    choices=["stacked", "axis"],
                    help="worker execution: 'stacked' runs the worker dim "
                         "in one program; 'axis' shards it over a "
                         "'worker' mesh axis (one device group per "
                         "worker) and gossips with ppermute inside "
                         "shard_map — needs >= --workers devices")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="with --comm axis --backend pallas: inner "
                         "model-parallel group size M per worker (2D "
                         "worker x model mesh; the packed state's row dim "
                         "is sharded M-ways, gossip still crosses only "
                         "the worker axis) — needs workers * M devices")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation microbatches per step "
                         "(must divide --batch); divides activation "
                         "memory by this factor in every backend")
    ap.add_argument("--damping", default="",
                    help="adaptive batch damping policy spec: "
                         "'adadamp:MAX[:EMA]', 'padadamp:MAX[:RATE]' or "
                         "'geodamp:MAX[:FACTOR[:DELAY]]' — grows the "
                         "gradient-accumulation chunk count as the loss "
                         "falls (MAX must divide --batch); one compiled "
                         "step serves every damping level. Mutually "
                         "exclusive with --microbatch > 1")
    ap.add_argument("--damping-per-worker", action="store_true",
                    help="one damping signal per worker (non-IID shards) "
                         "instead of the global mean-loss signal")
    ap.add_argument("--damping-lr-decay", type=float, default=0.5,
                    help="eta decay factor applied once the batch hits "
                         "the damping ceiling (with --damping-lr-decay-"
                         "every > 0)")
    ap.add_argument("--damping-lr-decay-every", type=int, default=0,
                    help="decay eta every N steps spent with every "
                         "worker at max_chunks (0 = off)")
    ap.add_argument("--skew", type=float, default=0.5,
                    help="non-IID-ness of worker shards")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    try:
        arch, cuts = sized_arch(args.arch, args.full, args.layers,
                                args.vocab)
    except ValueError as e:
        ap.error(str(e))
    if args.compute_dtype is not None:
        arch = dataclasses.replace(arch, model=dataclasses.replace(
            arch.model, compute_dtype=jnp.dtype(args.compute_dtype)))
    cfg = arch.model
    api = build_model(cfg)
    mesh = None
    if args.model_parallel > 1 and args.comm != "axis":
        raise SystemExit("--model-parallel > 1 requires --comm axis "
                         "(the 2D worker x model mesh)")
    if args.model_parallel > 1 and args.backend != "pallas":
        raise SystemExit("--model-parallel > 1 requires --backend pallas "
                         "(it shards the packed row dim)")
    if args.comm == "axis":
        need = args.workers * args.model_parallel
        if jax.device_count() < need:
            raise SystemExit(
                f"--comm axis needs workers * model_parallel devices: "
                f"have {jax.device_count()} devices for --workers "
                f"{args.workers} x --model-parallel "
                f"{args.model_parallel} (on CPU, set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need})")
        mesh = make_worker_mesh(args.workers,
                                model_parallel=args.model_parallel)
    opt = make_optimizer(args.optimizer, K=args.workers, eta=args.eta,
                         period=args.period, topology=args.topology,
                         gamma=args.gamma, compressor=args.compressor,
                         backend=args.backend, comm=args.comm, mesh=mesh,
                         staleness=args.staleness,
                         straggler_rate=args.straggler_rate,
                         straggler_seed=args.straggler_seed,
                         overlap=args.overlap)
    # 2D mesh: thread the head-aware mode='axis' sharding rules into the
    # loss (grad pipeline packed-GSPMD path) so matmul operands stay
    # P(..., 'model') instead of replicating whole per-worker param sets
    plan = (make_plan(arch, mesh, multi_pod=False, mode="axis")
            if args.model_parallel > 1 else None)
    damping = None
    if args.damping:
        import dataclasses as _dc

        from repro.train import make_damping
        damping = _dc.replace(
            make_damping(args.damping),
            per_worker=args.damping_per_worker,
            lr_decay=args.damping_lr_decay,
            lr_decay_every=args.damping_lr_decay_every)
        if args.batch % damping.max_chunks:
            raise SystemExit(
                f"--damping max_chunks {damping.max_chunks} must divide "
                f"--batch {args.batch}")
    trainer = DecentralizedTrainer(lambda p, b: api.loss(p, b), opt,
                                   microbatch=args.microbatch, plan=plan,
                                   damping=damping,
                                   sharded_loss=getattr(api, "sharded_loss",
                                                        None),
                                   donate=True)
    params = api.init(jax.random.PRNGKey(SEED))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    state = trainer.init(params)
    del params
    print(f"[train] {args.arch} ({'full' if args.full else 'reduced'}) "
          f"cuts: {', '.join(cuts) if cuts else 'none'}")
    print(f"[train] {args.arch} ({'full' if args.full else 'reduced'}) "
          f"N={n_params/1e6:.1f}M x {args.workers} workers "
          f"opt={args.optimizer} p={args.period} "
          f"topo={args.topology} backend={args.backend} comm={args.comm} "
          f"compute={jnp.dtype(cfg.compute_dtype).name}"
          + (" overlap" if args.overlap else ""))
    state_gb = sum(x.nbytes for x in jax.tree_util.tree_leaves(state)) / 1e9
    print(f"[train] resident optimizer state {state_gb:.2f} GB "
          f"({args.workers} workers)")
    if args.comm == "axis":
        print(f"[train] worker mesh: {tuple(mesh.shape.items())} — state "
              f"sharded one worker per slot; gossip = ppermute over "
              f"'worker'")
        if args.model_parallel > 1:
            print(f"[train] 2D execution: each worker = "
                  f"{args.model_parallel}-device model-parallel group; "
                  f"packed rows sharded P('worker', 'model'); compression "
                  f"scales psum over 'model'")
    if args.backend == "pallas":
        # packed-resident state: params + moments live in the stacked
        # (K, rows, 128) kernel layout across steps; grads are produced
        # packed by differentiating through the unpack view, and
        # checkpoints are stored in the portable (backend-agnostic) form.
        spec = state.spec
        print(f"[train] resident packed state: K={spec.k} "
              f"rows={spec.rows} ({spec.rows * 128 / 1e6:.2f}M slots/"
              f"worker, {spec.n / 1e6:.2f}M live; "
              f"{(spec.rows * 128 - spec.n) / max(spec.rows * 128, 1):.1%} "
              f"tile padding)")

    if damping is not None:
        print(f"[train] batch damping: {damping.policy} chunks "
              f"{damping.min_chunks}..{damping.max_chunks} "
              f"({'per-worker' if damping.per_worker else 'global'} "
              f"signal); one compiled step across all levels")

    it = make_batch_iter(cfg, args.workers, args.batch, args.seq, args.skew)
    done = 0
    log = None
    while done < args.steps:
        # step 1 alone (it compiles), then chunks of --log-every
        n = 1 if done == 0 else min(args.log_every, args.steps - done)
        # the log CONTINUES across fit calls: comm_mb / wall_s / grad
        # evals are cumulative, and schedule-entry comm accounting stays
        # aligned round to round; float(loss) at the log point syncs
        state, log = trainer.fit(state, it, n, log_every=n, log=log)
        done += n
        wall = log.wall_s
        rate = (f"first step {wall[0]:.1f} s incl. compile" if done == 1
                else f"{(wall[-1] - wall[0]) / (done - 1) * 1e3:.0f} "
                     f"ms/step after step 1")
        print(f"[train] step {done:5d} loss={log.loss[-1]:.4f} "
              f"consensus={log.consensus[-1]:.3e} "
              f"comm={log.comm_mb[-1]:.1f}MB "
              f"evals={log.grad_evals[-1]} ({rate})")
        if args.ckpt and args.ckpt_every and done % args.ckpt_every == 0:
            save(args.ckpt, state, step=done,
                 meta={"arch": args.arch, "optimizer": args.optimizer})
            print(f"[train] checkpointed -> {args.ckpt}")
    if args.ckpt:
        save(args.ckpt, state, step=done,
             meta={"arch": args.arch, "optimizer": args.optimizer})
        print(f"[train] final checkpoint -> {args.ckpt}")
    return TrainRun(args=args, cfg=cfg, cuts=cuts, trainer=trainer,
                    state=state, log=log, n_params=n_params)


if __name__ == "__main__":
    main()
