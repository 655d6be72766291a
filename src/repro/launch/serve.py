"""Serving driver: bucketed batch decode through the DecodeEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --buckets 2x32,6x32 --requests 8 --new-tokens 32

Without ``--full`` it serves the arch's reduced preset (the CPU test
size); ``--full`` takes the published widths, and ``--layers N`` /
``--vocab V`` cut depth and vocabulary as in ``repro.launch.train``. Params
are served from a ParamStore behind the lock-free version pointer (a
fresh init from seed 0, or the store an in-process caller passes to
``main(argv, store=...)``, e.g. one published from a training state). The
requests go through ``DecodeEngine.generate``: grouped into the compiled
(batch, seq) bucket set, and the compile cache is pinned at the bucket
count — a bucket escape raises instead of silently recompiling. Reports
the warm (compiling) and steady passes, per-token decode latency,
tokens/s and the compile counts — the serving-side counterpart of
launch/train.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    # env flags (device count, compile cache, async collectives) BEFORE
    # jax initializes
    from repro.launch import env as _env
    _env.setup()

import jax
import jax.numpy as jnp

from repro.configs import list_archs, sized_arch
from repro.models import build_model
from repro.serve import DecodeEngine, ParamStore


def parse_buckets(spec: str):
    """``"1x32,8x32"`` -> ((1, 32), (8, 32))."""
    out = []
    for part in spec.split(","):
        b, s = part.lower().split("x")
        out.append((int(b), int(s)))
    return tuple(out)


@dataclasses.dataclass
class ServeRun:
    """What one ``main(argv)`` run leaves behind: the engine's (n_new,)
    greedy outputs per request, the compile counts after both passes,
    and the wall times (warm includes compiling)."""
    cfg: Any
    buckets: Tuple[Tuple[int, int], ...]
    outputs: List[jax.Array]
    compile_counts: Dict[str, int]
    warm_s: float
    steady_s: float


def main(argv: Optional[Sequence[str]] = None, *,
         store: Optional[ParamStore] = None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=list_archs())
    ap.add_argument("--full", action="store_true",
                    help="published widths (needs a chip)")
    ap.add_argument("--layers", type=int, default=None,
                    help="with --full: keep the first N layers")
    ap.add_argument("--vocab", type=int, default=None,
                    help="with --full: keep the first V vocabulary rows")
    ap.add_argument("--buckets", default="2x32,6x32",
                    help="comma-separated batchxseq compile buckets")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--cache-dtype", default=None,
                    choices=[None, "bfloat16", "float32"],
                    help="KV-cache storage dtype (default: prefill dtype)")
    args = ap.parse_args(argv)

    try:
        arch, cuts = sized_arch(args.arch, args.full, args.layers,
                                args.vocab)
    except ValueError as e:
        ap.error(str(e))
    cfg = arch.model
    if store is None:
        store = ParamStore()
        store.publish(build_model(cfg).init(jax.random.PRNGKey(0)))

    cache_dtype = (None if args.cache_dtype is None
                   else jnp.dtype(args.cache_dtype))
    engine = DecodeEngine(cfg, store, buckets=parse_buckets(args.buckets),
                          max_new_tokens=args.new_tokens,
                          cache_dtype=cache_dtype)
    keys = jax.random.split(jax.random.PRNGKey(1), args.requests)
    prompts = [jax.random.randint(k, (args.prompt_len,), 0, cfg.vocab_size,
                                  jnp.int32) for k in keys]
    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = jax.random.normal(
            keys[0], (args.requests, cfg.n_patches, 1024))
    if cfg.family == "audio":
        extras["audio_embeds"] = jax.random.normal(
            keys[0], (args.requests, cfg.n_audio_ctx, cfg.d_model))

    # pass 1 compiles every bucket it touches; pass 2 must reuse them
    t0 = time.perf_counter()
    jax.block_until_ready(engine.generate(prompts, args.new_tokens,
                                          extras=extras or None))
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    outputs = jax.block_until_ready(engine.generate(
        prompts, args.new_tokens, extras=extras or None))
    t_steady = time.perf_counter() - t0

    total = args.requests * args.new_tokens
    print(f"[serve] {args.arch} ({'full' if args.full else 'reduced'}) "
          f"cuts: {', '.join(cuts) if cuts else 'none'}")
    print(f"[serve] requests={args.requests} prompt={args.prompt_len} "
          f"new={args.new_tokens} buckets={engine.buckets} "
          f"v{engine.last_version}")
    print(f"[serve] warm {t_warm * 1e3:.0f} ms | steady "
          f"{t_steady / args.new_tokens * 1e3:.1f} ms/tok | "
          f"{total / t_steady:.1f} tok/s | compiles {engine.compile_counts}")
    return ServeRun(cfg=cfg, buckets=engine.buckets, outputs=outputs,
                    compile_counts=engine.compile_counts, warm_s=t_warm,
                    steady_s=t_steady)


if __name__ == "__main__":
    main()
