"""Serving engine: cache specs, decode steps, and the batched bucket engine.

``cache_spec(cfg, batch, seq_len)`` returns the ShapeDtypeStruct pytree of
the KV/SSM cache for the dry-run (no allocation); ``make_serve_step``
returns the jit-able one-token decode function the decode shapes lower;
:class:`DecodeEngine` is the high-throughput serving path — padded-bucket
batching over a compile-once shape cache, batched prefill + KV-cache
decode, optional bf16 cache storage, and lock-free param hot-swap via a
``serve.publish.ParamStore``.

Long-context rule (DESIGN.md §6): for ``long_500k`` dense archs substitute
``cfg.long_context_window`` as a rotating sliding window — the cache is
window-sized and the step cost O(window) (sub-quadratic); SSM/hybrid archs
decode against their O(1) recurrent state natively.

Why seq padding is exact (the bucket contract): decode attention masks
cache slots with ``slot <= index`` and writes the new token at ``index``.
So a prompt of true length L right-padded to a bucket length S prefills
pad K/V into slots [L, S), but the engine then REWINDS the cache index to
L-1 and re-feeds the last real token: that decode step recomputes slot
L-1's K/V bit-identically (same token, same rope position), attends only
to slots <= L-1, and yields exactly the logits an unpadded prefill would
have produced — and every later step overwrites one pad slot before the
mask can reach it. This holds for positionally-indexed, non-rotating KV
caches (dense/moe/vlm without a sliding window); recurrent families
(ssm/hybrid/audio) and rotating windows fold pads into state, so for
those the engine pads only the batch dim and requires an exact seq match.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import attention, build_model, hybrid, rwkv6, whisper

PyTree = Any


def effective_config(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Apply the long-context window substitution for long_500k."""
    if (shape.name == "long_500k" and cfg.long_context_window
            and cfg.family in ("dense", "moe", "vlm")):
        return dataclasses.replace(cfg,
                                   sliding_window=cfg.long_context_window)
    if (shape.name == "long_500k" and cfg.family == "hybrid"
            and cfg.long_context_window):
        return dataclasses.replace(cfg,
                                   sliding_window=cfg.long_context_window)
    return cfg


def kv_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.sliding_window:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def cache_spec(cfg: ModelConfig, batch: int, seq_len: int,
               cache_dtype=jnp.bfloat16) -> PyTree:
    """ShapeDtypeStruct stand-in of the decode-input cache."""
    L = cfg.n_layers
    hd = cfg.resolved_head_dim
    idx = jax.ShapeDtypeStruct((), jnp.int32)
    if cfg.family in ("dense", "moe", "vlm"):
        S = kv_cache_len(cfg, seq_len)
        kv = jax.ShapeDtypeStruct((L, batch, S, cfg.n_kv_heads, hd),
                                  cache_dtype)
        return attention.KVCache(kv, kv, idx)
    if cfg.family == "ssm":
        d = cfg.d_model
        H = d // cfg.rwkv_head_size
        hs = cfg.rwkv_head_size
        return rwkv6.RWKVCache(
            jax.ShapeDtypeStruct((L, batch, d), cfg.compute_dtype),
            jax.ShapeDtypeStruct((L, batch, d), cfg.compute_dtype),
            jax.ShapeDtypeStruct((L, batch, H, hs, hs), jnp.float32), idx)
    if cfg.family == "hybrid":
        di, N = cfg.d_inner, cfg.ssm_state
        H = cfg.resolved_ssm_heads
        P = di // H
        A = hybrid.n_attn_sites(cfg)
        S = kv_cache_len(cfg, seq_len)
        return hybrid.HybridCache(
            jax.ShapeDtypeStruct((L, batch, cfg.ssm_conv - 1, di + 2 * N),
                                 cfg.compute_dtype),
            jax.ShapeDtypeStruct((L, batch, H, P, N), jnp.float32),
            jax.ShapeDtypeStruct((A, batch, S, cfg.n_kv_heads, hd),
                                 cache_dtype),
            jax.ShapeDtypeStruct((A, batch, S, cfg.n_kv_heads, hd),
                                 cache_dtype), idx)
    if cfg.family == "audio":
        S = seq_len
        kv = jax.ShapeDtypeStruct((L, batch, S, cfg.n_kv_heads, hd),
                                  cache_dtype)
        xkv = jax.ShapeDtypeStruct((L, batch, cfg.n_audio_ctx,
                                    cfg.n_kv_heads, hd), cache_dtype)
        return whisper.WhisperCache(kv, kv, xkv, xkv, idx)
    raise KeyError(cfg.family)


def make_serve_step(cfg: ModelConfig) -> Callable:
    """(params, cache, token) -> (logits, cache) — the decode-shape target."""
    api = build_model(cfg)

    def serve_step(params, cache, token):
        return api.decode_step(params, cache, token)

    return serve_step


def make_prefill_step(cfg: ModelConfig, cache_len: int) -> Callable:
    api = build_model(cfg)

    def prefill_step(params, batch):
        return api.prefill(params, batch, cache_len=cache_len)

    return prefill_step


# ----------------------------- request serving ------------------------------


def greedy_generate(cfg: ModelConfig, params: PyTree, batch: PyTree,
                    n_new: int, *, cache_len: Optional[int] = None
                    ) -> jax.Array:
    """Batched greedy decoding used by the serving example: prefill the
    prompt, then n_new jit-compiled decode steps."""
    api = build_model(cfg)
    prompt = batch["tokens"]
    B = prompt.shape[0]
    if n_new < 0:
        raise ValueError(f"n_new must be >= 0, got {n_new}")
    if n_new == 0:
        return jnp.zeros((B, 0), jnp.int32)
    need = prompt.shape[1] + n_new + (cfg.n_patches or 0)
    # `cache_len or need` would silently treat an explicit 0 as unset
    if cache_len is None:
        cache_len = need
    elif cache_len < need:
        raise ValueError(
            f"cache_len={cache_len} cannot hold prompt + {n_new} new "
            f"tokens (need >= {need})")
    logits, cache = api.prefill(params, batch, cache_len=cache_len)
    tok = jnp.argmax(logits[:, -1, :] if logits.ndim == 3 else logits,
                     axis=-1).astype(jnp.int32)
    step = jax.jit(api.decode_step)
    out = [tok]
    for _ in range(n_new - 1):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    return jnp.stack(out, axis=1)


# --------------------------- batched decode engine ---------------------------


def cast_cache(cache: PyTree, cache_dtype) -> PyTree:
    """Cast a decode cache's float leaves to ``cache_dtype`` (bf16 halves
    KV HBM and decode read bandwidth); integer leaves (the write index)
    pass through. ``None`` is the identity."""
    if cache_dtype is None:
        return cache
    return jax.tree_util.tree_map(
        lambda x: x.astype(cache_dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, cache)


def select_bucket(buckets: Sequence[Tuple[int, int]], batch: int, seq: int,
                  *, pad_seq: bool = True) -> Tuple[int, int]:
    """The tightest ``(batch, seq)`` bucket that holds a request group.

    Seq is padded up to the nearest bucket seq (exact match required when
    ``pad_seq`` is False — recurrent caches); batch is padded up to the
    smallest bucket batch >= ``batch``, falling back to the largest
    available (the caller then splits the group across calls)."""
    fits = [b for b in buckets if (b[1] >= seq if pad_seq else b[1] == seq)]
    if not fits:
        raise ValueError(
            f"no bucket holds seq={seq} (pad_seq={pad_seq}); "
            f"buckets={list(buckets)}")
    best_seq = min(s for _, s in fits)
    fits = [b for b in fits if b[1] == best_seq]
    exact = [b for b in fits if b[0] >= batch]
    return min(exact) if exact else max(fits)


class DecodeEngine:
    """Padded-bucket batched serving engine with compile-once shapes.

    Requests are grouped by prompt length and padded — batch dim up to
    the bucket's batch size, seq dim (where exact; see the module
    docstring) up to the bucket's seq — so every prefill/decode lowers to
    one of ``len(buckets)`` compiled ``(batch, seq)`` shapes. The shape
    cache is pinned by two ``RecompileWatch``es (JXL003): a request mix
    that escapes the bucket set raises instead of silently compiling per
    shape. Params come from a ``serve.publish.ParamStore`` (lock-free
    hot-swap: each generate call decodes one complete versioned snapshot)
    or a plain param pytree.

    Args:
      cfg: the model config (any registry family).
      source: a ``ParamStore`` or a param pytree.
      buckets: the compiled ``(batch, seq)`` shape set.
      max_new_tokens: per-bucket decode cache headroom. The cache length
        is ``seq + max_new_tokens`` (a static per-bucket constant), so
        every ``n_new <= max_new_tokens`` reuses the same compiled step.
      cache_dtype: optional storage dtype for the decode cache (e.g.
        ``jnp.bfloat16``); ``None`` keeps the prefill dtype. Must not be
        wider than ``cfg.compute_dtype``.
      recompile_limit: distinct-signature budget per watch; defaults to
        ``len(buckets)``.
    """

    def __init__(self, cfg: ModelConfig, source: Any, *,
                 buckets: Sequence[Tuple[int, int]] = ((1, 32), (8, 32)),
                 max_new_tokens: int = 32,
                 cache_dtype: Any = None,
                 recompile_limit: Optional[int] = None):
        from repro.analysis.jaxpr_lint import RecompileWatch

        if not buckets:
            raise ValueError("DecodeEngine needs at least one bucket")
        self.cfg = cfg
        self.api = build_model(cfg)
        self.buckets = tuple(sorted({(int(b), int(s)) for b, s in buckets}))
        self.max_new_tokens = int(max_new_tokens)
        if cache_dtype is not None and (jnp.dtype(cache_dtype).itemsize
                                        > jnp.dtype(cfg.compute_dtype).itemsize):
            # decode_attention promotes scores to the wider of (q, cache)
            # dtype, so an upcast cache would widen the hidden-state scan
            # carry mid-decode; only storage downcasts are meaningful.
            raise ValueError(
                f"cache_dtype {jnp.dtype(cache_dtype).name} is wider than "
                f"compute_dtype {jnp.dtype(cfg.compute_dtype).name}; the KV "
                "cache dtype may only narrow storage")
        self.cache_dtype = cache_dtype
        self._source = source
        # exact-seq-padding contract: positional, non-rotating KV caches
        self.pad_seq = (cfg.family in ("dense", "moe", "vlm")
                        and not cfg.sliding_window)
        self._prefill = jax.jit(self.api.prefill,
                                static_argnames=("cache_len",))
        self._decode = jax.jit(self.api.decode_step)
        limit = (len(self.buckets) if recompile_limit is None
                 else recompile_limit)
        self._watch_prefill = RecompileWatch("engine.prefill", limit=limit)
        self._watch_decode = RecompileWatch("engine.decode", limit=limit)
        self.last_version = 0

    # ------------------------------ internals ------------------------------

    def _params(self) -> Tuple[int, PyTree]:
        snap = getattr(self._source, "snapshot", None)
        if snap is not None:
            return snap()
        return 0, self._source

    def cache_len_for(self, seq: int) -> int:
        """Static per-bucket cache length: prompt slots + decode headroom
        (+ the vlm patch prefix the prefill prepends)."""
        extra = self.cfg.n_patches or 0
        return kv_cache_len(self.cfg, seq + extra + self.max_new_tokens)

    @property
    def compile_counts(self) -> dict:
        """Distinct compiled signatures per phase — pinned at the bucket-
        set size (the serving bench records and asserts this)."""
        return {"prefill": len(self._watch_prefill.signatures),
                "decode": len(self._watch_decode.signatures)}

    # ------------------------------ execution ------------------------------

    def generate_batch(self, tokens: jax.Array, n_new: int, *,
                       true_len: Optional[int] = None,
                       extras: Optional[dict] = None) -> jax.Array:
        """Greedy-decode one bucket-shaped batch.

        ``tokens``: (B, S) int32 with (B, S) in the bucket set, right-
        padded past ``true_len`` (the shared real prompt length; defaults
        to S). ``extras`` carries family-specific prefill inputs
        (``patches`` / ``audio_embeds``). Returns (B, n_new) int32.
        """
        B, S = tokens.shape
        if (B, S) not in self.buckets:
            raise ValueError(
                f"batch shape ({B}, {S}) is not in the bucket set "
                f"{list(self.buckets)} — pad requests with generate()")
        if n_new < 0:
            raise ValueError(f"n_new must be >= 0, got {n_new}")
        if n_new > self.max_new_tokens:
            raise ValueError(
                f"n_new={n_new} exceeds max_new_tokens="
                f"{self.max_new_tokens} (the per-bucket cache headroom)")
        if n_new == 0:
            return jnp.zeros((B, 0), jnp.int32)
        L = S if true_len is None else int(true_len)
        if not 0 < L <= S:
            raise ValueError(f"true_len={L} out of range for seq {S}")
        if L < S and not self.pad_seq:
            raise ValueError(
                f"family {self.cfg.family!r} (or a rotating window) folds "
                "pad tokens into its decode state; seq must match a "
                "bucket exactly (pad_seq=False)")
        version, params = self._params()
        batch = {"tokens": tokens, **(extras or {})}
        cl = self.cache_len_for(S)
        # cache_len is a pure function of the bucket, so the batch shapes
        # fully determine the compiled program — observe/check pins the
        # shape cache at the bucket-set size
        self._watch_prefill.observe(params, batch)
        self._watch_prefill.check()
        logits, cache = self._prefill(params, batch, cache_len=cl)
        cache = cast_cache(cache, self.cache_dtype)
        if L == S:
            tok = jnp.argmax(
                logits[:, -1, :] if logits.ndim == 3 else logits,
                axis=-1).astype(jnp.int32)
        else:
            # rewind + re-feed: recompute slot L-1 (bit-identical K/V),
            # attend only to real slots, recover the true last-position
            # logits the padded prefill did not return
            extra = self.cfg.n_patches or 0
            cache = cache._replace(
                index=jnp.asarray(L - 1 + extra, jnp.int32))
            tok = tokens[:, L - 1]
            self._watch_decode.observe(params, cache, tok)
            self._watch_decode.check()
            logits, cache = self._decode(params, cache, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = [tok]
        for _ in range(n_new - 1):
            self._watch_decode.observe(params, cache, tok)
            self._watch_decode.check()
            logits, cache = self._decode(params, cache, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(tok)
        self.last_version = version
        return jnp.stack(out, axis=1)

    def generate(self, prompts: Sequence[jax.Array], n_new: int, *,
                 extras: Optional[dict] = None) -> List[jax.Array]:
        """Serve a ragged request list: group by prompt length, pad each
        group to its bucket (batch rows replicate the first request; pad
        rows are dropped on the way out), split groups larger than the
        biggest bucket. ``extras`` holds per-request prefill inputs
        (``patches`` / ``audio_embeds``), one row per prompt. Returns one
        (n_new,) int32 array per request, in request order."""
        prompts = [jnp.asarray(p) for p in prompts]
        if any(p.ndim != 1 for p in prompts):
            raise ValueError("generate() takes 1-D token prompts; use "
                             "generate_batch() for pre-batched input")
        groups: dict = {}
        for i, p in enumerate(prompts):
            groups.setdefault(int(p.shape[0]), []).append(i)
        results: List[Optional[jax.Array]] = [None] * len(prompts)
        for L, idxs in sorted(groups.items()):
            pending = idxs
            while pending:
                B, S = select_bucket(self.buckets, len(pending), L,
                                     pad_seq=self.pad_seq)
                take = pending[:B]
                pending = pending[B:]
                # batch-dim padding replicates the group's first request
                idx = take + [take[0]] * (B - len(take))
                rows = [jnp.pad(prompts[i], (0, S - L)) for i in idx]
                out = self.generate_batch(
                    jnp.stack(rows).astype(jnp.int32), n_new, true_len=L,
                    extras=None if extras is None else {
                        k: v[jnp.asarray(idx)] for k, v in extras.items()})
                for r, i in enumerate(take):
                    results[i] = out[r]
        return results  # type: ignore[return-value]
