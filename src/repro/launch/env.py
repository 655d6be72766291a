"""Process-level XLA environment setup shared by every entrypoint.

jax reads ``XLA_FLAGS`` exactly once, at backend initialization, so any
flag this module manages must be installed BEFORE the first jax import in
the process. The module itself imports nothing heavier than ``os`` — it
is safe (and intended) to import at the very top of a driver script:

    from repro.launch import env
    env.setup()          # then `import jax`

``setup()`` also fixes where JAX keeps its persistent compilation
cache: a ``JAX_COMPILATION_CACHE_DIR`` the caller exported is left alone,
and otherwise it is set to ``<checkout>/.jax_cache`` — a fixed path
(git-ignored, the one ``scripts/tier1.sh`` uses), so a second run of the
same program finds what the first one compiled. No other cache location
is set anywhere in the code.

Two rules govern every helper here:

* **append, never clobber** — a pre-set ``XLA_FLAGS`` survives intact;
  new flags are appended after it (the Python port of tier1.sh's
  ``${XLA_FLAGS:+ $XLA_FLAGS}`` idiom), and
* **first writer wins per flag** — a flag whose name is already present
  in ``XLA_FLAGS`` is never added again, so callers (CI, tier1.sh, a
  user shell) keep full control by exporting it themselves.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping, MutableMapping, Optional, Sequence

HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"
CACHE_DIR_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: this file is <checkout>/src/repro/launch/env.py
DEFAULT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                        / ".jax_cache")

# Async-collective + latency-hiding-scheduler flags: let XLA issue
# collective-permute-start early and schedule independent fused-Adam
# compute between start and done — the compiler-side half of the overlap
# story (`overlap=True` in make_optimizer is the algorithm-side half).
# CPU-only jaxlib builds ABORT at startup on unknown XLA_FLAGS names, so
# these are only installed when a GPU plugin is importable (see
# gpu_flags_supported) or the caller forces REPRO_ASYNC_COLLECTIVES=1.
ASYNC_COLLECTIVE_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def _flag_name(flag: str) -> str:
    return flag.split("=", 1)[0]


def _present_names(xla_flags: str) -> set:
    return {_flag_name(tok) for tok in xla_flags.split()}


def ensure_xla_flags(flags: Sequence[str], *,
                     env: Optional[MutableMapping[str, str]] = None) -> str:
    """Append each of ``flags`` to ``XLA_FLAGS`` unless a flag of the
    same name is already present (pre-set values always win). Returns the
    resulting ``XLA_FLAGS`` string."""
    env = os.environ if env is None else env
    current = env.get("XLA_FLAGS", "")
    have = _present_names(current)
    add = [f for f in flags if _flag_name(f) not in have]
    if add:
        current = " ".join(([current] if current else []) + add)
        env["XLA_FLAGS"] = current
    return current


def host_device_count(env: Optional[Mapping[str, str]] = None
                      ) -> Optional[int]:
    """The forced host-device count currently in ``XLA_FLAGS``, or None
    when the flag is absent/unparsable."""
    env = os.environ if env is None else env
    for tok in env.get("XLA_FLAGS", "").split():
        if _flag_name(tok) == HOST_DEVICE_FLAG and "=" in tok:
            try:
                return int(tok.split("=", 1)[1])
            except ValueError:
                return None
    return None


def ensure_host_devices(n: Optional[int] = None, *,
                        env: Optional[MutableMapping[str, str]] = None
                        ) -> int:
    """Force ``n`` virtual host CPU devices unless the caller already
    forced a count via ``XLA_FLAGS``. ``n`` defaults to the
    ``REPRO_HOST_DEVICES`` env var, then 8 (the tier1.sh convention).
    Returns the count actually in effect."""
    env = os.environ if env is None else env
    existing = host_device_count(env)
    if existing is not None:
        return existing
    if n is None:
        n = int(env.get("REPRO_HOST_DEVICES", "8"))
    ensure_xla_flags([f"{HOST_DEVICE_FLAG}={int(n)}"], env=env)
    return int(n)


def gpu_flags_supported(env: Optional[Mapping[str, str]] = None) -> bool:
    """Whether this process's XLA will accept ``--xla_gpu_*`` flags.

    CPU-only jaxlib builds treat unknown ``XLA_FLAGS`` names as a FATAL
    parse error at backend init, so the async flags must never reach them.
    A GPU plugin being importable is the pre-jax-import signal that the
    flags are registered; ``REPRO_ASYNC_COLLECTIVES=1`` / ``=0`` forces
    the answer either way (e.g. for a TPU pod driver or a broken probe).
    """
    env = os.environ if env is None else env
    force = env.get("REPRO_ASYNC_COLLECTIVES")
    if force is not None:
        return force.lower() not in ("0", "false", "")
    import importlib.util
    return any(importlib.util.find_spec(mod) is not None
               for mod in ("jax_cuda12_plugin", "jax_cuda11_plugin",
                           "jax_rocm60_plugin"))


def enable_async_collectives(*, env: Optional[MutableMapping[str, str]]
                             = None) -> str:
    """Install the async-collective / latency-hiding-scheduler flags when
    the backend supports them (appended, never clobbering). Returns the
    resulting ``XLA_FLAGS`` (unchanged when unsupported)."""
    env = os.environ if env is None else env
    if not gpu_flags_supported(env):
        return env.get("XLA_FLAGS", "")
    return ensure_xla_flags(ASYNC_COLLECTIVE_FLAGS, env=env)


def ensure_compile_cache(env: Optional[MutableMapping[str, str]] = None
                         ) -> str:
    """Point JAX's persistent compilation cache at
    :data:`DEFAULT_CACHE_DIR` unless ``JAX_COMPILATION_CACHE_DIR`` is
    already set (then it is left as it is). Returns the directory in
    effect."""
    env = os.environ if env is None else env
    if not env.get(CACHE_DIR_VAR):
        env[CACHE_DIR_VAR] = DEFAULT_CACHE_DIR
    return env[CACHE_DIR_VAR]


def setup(host_devices: Optional[int] = None, *,
          async_collectives: bool = True,
          platform: Optional[str] = None,
          env: Optional[MutableMapping[str, str]] = None) -> int:
    """One-call environment setup for drivers and benchmarks. Must run
    before jax initializes. Returns the host-device count in effect."""
    env = os.environ if env is None else env
    if platform is not None:
        env.setdefault("JAX_PLATFORMS", platform)
    ensure_compile_cache(env=env)
    n = ensure_host_devices(host_devices, env=env)
    if async_collectives:
        enable_async_collectives(env=env)
    return n
