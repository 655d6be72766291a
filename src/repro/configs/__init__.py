"""Architecture config registry: ``get_arch(id)`` / ``get_reduced(id)``.

Every assigned architecture is a selectable config (``--arch <id>``); each
module cites its source in the docstring and carries a ``reduced()``
CPU-smoke variant (<=2 layers, d_model<=512, <=4 experts).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Optional, Tuple

from repro.configs.base import (ArchConfig, InputShape, INPUT_SHAPES,
                                ModelConfig, ParallelConfig)

_MODULES: Dict[str, str] = {
    "llama3.2-1b": "repro.configs.llama3_2_1b",
    "qwen1.5-32b": "repro.configs.qwen1_5_32b",
    "starcoder2-15b": "repro.configs.starcoder2_15b",
    "phi3.5-moe-42b-a6.6b": "repro.configs.phi3_5_moe",
    "rwkv6-3b": "repro.configs.rwkv6_3b",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
    "zamba2-7b": "repro.configs.zamba2_7b",
    "yi-6b": "repro.configs.yi_6b",
    "llama4-maverick-400b-a17b": "repro.configs.llama4_maverick",
    "phi-3-vision-4.2b": "repro.configs.phi3_vision",
}

# (arch, shape) combos that are skipped by design — see DESIGN.md §6.
SKIPS = {
    ("whisper-large-v3", "long_500k"):
        "enc-dec decoder positionally capped; 524k-token decode is "
        "architecturally meaningless and whisper has no sub-quadratic "
        "decoder variant",
}


def list_archs() -> List[str]:
    return sorted(_MODULES)


def get_arch(arch_id: str) -> ArchConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; have {list_archs()}")
    return importlib.import_module(_MODULES[arch_id]).FULL


def cut_arch(arch: ArchConfig, *, layers: Optional[int] = None,
             vocab: Optional[int] = None) -> Tuple[ArchConfig, Tuple[str, ...]]:
    """Size a published config for one chip without touching any width.

    ``layers`` keeps the first N of the published layers (a depth cut: the
    rest would be further pipeline stages). ``vocab`` keeps the first V
    rows of the vocabulary, at least one eighth of the published count: a
    sliced vocabulary is a smaller vocabulary, so token ids are drawn from
    the slice and logits and the loss are over it. Returns the cut config
    and one human-readable line per cut made."""
    m = arch.model
    repl, cuts = {}, []
    if layers is not None:
        if not 1 <= layers <= m.n_layers:
            raise ValueError(f"--layers {layers} outside [1, {m.n_layers}] "
                             f"for {m.arch_id}")
        if layers < m.n_layers:
            repl["n_layers"] = layers
            cuts.append(f"n_layers {m.n_layers} -> {layers}")
    if vocab is not None:
        floor = -(-m.vocab_size // 8)
        if not floor <= vocab <= m.vocab_size:
            raise ValueError(f"--vocab {vocab} outside [{floor}, "
                             f"{m.vocab_size}] (at least 1/8 of the "
                             f"published vocabulary of {m.arch_id})")
        if vocab < m.vocab_size:
            repl["vocab_size"] = vocab
            cuts.append(f"vocab_size {m.vocab_size} -> {vocab}")
    if not repl:
        return arch, ()
    return (dataclasses.replace(arch, model=dataclasses.replace(m, **repl)),
            tuple(cuts))


def sized_arch(arch_id: str, full: bool, layers: Optional[int] = None,
               vocab: Optional[int] = None
               ) -> Tuple[ArchConfig, Tuple[str, ...]]:
    """The arch config a launch runs: the reduced preset, or the published
    one with the ``--layers``/``--vocab`` cuts of :func:`cut_arch` (only
    with ``--full``). Raises ValueError on a cut it cannot make."""
    if not full:
        if layers is not None or vocab is not None:
            raise ValueError("--layers/--vocab cut the published config; "
                             "pass --full")
        return get_reduced(arch_id), ()
    return cut_arch(get_arch(arch_id), layers=layers, vocab=vocab)


def get_reduced(arch_id: str) -> ArchConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; have {list_archs()}")
    return importlib.import_module(_MODULES[arch_id]).reduced()


__all__ = ["ArchConfig", "ModelConfig", "ParallelConfig", "InputShape",
           "INPUT_SHAPES", "SKIPS", "list_archs", "get_arch", "get_reduced",
           "cut_arch", "sized_arch"]
