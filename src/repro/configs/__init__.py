"""Config registry: ``get_config(arch_id)`` -> ArchConfig."""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import (ArchConfig, InputShape, INPUT_SHAPES,
                                LONG_CONTEXT_OK)

_ARCHS = (
    "minicpm3_4b", "mamba2_2p7b", "hymba_1p5b", "gemma3_1b", "llama3p2_1b",
    "whisper_base", "qwen2_vl_7b", "qwen3_1p7b", "deepseek_v3_671b",
    "deepseek_v2_lite_16b",
)

_BY_ID: dict[str, ArchConfig] = {}


def _load():
    if _BY_ID:
        return
    for mod_name in _ARCHS:
        mod = importlib.import_module(f"repro.configs.{mod_name}")
        cfg: ArchConfig = mod.CONFIG
        _BY_ID[cfg.arch_id] = cfg


def get_config(arch_id: str) -> ArchConfig:
    _load()
    return _BY_ID[arch_id]


def all_arch_ids() -> list[str]:
    _load()
    return sorted(_BY_ID)


def resolve_config(arch_id: str, *, reduced: bool = False, layers: int = 0,
                   d_model: int = 0, seq: int = 64) -> ArchConfig:
    """The entry points' config.  ``reduced`` shrinks every width to the
    smoke-test variant (``layers`` defaults to 4, ``d_model`` to 256).
    Without it, ``layers`` cuts the depth only: every width stays as
    published."""
    cfg = get_config(arch_id)
    if reduced:
        return cfg.reduced(n_layers=layers or 4, d_model=d_model or 256,
                           seq=seq)
    if d_model:
        raise ValueError("d_model only applies to the reduced variant")
    if layers:
        if layers <= cfg.n_enc_layers:
            raise ValueError(f"{arch_id} has {cfg.n_enc_layers} encoder "
                             f"layers; layers must exceed that")
        cfg = dataclasses.replace(cfg, n_layers=layers)
    return cfg
