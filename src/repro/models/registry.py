"""Architecture registry: ``--arch <id>`` -> (config, model module).

Every model module exposes: init(cfg, key), forward(...), loss(cfg,
params, batch, *, remat), init_cache(cfg, B, T), decode_step(cfg,
params, cache, tokens).  ``batch_spec``/``decode_spec`` document the
input names each family needs (used by launch.input_specs).
"""
from __future__ import annotations

import dataclasses
import importlib
from types import ModuleType

import jax

from repro.configs.base import ArchConfig

_CONFIG_MODULES = {
    "qwen2.5-32b": "qwen2_5_32b",
    "starcoder2-3b": "starcoder2_3b",
    "qwen1.5-32b": "qwen1_5_32b",
    "stablelm-12b": "stablelm_12b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "internvl2-2b": "internvl2_2b",
    "rwkv6-7b": "rwkv6_7b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "whisper-large-v3": "whisper_large_v3",
}

_FAMILY_MODULES = {
    "dense": "repro.models.transformer",
    "vlm": "repro.models.transformer",  # backbone; vision stub via prefix_embeds
    "moe": "repro.models.moe",
    "ssm": "repro.models.rwkv",
    "hybrid": "repro.models.rglru",
    "audio": "repro.models.whisper",
}

ARCH_IDS = tuple(_CONFIG_MODULES)


@dataclasses.dataclass(frozen=True)
class Arch:
    cfg: ArchConfig
    module: ModuleType

    @property
    def name(self) -> str:
        return self.cfg.name

    def tiny(self) -> "Arch":
        return Arch(self.cfg.tiny(), self.module)

    def init(self, key) -> dict:
        """Random parameters, built under ``jit`` so each weight is drawn
        straight into its dtype: eager init materializes every stacked
        layer weight in float32 first, which does not fit one chip's HBM
        at full width."""
        return jax.jit(self.module.init, static_argnums=0)(self.cfg, key)


def get_config(arch_id: str) -> ArchConfig:
    if arch_id not in _CONFIG_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_CONFIG_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_CONFIG_MODULES[arch_id]}")
    return mod.CONFIG


def get(arch_id: str) -> Arch:
    cfg = get_config(arch_id)
    return Arch(cfg, importlib.import_module(_FAMILY_MODULES[cfg.family]))


def all_archs() -> dict[str, Arch]:
    return {a: get(a) for a in ARCH_IDS}
