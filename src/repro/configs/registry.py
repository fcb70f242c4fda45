"""Registry mapping --arch ids to ModelConfig builders."""

from __future__ import annotations

import importlib

from repro.models.config import ModelConfig

_ARCH_MODULES = {
    "seamless-m4t-medium": "repro.configs.seamless_m4t_medium",
    "stablelm-12b": "repro.configs.stablelm_12b",
    "qwen2.5-3b": "repro.configs.qwen2_5_3b",
    "internlm2-20b": "repro.configs.internlm2_20b",
    "h2o-danube-1.8b": "repro.configs.h2o_danube_1_8b",
    "pixtral-12b": "repro.configs.pixtral_12b",
    "deepseek-moe-16b": "repro.configs.deepseek_moe_16b",
    "granite-moe-1b-a400m": "repro.configs.granite_moe_1b_a400m",
    "mamba2-780m": "repro.configs.mamba2_780m",
    "zamba2-1.2b": "repro.configs.zamba2_1_2b",
    "roshambo-nullhop": "repro.configs.roshambo",
    "moonlight-16b-a3b": "repro.configs.moonlight_16b_a3b",
}

# the dry-run / training architectures; roshambo runs through the NullHop
# executor, and moonlight is served with its experts in host memory
# (repro.models.moonlight), neither through build_model
ARCHS = tuple(k for k in _ARCH_MODULES
              if k not in ("roshambo-nullhop", "moonlight-16b-a3b"))


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(_ARCH_MODULES[name])
    cfg = mod.config()
    return cfg.replace(**overrides) if overrides else cfg


def list_archs() -> tuple[str, ...]:
    return ARCHS


def smoke_config(name: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    mod = importlib.import_module(_ARCH_MODULES[name])
    return mod.smoke_config()
