"""Architecture config registry.

One module per assigned architecture (exact published dims) plus the
paper's own evaluation models (Llama3 family, Llama4-Scout) used by the
RPU simulator benchmarks.  ``get_config(name)`` accepts either the
registry id (``qwen2.5-14b``) or the module name (``qwen2_5_14b``).

``reduced_config(cfg)`` returns a tiny same-family config for CPU smoke
tests (few layers / small widths / few experts), per the assignment:
full configs are exercised only via the dry-run.
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.models.common import ModelConfig

_ARCH_MODULES = {
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "qwen2.5-14b": "qwen2_5_14b",
    "qwen3-14b": "qwen3_14b",
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "hubert-xlarge": "hubert_xlarge",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "hymba-1.5b": "hymba_1_5b",
    "internvl2-26b": "internvl2_26b",
    "mamba2-370m": "mamba2_370m",
    # paper-benchmark models (simulator baselines, not dry-run archs)
    "llama3-8b": "llama3_8b",
    "llama3-70b": "llama3_70b",
    "llama3-405b": "llama3_405b",
    "llama4-scout-109b-a17b": "llama4_scout",
}

ASSIGNED_ARCHS = list(_ARCH_MODULES)[:10]
PAPER_ARCHS = list(_ARCH_MODULES)[10:]

# one device's share of an expert-parallel deployment of a listed model:
# id -> (module, attribute)
_SHARES = {
    "deepseek-v2-lite-16b-ep8": ("deepseek_v2_lite_16b", "EP8"),
}


def list_configs() -> list[str]:
    return list(_ARCH_MODULES)


def get_config(name: str) -> ModelConfig:
    if name in _SHARES:
        mod, attr = _SHARES[name]
        return getattr(importlib.import_module(f"repro.configs.{mod}"), attr)
    key = name if name in _ARCH_MODULES else None
    if key is None:
        for k, mod in _ARCH_MODULES.items():
            if mod == name.replace("-", "_").replace(".", "_"):
                key = k
                break
    if key is None:
        raise KeyError(f"unknown architecture {name!r}; know {list(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[key]}")
    return mod.CONFIG


def reduced_config(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests."""
    kw: dict = dict(
        name=cfg.name + "-reduced",
        n_layers=2 if cfg.moe_layer_period <= 1 else 2 * cfg.moe_layer_period,
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        vocab_pad_multiple=1,
    )
    if cfg.mla:
        kw.update(kv_lora_rank=32, rope_head_dim=8, head_dim=16)
    if cfg.moe:
        kw.update(n_experts=4, n_experts_per_token=min(2, cfg.n_experts_per_token),
                  moe_d_ff=64,
                  first_dense_layers=min(cfg.first_dense_layers, 1))
    if cfg.ssm or cfg.family in ("ssm", "hybrid"):
        kw.update(ssm_state=8, ssm_head_dim=8, ssm_heads=0, ssm_chunk=16)
    if cfg.sliding_window is not None:
        kw.update(sliding_window=8)
    return dataclasses.replace(cfg, **kw)
