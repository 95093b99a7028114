"""Architecture registry: ``--arch <id>`` -> (ModelConfig, Model)."""
from __future__ import annotations

import importlib

from ..core.policy import PrecisionPolicy, get_policy
from .transformer import Model

ARCHS = (
    "internvl2_26b", "deepseek_v2_lite_16b", "qwen3_moe_30b_a3b",
    "whisper_small", "xlstm_1_3b", "granite_20b", "gemma2_9b",
    "minicpm3_4b", "gemma3_12b", "zamba2_1_2b", "internlm2_20b",
)

# external ids (assignment spelling) -> module names
ALIASES = {
    "internvl2-26b": "internvl2_26b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "whisper-small": "whisper_small",
    "xlstm-1.3b": "xlstm_1_3b",
    "granite-20b": "granite_20b",
    "gemma2-9b": "gemma2_9b",
    "minicpm3-4b": "minicpm3_4b",
    "gemma3-12b": "gemma3_12b",
    "zamba2-1.2b": "zamba2_1_2b",
    "fpnew-case-study": "fpnew_case_study",
    "internlm2-20b": "internlm2_20b",
}


def canonical(arch: str) -> str:
    return ALIASES.get(arch, arch.replace("-", "_").replace(".", "_"))


def get_config(arch: str, reduced: bool = False):
    mod = importlib.import_module(f"repro.configs.{canonical(arch)}")
    cfg = mod.CONFIG
    if reduced:
        cfg = mod.reduced()
    return cfg.validate()


def build_model(arch: str, policy="tp_bf16", reduced: bool = False) -> Model:
    cfg = get_config(arch, reduced=reduced)
    return Model(cfg=cfg, policy=get_policy(policy))
