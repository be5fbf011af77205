"""Assigned-architecture registry: ``get_arch(name)`` / ``--arch <id>``."""
from repro.configs.base import ArchConfig

from repro.configs.qwen2_5_14b import CONFIG as qwen2_5_14b
from repro.configs.phi4_mini_3_8b import CONFIG as phi4_mini_3_8b
from repro.configs.llama3_405b import CONFIG as llama3_405b
from repro.configs.qwen2_5_32b import CONFIG as qwen2_5_32b
from repro.configs.qwen3_moe_30b_a3b import CONFIG as qwen3_moe_30b_a3b
from repro.configs.mixtral_8x7b import CONFIG as mixtral_8x7b
from repro.configs.internvl2_26b import CONFIG as internvl2_26b
from repro.configs.whisper_base import CONFIG as whisper_base
from repro.configs.mamba2_2_7b import CONFIG as mamba2_2_7b
from repro.configs.zamba2_1_2b import CONFIG as zamba2_1_2b

ARCHS: dict[str, ArchConfig] = {
    c.name: c for c in [
        qwen2_5_14b, phi4_mini_3_8b, llama3_405b, qwen2_5_32b,
        qwen3_moe_30b_a3b, mixtral_8x7b, internvl2_26b, whisper_base,
        mamba2_2_7b, zamba2_1_2b,
    ]
}


def get_arch(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


__all__ = ["ArchConfig", "ARCHS", "get_arch"]
