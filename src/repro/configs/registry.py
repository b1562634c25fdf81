"""Architecture registry: ``--arch <id>`` -> ArchConfig, and ``--quant
<name>`` -> a QuantConfig or QuantPolicy."""
from __future__ import annotations

import importlib

from repro.models.config import ArchConfig

_MODULES = {
    "zamba2-2.7b": "zamba2_2p7b",
    "qwen1.5-0.5b": "qwen1p5_0p5b",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "smollm-135m": "smollm_135m",
    "mistral-large-123b": "mistral_large_123b",
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "mixtral-8x7b": "mixtral_8x7b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2p7b",
    "mamba2-370m": "mamba2_370m",
    "whisper-large-v3": "whisper_large_v3",
    "mellum2-12b-a2.5b": "mellum2_12b_a2p5b",
}

ARCH_IDS = tuple(_MODULES)

#: archs whose params+optimizer exceed ~8 GB/device without FSDP
FSDP_ARCHS = frozenset({
    "mistral-nemo-12b", "mistral-large-123b", "llava-next-mistral-7b",
    "mixtral-8x7b", "qwen2-moe-a2.7b", "zamba2-2.7b", "whisper-large-v3",
    "mellum2-12b-a2.5b",
})


def get_config(arch: str) -> ArchConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; have {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG


def use_fsdp(arch: str) -> bool:
    return arch in FSDP_ARCHS


#: every quantization preset a ``--quant`` flag accepts: the paper's uniform
#: QuantConfig grid plus the mixed-precision QuantPolicy presets.
def quant_ids():
    from repro.core import qpolicy
    return qpolicy.ALL_PRESETS


def get_quant(name: str):
    """``--quant <name>`` -> QuantConfig (uniform presets) or QuantPolicy
    (path-scoped presets like ``int8_embed16``); every launcher and model
    entry point accepts either."""
    from repro.core import qpolicy
    return qpolicy.get(name)

