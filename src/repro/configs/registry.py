"""Architecture registry: ``--arch <id>`` -> ArchConfig + model entry points
+ dry-run ``input_specs``.

``input_specs(arch, shape)`` returns ShapeDtypeStruct stand-ins for every
model input of that (arch x shape) cell — weak-type-correct, shardable, no
device allocation — consumed by ``launch/dryrun.py``.
"""
from __future__ import annotations

import importlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.config import SHAPES, ArchConfig, shape_applicable

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


# ---------------------------------------------------------------------------
# input specs per (arch, shape)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ArchConfig, shape: str) -> Dict[str, Any]:
    """ShapeDtypeStructs for the entry point selected by ``shape``.

    train:   the batch pytree fed to ``train_step``
    prefill: prompt batch for ``prefill``
    decode:  one-token batch + cache for ``serve_step``
    """
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        raise ValueError(f"{cfg.name} x {shape}: {why}")
    S, B, kind = SHAPES[shape]

    if kind == "train":
        if cfg.enc_dec:
            return {
                "frames": _sds((B, S, cfg.d_model), jnp.float32),
                "tokens": _sds((B, S), jnp.int32),
                "labels": _sds((B, S), jnp.int32),
            }
        batch = {"tokens": _sds((B, S), jnp.int32),
                 "labels": _sds((B, S), jnp.int32)}
        if cfg.vlm_prefix:
            batch["tokens"] = _sds((B, S - cfg.vlm_prefix), jnp.int32)
            batch["labels"] = _sds((B, S - cfg.vlm_prefix), jnp.int32)
            batch["patch_embeds"] = _sds((B, cfg.vlm_prefix, cfg.d_model),
                                         jnp.float32)
        return batch

    if kind == "prefill":
        if cfg.enc_dec:
            return {"frames": _sds((B, S, cfg.d_model), jnp.float32),
                    "tokens": _sds((B, S), jnp.int32)}
        batch = {"tokens": _sds((B, S), jnp.int32)}
        if cfg.vlm_prefix:
            batch["tokens"] = _sds((B, S - cfg.vlm_prefix), jnp.int32)
            batch["patch_embeds"] = _sds((B, cfg.vlm_prefix, cfg.d_model),
                                         jnp.float32)
        return batch

    # decode: one new token against a seq_len-deep cache
    from repro.models import encdec, lm  # local import to avoid cycles
    spec = {"token": _sds((B, 1), jnp.int32)}
    if cfg.enc_dec:
        cache = jax.eval_shape(
            lambda: encdec.encdec_init_cache(cfg, B, S))
        spec["cache"] = cache
        KV, hd = cfg.n_kv_heads, cfg.head_dim
        spec["cross_kv"] = (
            _sds((cfg.n_layers, B, S, KV, hd), jnp.bfloat16),
            _sds((cfg.n_layers, B, S, KV, hd), jnp.bfloat16),
        )
    else:
        spec["cache"] = jax.eval_shape(lambda: lm.init_cache(cfg, B, S))
    return spec
