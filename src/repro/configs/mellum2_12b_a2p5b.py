"""mellum2-12b-a2.5b — JetBrains Mellum2-12B-A2.5B: GQA 32/4 at head_dim 128,
a period of three sliding-window layers (window 1024, RoPE theta 5e5) and
one full layer (YaRN x16 over 8192), 64 SiLU-gated experts of width 896,
top-8 renormalised, no shared expert, untied 98,304-id vocabulary
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

As published, every token reaches its top 8 experts, none dropped: the
config holds all 64 as one share (``moe_shard``), which routes drop-free
(``models/blocks.py::_expert_share_apply``).  The chip benchmark runs one
chip's share of an expert-parallel deployment, 8 of the 64, through
``dataclasses.replace`` (``benchmarks/chip/configs/mellum2-12b-a2.5b.json``).
"""
from repro.models.config import ArchConfig, RopeConfig

_THETA = 500000.0

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4, d_ff=896,
    vocab=98304, head_dim=128,
    rope_theta=_THETA, sliding_window=1024,
    max_position_embeddings=131072,
    layer_pattern=("sliding", "sliding", "sliding", "full"),
    rope_by_kind=(
        ("sliding", RopeConfig(theta=_THETA)),
        ("full", RopeConfig(theta=_THETA, kind="yarn", factor=16.0,
                            original_max_position=8192, beta_fast=32.0,
                            beta_slow=1.0,
                            attention_factor=1.2772588722239782)),
    ),
    moe_experts=64, moe_topk=8, moe_shard=(0, 64),
)
