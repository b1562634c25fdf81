"""llava-next-mistral-7b — VLM, anyres tiling stub [hf:llava-hf/llava-v1.6-mistral-7b-hf; unverified].

The vision tower is a stub: the batch carries precomputed patch embeddings
(``batch["patch_embeds"]``; anyres tiling: base 576 patches + 4 tiles of
576 = 2880-token prefix); the mm projector + LM backbone are real.
"""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="llava-next-mistral-7b", family="vlm",
    n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=14336,
    vocab=32000, head_dim=128,
    frontend="vision_stub", vlm_prefix=2880,
)
