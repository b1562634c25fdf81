"""whisper-large-v3 — enc-dec, conv frontend stub [arXiv:2212.04356; unverified].

The batch carries precomputed frame embeddings (the mel+conv frontend is a
stub); encoder/decoder transformer stacks are real.
"""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="whisper-large-v3", family="audio",
    n_layers=32, d_model=1280, n_heads=20, n_kv_heads=20, d_ff=5120,
    vocab=51866, norm="layernorm", act="gelu",
    enc_dec=True, n_enc_layers=32, frontend="audio_stub",
)
