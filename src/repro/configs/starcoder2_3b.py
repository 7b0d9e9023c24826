"""starcoder2-3b [dense] — 30L d_model=3072 24H (GQA kv=2) d_ff=12288 vocab=49152.

The StarCoder2 block of ``starcoder2_7b`` at the 3B's published sizes
(arXiv:2402.19173; ``bigcode/starcoder2-3b`` config.json): head size 128,
RoPE theta 999999.44, window 4096, 16384 positions, LayerNorm eps 1e-5,
tied head (3.03 B parameters tied, 3.18 B untied). Trained by BigCode
separately from the 7B, so it is a growth source for it rather than a
reduction of it.
"""
from repro.configs.base import ATTN, ModelConfig

CONFIG = ModelConfig(
    name="starcoder2-3b",
    family="dense",
    n_layers=30,
    d_model=3072,
    n_heads=24,
    n_kv_heads=2,
    d_ff=12288,
    vocab_size=49152,
    block_pattern=(ATTN,),
    window=4096,
    rope="rope",
    rope_theta=999999.4420358813,
    act="gelu",
    norm="layer",
    norm_eps=1e-5,
    tie_embeddings=True,
    max_seq=16384,
)
