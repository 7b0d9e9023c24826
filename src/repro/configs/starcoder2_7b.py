"""starcoder2-7b [dense] — 32L d_model=4608 36H (GQA kv=4) d_ff=18432 vocab=49152.

StarCoder2 (Lozhkov et al. 2024, arXiv:2402.19173; ``bigcode/starcoder2-7b``
config.json): pre-norm LayerNorm with bias (eps 1e-5), biased q/k/v/o and a
biased c_fc -> gelu(tanh) -> c_proj MLP, GQA with head size 128, RoPE
(theta 1e6), causal sliding-window attention over 4096 positions, 16384
positions in all. The LM head is tied to the token embedding: tied, the
tree counts 7.17 B parameters, the size BigCode gives (untied, 7.40 B).
"""
from repro.configs.base import ATTN, ModelConfig

CONFIG = ModelConfig(
    name="starcoder2-7b",
    family="dense",
    n_layers=32,
    d_model=4608,
    n_heads=36,
    n_kv_heads=4,
    d_ff=18432,
    vocab_size=49152,
    block_pattern=(ATTN,),
    window=4096,
    rope="rope",
    rope_theta=1000000.0,
    act="gelu",
    norm="layer",
    norm_eps=1e-5,
    tie_embeddings=True,
    max_seq=16384,
)
