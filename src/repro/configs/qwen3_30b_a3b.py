"""Qwen3-30B-A3B [moe] — 48L d_model=2048 32H (GQA kv=4, head_dim 128),
128 experts top-8 (renormalised) of width 768 in every layer, no shared
expert, vocab=151936, untied head. The sparse-expert policy of GSPO's RL
runs (arXiv:2507.18071). [hf:Qwen/Qwen3-30B-A3B]"""
from repro.config import ATTN, MOE, ModelConfig

CONFIG = ModelConfig(
    name="qwen3-30b-a3b",
    family="moe",
    num_layers=48,
    d_model=2048,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=768,                       # moe_intermediate_size
    vocab_size=151936,
    block_pattern=(ATTN,),
    ffn_pattern=(MOE,),
    num_experts=128,
    experts_per_token=8,
    rope_theta=1_000_000.0,
)
