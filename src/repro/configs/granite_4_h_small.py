"""Granite-4.0-H-Small (32B total, 9B active) — Mamba-2 + NoPE GQA hybrid
with a 72-expert top-10 MoE and a shared expert in every layer
[hf:ibm-granite/granite-4.0-h-small].

Period of 10 layers: nine Mamba-2 mixers and one attention layer at index
5 (layers 5, 15, 25, 35 of 40). Every layer then runs the routed MoE plus a
shared SwiGLU expert, both on the same normed input. Granite's scalars:
embeddings x12, each sub-layer's output x0.22 before the residual add,
logits /16, attention scale 1/128; the output head is the embedding table.
"""
from repro.configs.base import ArchConfig, SubLayer

CONFIG = ArchConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    d_ff=0,
    vocab_size=100352,
    period=(
        *(SubLayer("mamba", "moe"),) * 5,
        SubLayer("attn", "moe"),
        *(SubLayer("mamba", "moe"),) * 4,
    ),
    num_experts=72,
    top_k=10,
    moe_d_ff=768,
    num_shared_experts=1,
    shared_d_ff=1536,
    moe_shard="experts",
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_groups=1,
    ssm_conv_width=4,
    ssm_chunk=256,
    ssm_conv_bias=True,
    pos_encoding="none",
    long_context="native",
    attention_multiplier=0.0078125,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    tie_word_embeddings=True,
    norm_eps=1e-5,
    citation="hf:ibm-granite/granite-4.0-h-small",
)
