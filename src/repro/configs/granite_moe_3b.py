"""Granite-3.0-3B-A800M [hf:ibm-granite/granite-3.0-3b-a800m-instruct, config.json]:
MoE 40 experts top-8, GQA 24/8 heads, tied embeddings, RMSNorm eps 1e-6, and
Granite's four multipliers (embedding, attention, residual, logits).  Heads
are padded 24->32, kv heads 8->16 and experts 40->48 for 16-way TP/EP plans."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name='granite_moe_3b',
    family='moe',
    n_layers=32,
    d_model=1536,
    n_heads=24,
    n_kv_heads=8,
    d_ff=0,
    vocab=49155,
    head_dim=64,
    n_experts=40,
    n_experts_padded=48,
    top_k=8,
    d_ff_expert=512,
    mlp_act='swiglu',
    n_heads_padded=32,
    n_kv_heads_padded=16,
    vocab_padded=49168,
    tie_embeddings=True,
    norm_eps=1e-6,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=6.0,
)

SMOKE = ArchConfig(
    name='granite_moe_3b_smoke',
    family='moe',
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_ff=0,
    vocab=256,
    head_dim=16,
    n_experts=5,
    n_experts_padded=6,
    top_k=2,
    d_ff_expert=64,
    mlp_act='swiglu',
    tie_embeddings=True,
    norm_eps=1e-6,
    embedding_multiplier=12.0,
    attention_multiplier=0.0625,
    residual_multiplier=0.22,
    logits_scaling=6.0,
)
