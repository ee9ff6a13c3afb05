"""Granite-4.0-H-Small [hybrid]: 40 layers, 36 Mamba-2 (128 heads of 64,
d_state 128) and 4 NoPE GQA attention layers (32/8 heads of 128, at 5, 15,
25, 35); every layer's FFN is a MoE of 72 experts, top-10, width 768, with a
shared expert of width 1536; tied head; embedding, attention, residual and
logit multipliers.  [hf:ibm-granite/granite-4.0-h-small, granitemoehybrid]"""
import dataclasses
from repro.models.config import ArchConfig, MambaConfig, MoEConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8,
    d_ff=0,                         # every layer's FFN is the MoE below
    vocab=100352, head_dim=128,
    attn_every=10, attn_offset=5,   # M M M M M A M M M M, four times
    mamba=MambaConfig(d_state=128, d_conv=4, expand=2, n_heads=128,
                      head_dim=64, n_groups=1, chunk_size=256),
    moe=MoEConfig(num_experts=72, top_k=10, d_ff_expert=768,
                  shared_d_ff=1536, drop_free=True),
    norm_eps=1e-5, tie_embeddings=True,
    embedding_multiplier=12.0, attention_multiplier=0.0078125,
    residual_multiplier=0.22, logits_scaling=16.0, nope=True,
    group_size=10,
)


def smoke_config() -> ArchConfig:
    return dataclasses.replace(
        CONFIG, n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        vocab=256, attn_every=4, attn_offset=2, group_size=4, dtype="float32",
        mamba=MambaConfig(d_state=16, d_conv=4, expand=2, n_heads=8,
                          head_dim=16, n_groups=1, chunk_size=8),
        moe=MoEConfig(num_experts=8, top_k=3, d_ff_expert=32, shared_d_ff=64,
                      drop_free=True),
    )
