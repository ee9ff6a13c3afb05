"""PaliGemma-3B [vlm]: SigLIP patch prefix (stub) + gemma decoder, MQA.
[arXiv:2407.07726; hf]"""
import dataclasses
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="paligemma-3b", family="vlm",
    n_layers=18, d_model=2048, n_heads=8, n_kv_heads=1,
    d_ff=16384, vocab=257216, head_dim=256,
    tie_embeddings=True,
    embedding_multiplier=2048 ** 0.5,    # Gemma: embeddings scaled by sqrt(d)
    prefix_len=256,                 # 16x16 SigLIP patches at 224px
    group_size=3,
)


def smoke_config() -> ArchConfig:
    return dataclasses.replace(
        CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=1, head_dim=16,
        d_ff=128, vocab=256, embedding_multiplier=64 ** 0.5,
        prefix_len=4, group_size=1, dtype="float32",
    )
