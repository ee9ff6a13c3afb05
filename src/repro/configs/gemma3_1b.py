"""Gemma3-1B [dense]: GQA kv=1 (MQA), 5:1 local:global sliding window,
tied embeddings, 262k vocab.  [hf:google/gemma-3-1b-pt; unverified]"""
import dataclasses
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="gemma3-1b", family="dense",
    n_layers=26, d_model=1152, n_heads=4, n_kv_heads=1,
    d_ff=6912, vocab=262144, head_dim=256,
    local_window=512, local_ratio=5, rope_theta=1e6,
    post_block_norm=True, tie_embeddings=True,
    embedding_multiplier=1152 ** 0.5,    # Gemma: embeddings scaled by sqrt(d)
    # 26 layers scanned as 2 groups of 13; the 5:1 local:global cadence is
    # approximated per group (globals at in-group positions 6 and 12 -> 4
    # global layers per 26, matching the 5:1 ratio; the exact phase shifts
    # by one at the group boundary).
    group_size=13,
)


def smoke_config() -> ArchConfig:
    return dataclasses.replace(
        CONFIG, n_layers=6, d_model=64, n_heads=4, n_kv_heads=1, head_dim=16,
        d_ff=128, vocab=256, embedding_multiplier=64 ** 0.5,
        local_window=8, group_size=6, dtype="float32",
    )
