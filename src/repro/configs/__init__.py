"""Assigned-architecture registry.

Each module defines `CONFIG` (full production config, exact constants from
the assignment) and `smoke_config()` (reduced same-family config for CPU
tests).  `get(name)` / `list_archs()` are the public API; `--arch <id>` in
the launchers resolves through here.
"""

from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import ArchConfig

_ARCH_MODULES = {
    "whisper-medium": "repro.configs.whisper_medium",
    "qwen3-14b": "repro.configs.qwen3_14b",
    "mistral-nemo-12b": "repro.configs.mistral_nemo_12b",
    "qwen2.5-14b": "repro.configs.qwen2_5_14b",
    "gemma3-1b": "repro.configs.gemma3_1b",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "arctic-480b": "repro.configs.arctic_480b",
    "paligemma-3b": "repro.configs.paligemma_3b",
    "jamba-1.5-large-398b": "repro.configs.jamba_1_5_large",
    "granite-4.0-h-small": "repro.configs.granite_4_0_h_small",
    "xlstm-1.3b": "repro.configs.xlstm_1_3b",
    # The paper's own transformer benchmark backbones (Table 2):
    "bert-base": "repro.configs.bert_base",
    "vit-b-16": "repro.configs.vit_b_16",
}


def list_archs() -> List[str]:
    return list(_ARCH_MODULES)


def get(name: str) -> ArchConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {list_archs()}")
    return importlib.import_module(_ARCH_MODULES[name]).CONFIG


def get_smoke(name: str) -> ArchConfig:
    return importlib.import_module(_ARCH_MODULES[name]).smoke_config()


# Shape grid assigned to the LM-family architectures.
SHAPES: Dict[str, dict] = {
    "train_4k": dict(kind="train", seq_len=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq_len=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq_len=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq_len=524288, global_batch=1),
}

# long_500k requires sub-quadratic sequence mixing; only the SSM/hybrid archs
# run it (see DESIGN.md §Arch-applicability).
LONG_CONTEXT_ARCHS = {"jamba-1.5-large-398b", "xlstm-1.3b"}


def shapes_for(name: str) -> List[str]:
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if name in LONG_CONTEXT_ARCHS:
        out.append("long_500k")
    return out
