"""Architecture configuration schema for the model zoo.

One `ArchConfig` describes any of the 10 assigned architectures (plus the
paper's own ViT/BERT encoders).  The flags are the union of the features the
zoo needs: GQA, qk-norm, QKV bias, sliding-window patterns, MoE (incl. dense
residual), Mamba/attention hybrids, xLSTM blocks, encoder-decoder and
prefix-LM (VLM) wiring.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    # Snowflake Arctic: dense FFN residual in parallel with the MoE FFN.
    dense_residual: bool = False
    # Granite 4.0-H: a shared SwiGLU expert of this width beside the routed
    # ones, computed for every token (0: none).
    shared_d_ff: int = 0
    # Expert parallelism: this chip holds experts first_expert ..
    # first_expert + experts_held - 1 of the router's num_experts (None: all)
    # and computes only their part of the layer; the router still scores
    # every expert and keeps its top_k.
    first_expert: int = 0
    experts_held: Optional[int] = None
    # No routed (token, expert) pair is ever dropped: every held expert has
    # room for every token (capacity_factor is then unused).
    drop_free: bool = False

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    def __post_init__(self):
        if not 0 <= self.first_expert <= self.first_expert + self.held <= self.num_experts:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.held - 1} "
                f"are not among the router's {self.num_experts}")


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None  # default ceil(d_model / 16)
    # Mamba-2 (SSD) when n_heads > 0: d_inner = expand * d_model splits into
    # n_heads heads of head_dim, each with one scalar decay; B and C are
    # shared by the heads of each of n_groups groups; prefill runs the
    # chunked (SSD) form in chunks of chunk_size tokens.
    n_heads: int = 0
    head_dim: int = 64
    n_groups: int = 1
    chunk_size: int = 256

    def resolved_dt_rank(self, d_model: int) -> int:
        return self.dt_rank or -(-d_model // 16)

    @property
    def mamba2(self) -> bool:
        return self.n_heads > 0

    def conv_dim(self, d_model: int) -> int:
        """Channels of the causal conv: x alone (Mamba-1), or x, B and C
        (Mamba-2)."""
        di = self.expand * d_model
        return di + 2 * self.n_groups * self.d_state if self.mamba2 else di


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                   # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None          # default d_model // n_heads

    # attention flavor
    qk_norm: bool = False                   # qwen3
    qkv_bias: bool = False                  # qwen2.5
    rope_theta: float = 10_000.0
    local_window: Optional[int] = None      # sliding-window size
    local_ratio: int = 0                    # gemma3: N local layers per global
    logit_softcap: Optional[float] = None

    # ffn flavor
    mlp_variant: str = "swiglu"             # swiglu | gelu (whisper/encoders)

    # mixture of experts; MoE replaces the dense FFN on every `moe_every`-th
    # layer (Jamba: 2 -> alternate layers; DBRX/Arctic: 1 -> all layers).
    moe: Optional[MoEConfig] = None
    moe_every: int = 1

    # hybrid (jamba): one attention layer per `attn_every` layers, rest Mamba;
    # attention sits at offset `attn_offset` of each period (None: last, as in
    # Jamba; granite 4.0-H: 5 of every 10)
    attn_every: int = 0
    attn_offset: Optional[int] = None
    mamba: Optional[MambaConfig] = None

    # ssm (xlstm): mLSTM blocks with one sLSTM per `slstm_every`
    slstm_every: int = 0

    # encoder-decoder (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 0                    # frontend-stub sequence length

    # vlm prefix (paligemma)
    prefix_len: int = 0                     # image-patch prefix (stub embeds)

    # norms
    norm: str = "rms"                       # rms | ln (whisper/encoders)
    norm_eps: float = 1e-6
    post_block_norm: bool = False           # gemma-style post norms
    tie_embeddings: bool = False

    # multipliers (granite): x = embed[tok] * embedding_multiplier; every
    # residual add is x + residual_multiplier * sublayer(x); the softmax scale
    # is attention_multiplier (None: head_dim ** -0.5); logits are divided by
    # logits_scaling.  `nope` drops the rotary embedding (no positions).
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    nope: bool = False

    # numerics
    dtype: str = "bfloat16"
    remat: bool = True                      # activation checkpointing per group

    # layer grouping for scan-over-layers (compile-time compression)
    group_size: int = 1

    def __post_init__(self):
        if self.n_heads % max(1, self.n_kv_heads):
            raise ValueError("n_heads must be divisible by n_kv_heads")
        if self.family == "hybrid" and not (self.attn_every and self.mamba):
            raise ValueError("hybrid needs attn_every and mamba config")
        if self.attn_offset is not None and not 0 <= self.attn_offset < self.attn_every:
            raise ValueError("attn_offset must lie in [0, attn_every)")
        if self.local_ratio and not self.local_window:
            raise ValueError("local_ratio needs local_window")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def jax_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    @property
    def n_groups(self) -> int:
        if self.n_layers % self.group_size:
            raise ValueError(
                f"{self.name}: n_layers {self.n_layers} not divisible by "
                f"group_size {self.group_size}"
            )
        return self.n_layers // self.group_size

    def layer_kinds(self) -> Tuple[str, ...]:
        """Sub-layer kinds inside one scanned group, in execution order.

        'attn' | 'attn_local' | 'mamba' | 'mlstm' | 'slstm' — each is
        followed by its FFN (if d_ff > 0).
        """
        kinds = []
        for i in range(self.group_size):
            if self.family in ("ssm",):
                # xLSTM: one sLSTM per slstm_every, rest mLSTM.
                if self.slstm_every and (i + 1) % self.slstm_every == 0:
                    kinds.append("slstm")
                else:
                    kinds.append("mlstm")
            elif self.family == "hybrid":
                # Attention once per attn_every (at attn_offset), rest Mamba.
                off = (self.attn_every - 1 if self.attn_offset is None
                       else self.attn_offset)
                kinds.append("attn" if i % self.attn_every == off else "mamba")
            elif self.local_ratio:
                # Gemma3: local_ratio local layers then one global.
                kinds.append(
                    "attn" if (i + 1) % (self.local_ratio + 1) == 0 else "attn_local"
                )
            else:
                kinds.append("attn")
        return tuple(kinds)

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks), for roofline."""
        d, v = self.d_model, self.vocab
        hd = self.resolved_head_dim
        n = v * d * (1 if self.tie_embeddings else 2)
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        ffn = 3 * d * self.d_ff if self.mlp_variant == "swiglu" else 2 * d * self.d_ff
        moe = 0
        if self.moe:
            moe = (
                d * self.moe.num_experts
                + self.moe.held * 3 * d * self.moe.d_ff_expert
                + 3 * d * self.moe.shared_d_ff
            )
            if self.moe.dense_residual:
                moe += ffn

        def ffn_params(layer_idx: int) -> int:
            if self.moe and (layer_idx + 1) % self.moe_every == 0:
                return moe
            return ffn if self.d_ff else 0

        mixer = {}
        mixer["attn"] = mixer["attn_local"] = attn
        if self.mamba and self.mamba.mamba2:
            mc = self.mamba
            di, H = mc.expand * d, mc.n_heads
            cd = mc.conv_dim(d)
            mixer["mamba"] = (
                d * (di + cd + H) + mc.d_conv * cd + cd + 3 * H + di + di * d)
        elif self.mamba:
            di = self.mamba.expand * d
            dtr = self.mamba.resolved_dt_rank(d)
            mixer["mamba"] = (
                d * 2 * di + self.mamba.d_conv * di
                + di * (dtr + 2 * self.mamba.d_state) + dtr * di
                + di * self.mamba.d_state + di + di * d
            )
        if self.family == "ssm":
            # xLSTM blocks: in/out projections + gates, no separate FFN.
            di = 2 * d
            mixer["mlstm"] = d * 2 * di + 4 * di * hd + di * d + 3 * di
            mixer["slstm"] = 4 * d * d + int(8 / 3 * d * d) * 2
        kinds = self.layer_kinds()
        per_group = sum(
            mixer[k] + (ffn_params(i) if k not in ("mlstm", "slstm") else 0)
            for i, k in enumerate(kinds)
        )
        n += self.n_groups * per_group
        if self.encoder_layers:
            n += self.encoder_layers * (attn + ffn + attn)  # enc + cross-attn
        return n

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only top_k experts)."""
        if not self.moe:
            return self.param_count()
        total = self.param_count()
        expert_p = self.moe.held * 3 * self.d_model * self.moe.d_ff_expert
        active_p = (min(self.moe.top_k, self.moe.held)
                    * 3 * self.d_model * self.moe.d_ff_expert)
        n_moe_layers = self.n_layers // self.moe_every
        return total - n_moe_layers * (expert_p - active_p)
