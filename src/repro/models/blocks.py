"""Transformer/hybrid blocks: one mixer (attention | mamba | mLSTM | sLSTM)
plus its FFN/MoE, with pre- (and optionally post-) norms.

Blocks are grouped into `cfg.group_size`-layer groups whose parameters are
stacked along a leading axis and executed under `jax.lax.scan` (model.py) —
compile time stays O(group) instead of O(layers), which is what makes the
35-72 layer production configs lowerable in minutes on the CPU dry-run.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import layers, moe as moe_lib, ssm


def _init_norm(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "ln":
        return layers.init_layernorm(d, cfg.jax_dtype)
    return layers.init_rmsnorm(d, cfg.jax_dtype)


def _norm(x, p, cfg):
    if cfg.norm == "ln":
        return layers.layer_norm(x, p, cfg.norm_eps)
    return layers.rms_norm(x, p, cfg.norm_eps)


# The named scope each mixer kind runs under (apply_block).
MIXER_SCOPE = {"attn": "attn", "attn_local": "attn", "mamba": "mamba",
               "mlstm": "mlstm", "slstm": "slstm"}


def _residual(x, h, cfg):
    """x + residual_multiplier * h (granite's 0.22; 1 elsewhere)."""
    r = cfg.residual_multiplier
    return x + (h if r == 1.0 else h * jnp.asarray(r, h.dtype))


def _layer_uses_moe(cfg, layer_idx: int) -> bool:
    return cfg.moe is not None and (layer_idx + 1) % cfg.moe_every == 0


def init_block(key, cfg, kind: str, *, layer_idx: int = 0,
               cross_attention: bool = False):
    ks = jax.random.split(key, 6)
    p: dict = {"norm1": _init_norm(cfg)}
    if kind in ("attn", "attn_local"):
        p["mixer"] = attn_lib.init_attention(ks[0], cfg)
    elif kind == "mamba":
        p["mixer"] = ssm.init_mamba(ks[0], cfg)
    elif kind == "mlstm":
        p["mixer"] = ssm.init_mlstm(ks[0], cfg)
    elif kind == "slstm":
        p["mixer"] = ssm.init_slstm(ks[0], cfg)
    else:
        raise ValueError(kind)
    if cross_attention:
        p["norm_cross"] = _init_norm(cfg)
        p["cross"] = attn_lib.init_attention(ks[1], cfg, cross=True)
    # xLSTM blocks carry their own FFN (d_ff == 0); others get MLP or MoE.
    if kind in ("attn", "attn_local", "mamba") and (cfg.d_ff or cfg.moe):
        p["norm2"] = _init_norm(cfg)
        if _layer_uses_moe(cfg, layer_idx):
            p["ffn"] = moe_lib.init_moe(ks[2], cfg)
        else:
            p["ffn"] = layers.init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_variant, cfg.jax_dtype)
    if cfg.post_block_norm:
        p["post_norm1"] = _init_norm(cfg)
        if "ffn" in p:
            p["post_norm2"] = _init_norm(cfg)
    return p


def apply_block(
    x: jax.Array,
    p,
    cfg,
    kind: str,
    *,
    positions: jax.Array,
    causal: bool = True,
    prefix_len: int = 0,
    cache: Optional[Any] = None,
    cache_index: Optional[jax.Array] = None,
    encoder_out: Optional[jax.Array] = None,
    block_tables: Optional[jax.Array] = None,
    block_base=0,
    collect_states: bool = False,
) -> Tuple[jax.Array, Any]:
    """Returns (x, new_mixer_cache).  cache is the mixer state (paged KV
    pool / recurrent state).

    ``collect_states`` asks recurrent mixers for per-position states (an
    extra (S,) axis on every state leaf) instead of the final state —
    speculative verification selects the state at the accepted position.
    Attention kinds ignore it (the paged KV pool is positional already).

    Each sub-layer, norms and residual add included, runs under a
    ``jax.named_scope`` of its layer kind (MIXER_SCOPE, then "ffn" or
    "moe"): the op_name of every operation in the compiled step says which
    kind of layer it belongs to, so a profile splits the step by kind.
    """
    if kind not in MIXER_SCOPE:
        raise ValueError(kind)
    with jax.named_scope(MIXER_SCOPE[kind]):
        h = _norm(x, p["norm1"], cfg)
        if kind in ("attn", "attn_local"):
            window = cfg.local_window if kind == "attn_local" else None
            h, new_cache = attn_lib.attention(
                h, p["mixer"], cfg, positions=positions, causal=causal,
                window=window, prefix_len=prefix_len, cache=cache,
                cache_index=cache_index, block_tables=block_tables,
                block_base=block_base,
            )
        elif kind == "mamba":
            h, new_cache = ssm.mamba_block(h, p["mixer"], cfg, state=cache,
                                           collect_states=collect_states)
        elif kind == "mlstm":
            h, new_cache = ssm.mlstm_block(h, p["mixer"], cfg, state=cache,
                                           collect_states=collect_states)
        else:
            h, new_cache = ssm.slstm_block(h, p["mixer"], cfg, state=cache,
                                           collect_states=collect_states)
        if cfg.post_block_norm:
            h = _norm(h, p["post_norm1"], cfg)
        x = _residual(x, h, cfg)

    if "cross" in p:
        with jax.named_scope("attn"):
            h = _norm(x, p["norm_cross"], cfg)
            h, _ = attn_lib.attention(
                h, p["cross"], cfg, positions=positions, causal=False,
                kv_src=encoder_out,
            )
            x = _residual(x, h, cfg)

    if "ffn" in p:
        moe = "router" in p["ffn"]
        with jax.named_scope("moe" if moe else "ffn"):
            h = _norm(x, p["norm2"], cfg)
            if moe:
                h = moe_lib.moe_block(h, p["ffn"], cfg)
            else:
                h = layers.mlp(h, p["ffn"], cfg.mlp_variant)
            if cfg.post_block_norm:
                h = _norm(h, p["post_norm2"], cfg)
            x = _residual(x, h, cfg)
    return x, new_cache


def init_group(key, cfg, *, cross_attention: bool = False):
    """Parameters for one scanned group: dict sub0..sub{G-1}."""
    kinds = cfg.layer_kinds()
    ks = jax.random.split(key, len(kinds))
    return {
        f"sub{i}": init_block(
            ks[i], cfg, kind, layer_idx=i, cross_attention=cross_attention
        )
        for i, kind in enumerate(kinds)
    }


def apply_group(
    x, gp, cfg, *, positions, causal=True, prefix_len=0,
    caches=None, cache_index=None, encoder_out=None,
    block_tables=None, block_base=0, collect_states=False,
):
    """Apply one group of cfg.group_size blocks; returns (x, new_caches).
    ``block_base`` is the row of this group's block 0 in pools that merge
    every group's pool (model.py ``_trunk_step``)."""
    kinds = cfg.layer_kinds()
    new_caches = []
    for i, kind in enumerate(kinds):
        x, nc = apply_block(
            x, gp[f"sub{i}"], cfg, kind,
            positions=positions, causal=causal, prefix_len=prefix_len,
            cache=None if caches is None else caches[i],
            cache_index=cache_index,
            encoder_out=encoder_out,
            block_tables=block_tables, block_base=block_base,
            collect_states=collect_states,
        )
        new_caches.append(nc)
    return x, tuple(new_caches)


def init_cache_for_kind(cfg, kind: str, batch: int):
    """Per-slot recurrent state of one block of a recurrent kind; attention
    kinds keep their KV in a paged pool (``init_paged_cache_for_kind``)."""
    if kind in ("attn", "attn_local"):
        raise ValueError(
            f"{kind!r} layers hold no per-slot state: their KV lives in a "
            f"paged pool, see init_paged_cache_for_kind")
    if kind == "mamba":
        return ssm.init_mamba_state(cfg, batch)
    if kind == "mlstm":
        return ssm.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return ssm.init_slstm_state(cfg, batch)
    raise ValueError(kind)


def init_paged_cache_for_kind(
    cfg, kind: str, batch: int, num_blocks: int, block_size: int,
    kv_precision: str = "float",
):
    """Paged-serving decode state: attention kinds get a shared block pool
    (no per-slot KV allocation — the point of paging); SSM kinds keep their
    O(1) per-slot state.  `kv_precision="int8"` makes the pool int8-resident
    with per-(block, position, head) scales (see serving/kv_cache.py)."""
    from repro.serving import kv_cache as paged

    if kind in ("attn", "attn_local"):
        return paged.init_paged_kv(
            num_blocks, block_size, cfg.n_kv_heads, cfg.resolved_head_dim,
            cfg.jax_dtype, kv_precision=kv_precision,
        )
    return init_cache_for_kind(cfg, kind, batch)
