"""Mixture-of-Experts: top-k routing with capacity-bounded scatter dispatch.

Dispatch strategy (compile-friendly at 128 experts x 1M tokens):
  * router logits -> top_k -> softmax over the selected experts,
  * position-in-expert via a cumulative sum over the one-hot assignment,
  * tokens scattered into a (E, capacity, d) buffer (drops beyond capacity;
    `drop_free` gives every expert room for every token, so none drops),
  * expert FFNs run as one batched einsum over the expert dimension (sharded
    expert-parallel on the "model" mesh axis),
  * results gathered back and combined with routing weights.

Arctic's dense-residual variant runs a small dense FFN in parallel and sums;
Granite's shared expert (`shared_d_ff`) is the same, at its own width.

Under expert parallelism a chip holds only experts first_expert ..
first_expert + experts_held - 1: the router still scores all of them and
keeps its top_k, and the layer computes the part of the result that the
held experts give (pairs routed elsewhere add nothing here).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.parallel.logical import shard


def init_moe(key, cfg):
    mc = cfg.moe
    d, E, ffe = cfg.d_model, mc.num_experts, mc.d_ff_expert
    Eh = mc.held
    ks = jax.random.split(key, 5)
    dt = cfg.jax_dtype
    scale = d ** -0.5
    p = {
        "router": layers._init_dense(ks[0], d, E, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (Eh, d, ffe)) * scale).astype(dt),
        "w_up": (jax.random.normal(ks[2], (Eh, d, ffe)) * scale).astype(dt),
        "w_down": (jax.random.normal(ks[3], (Eh, ffe, d)) * ffe ** -0.5).astype(dt),
    }
    if mc.dense_residual:
        p["dense"] = layers.init_mlp(ks[4], d, cfg.d_ff, "swiglu", dt)
    if mc.shared_d_ff:
        p["shared"] = layers.init_mlp(jax.random.fold_in(key, 5), d,
                                      mc.shared_d_ff, "swiglu", dt)
    return p


def _capacity(tokens: int, cfg) -> int:
    mc = cfg.moe
    if mc.drop_free:
        c = tokens            # top_k picks distinct experts: <= 1 pair a token
    else:
        c = int(tokens * mc.top_k / mc.num_experts * mc.capacity_factor)
    return max(8, -(-c // 8) * 8)


def moe_block(x: jax.Array, p, cfg, *, quant: Optional[str] = None) -> jax.Array:
    B, S, d = x.shape
    mc = cfg.moe
    E, k = mc.num_experts, mc.top_k
    first, Eh = mc.first_expert, mc.held
    T = B * S
    C = _capacity(T, cfg)

    x2 = x.reshape(T, d)
    logits = layers.dense(x2.astype(jnp.float32), p["router"])       # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(logits, k)                 # (T, k)
    weights = jax.nn.softmax(gate_vals, axis=-1)                     # (T, k)

    # Flatten (token, slot) pairs; earlier tokens win capacity slots.  Pairs
    # routed to experts this chip does not hold take no slot.
    local = expert_idx.reshape(T * k) - first                        # (T*k,)
    held = (local >= 0) & (local < Eh)
    flat_e = jnp.where(held, local, 0)
    oh = jax.nn.one_hot(flat_e, Eh, dtype=jnp.int32) * held[:, None]  # (T*k, Eh)
    pos = jnp.cumsum(oh, axis=0) - oh                                # pre-count
    pos_in_e = jnp.sum(pos * oh, axis=-1)                            # (T*k,)
    keep = held & (pos_in_e < C)
    # Dropped pairs go to a sacrificial slot C (buffer has C+1 rows).
    slot = jnp.where(keep, pos_in_e, C)

    buf = jnp.zeros((Eh, C + 1, d), x.dtype)
    token_ids = jnp.repeat(jnp.arange(T), k)
    buf = buf.at[flat_e, slot].add(x2[token_ids])
    buf = shard(buf, "expert", None, None)[:, :C]                    # (E, C, d)

    # Expert FFNs (SwiGLU), batched over E.
    bf = buf.astype(jnp.float32)
    gate = jnp.einsum("ecd,edf->ecf", bf, p["w_gate"].astype(jnp.float32))
    up = jnp.einsum("ecd,edf->ecf", bf, p["w_up"].astype(jnp.float32))
    h = jax.nn.silu(gate) * up
    h = shard(h.astype(x.dtype), "expert", None, "mlp")
    out_buf = jnp.einsum("ecf,efd->ecd", h.astype(jnp.float32),
                         p["w_down"].astype(jnp.float32))            # (E, C, d)
    out_buf = shard(out_buf.astype(x.dtype), "expert", None, None)

    # Gather back and combine with routing weights (dropped -> zero).
    out_pairs = out_buf[flat_e, jnp.minimum(slot, C - 1)]            # (T*k, d)
    out_pairs = jnp.where(keep[:, None], out_pairs, 0)
    w_pairs = weights.reshape(T * k, 1).astype(out_pairs.dtype)
    y = jnp.zeros((T, d), out_pairs.dtype).at[token_ids].add(out_pairs * w_pairs)
    y = y.reshape(B, S, d).astype(x.dtype)

    if mc.dense_residual:
        y = y + layers.mlp(x, p["dense"], "swiglu", quant=quant)
    if mc.shared_d_ff:
        y = y + layers.mlp(x, p["shared"], "swiglu", quant=quant)
    return shard(y, "batch", "seq", "embed")


def aux_load_balance_loss(logits: jax.Array, expert_idx: jax.Array, E: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss (exposed for training)."""
    probs = jax.nn.softmax(logits, axis=-1)                          # (T, E)
    frac_tokens = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac_tokens * frac_probs)
