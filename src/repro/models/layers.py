"""Elementary layers: norms, embeddings, RoPE, MLPs.

Functional style: `init_*` returns a params pytree, `apply` functions are
pure.  Every dense projection routes through repro.kernels.ops.linear, so the
OpenGeMM kernel (and its int8 deployment mode) underlies the whole zoo.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.parallel.logical import shard


def _init_dense(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
          *, quant: Optional[str] = None) -> jax.Array:
    y = ops.linear(x, w, quant=quant)
    if b is not None:
        y = y + b
    return y


# -- norms -------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> jax.Array:
    return jnp.ones((d,), dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layer_norm(x: jax.Array, p, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


# -- embeddings ---------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def embed(tokens: jax.Array, table: jax.Array,
          multiplier: float = 1.0) -> jax.Array:
    """table[tokens] * multiplier (Gemma's sqrt(d), granite's 12)."""
    x = jnp.take(table, tokens, axis=0)
    if multiplier != 1.0:
        x = x * jnp.asarray(multiplier, x.dtype)
    return shard(x, "batch", "seq", "embed")


def unembed(x: jax.Array, table: jax.Array) -> jax.Array:
    """Logits = x @ table^T (tied) — table is (vocab, d)."""
    logits = ops.linear(x, table.T.astype(x.dtype))
    return shard(logits, "batch", "seq", "vocab")


# -- rotary position embedding -------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10_000.0) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0) -> jax.Array:
    """x: (B, S, H, D), positions: (B, S) or (S,)."""
    freqs = rope_frequencies(x.shape[-1], theta)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- feed-forward ---------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, variant: str, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    if variant == "swiglu":
        return {
            "w_gate": _init_dense(k1, d, d_ff, dtype),
            "w_up": _init_dense(k2, d, d_ff, dtype),
            "w_down": _init_dense(k3, d_ff, d, dtype),
        }
    if variant == "gelu":
        return {
            "w_up": _init_dense(k1, d, d_ff, dtype),
            "b_up": jnp.zeros((d_ff,), dtype),
            "w_down": _init_dense(k2, d_ff, d, dtype),
            "b_down": jnp.zeros((d,), dtype),
        }
    raise ValueError(variant)


def mlp(x: jax.Array, p, variant: str, *, quant: Optional[str] = None) -> jax.Array:
    if variant == "swiglu":
        gate = dense(x, p["w_gate"], quant=quant)
        up = dense(x, p["w_up"], quant=quant)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        h = shard(h, "batch", "seq", "mlp")
        return dense(h, p["w_down"], quant=quant)
    h = dense(x, p["w_up"], p["b_up"], quant=quant)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = shard(h, "batch", "seq", "mlp")
    return dense(h, p["w_down"], p["b_down"], quant=quant)
