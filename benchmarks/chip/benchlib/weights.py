"""Seeded weights of a dense GQA decoder, made by the benchmark itself.

Every tensor is a pure function of (seed, layer, tensor name), so the
program's copy (one jitted call on the device, in the served dtype) and the
reference's copy (made again layer by layer after the window) are the same
numbers without either reading the other's arrays.

Tensors per layer, with the shapes of the published checkpoints (x @ w):

  attn_norm (d,)   wq (d, Hq*D)   wk, wv (d, Hkv*D)   wo (Hq*D, d)
  q_norm, k_norm (D,)            [qk-norm models only]
  mlp_norm (d,)    w_gate, w_up (d, F)   w_down (F, d)

plus embed (V, d), final_norm (d,) and an untied head (d, V).  Norm weights
are 1 + 0.1 N(0, 1), so a path that drops a norm weight is caught;
projections are N(0, 1/fan_in); the embedding is N(0, 0.02^2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LAYER_TENSORS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                 "mlp_norm", "w_gate", "w_up", "w_down")
GLOBAL_TENSORS = ("embed", "final_norm", "head")


def base_key(seed: int):
    """A PRNG key for any non-negative seed, 64-bit seeds included."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    lo, hi = seed & 0x7FFFFFFF, (seed >> 31) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def layer_shapes(m) -> dict:
    """name -> shape of one layer's tensors for model dims `m`
    (a benchlib.cells.ModelDims)."""
    d, D, F = m.d_model, m.head_dim, m.d_ff
    shapes = {
        "attn_norm": (d,), "wq": (d, m.n_heads * D),
        "wk": (d, m.n_kv_heads * D), "wv": (d, m.n_kv_heads * D),
        "wo": (m.n_heads * D, d), "mlp_norm": (d,),
        "w_gate": (d, F), "w_up": (d, F), "w_down": (F, d),
    }
    if m.qk_norm:
        shapes["q_norm"] = (D,)
        shapes["k_norm"] = (D,)
    return shapes


def _make(key, name: str, shape, dtype):
    if name.endswith("norm"):
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:
        w = jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5
    return w.astype(dtype)


def layer_tensor(key, layer: int, name: str, shape, dtype=jnp.bfloat16):
    k = jax.random.fold_in(jax.random.fold_in(key, 1000 + layer),
                           LAYER_TENSORS.index(name))
    return _make(k, name, shape, dtype)


def global_tensor(key, name: str, shape, dtype=jnp.bfloat16):
    return _make(jax.random.fold_in(key, GLOBAL_TENSORS.index(name)),
                 name, shape, dtype)


def global_shapes(m) -> dict:
    return {"embed": (m.vocab, m.d_model), "final_norm": (m.d_model,),
            "head": (m.d_model, m.vocab)}
