"""Seeded weights, made by the benchmark itself, for every architecture.

Every tensor is a pure function of (seed, layer, tensor name), so the
program's copy (one jitted call on the device, in the served dtype) and the
reference's copy (made again layer by layer after the window) are the same
numbers without either reading the other's arrays.  A tensor's key folds
into the seed's key 1000 + its layer and then its name's position in the
architecture module's LAYER_TENSORS (GLOBAL_TENSORS for a tensor of no
layer, folded in directly).

Norm weights are 1 + 0.1 N(0, 1), so a path that drops a norm weight is
caught; the embedding is N(0, 0.02^2); every other tensor is a projection,
N(0, 1/fan_in) with fan_in its first dimension, unless the module's
`init(name)` gives a draw of its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A PRNG key for any non-negative seed, 64-bit seeds included."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    lo, hi = seed & 0x7FFFFFFF, (seed >> 31) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _make(key, name: str, shape, dtype, init=None):
    draw = init(name) if init is not None else None
    if draw is not None:
        w = draw(key, shape)
    elif name.endswith("norm"):
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:
        w = jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5
    return w.astype(dtype)


def layer_tensor(key, names, layer: int, name: str, shape,
                 dtype=jnp.bfloat16, init=None):
    """Tensor `name` of layer `layer`; `names` is the module's
    LAYER_TENSORS and `init` its optional init."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1000 + layer),
                           names.index(name))
    return _make(k, name, shape, dtype, init)


def global_tensor(key, names, name: str, shape, dtype=jnp.bfloat16,
                  init=None):
    """Tensor `name` of no layer; `names` is the module's GLOBAL_TENSORS."""
    return _make(jax.random.fold_in(key, names.index(name)),
                 name, shape, dtype, init)
