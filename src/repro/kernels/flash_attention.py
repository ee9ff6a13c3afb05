"""Fused flash attention (Pallas TPU): the framework's second hot-spot kernel.

The roofline hillclimb (EXPERIMENTS.md §Perf, qwen3 iterations 3-4) showed
that with attention expressed as XLA ops, the f32 score/probability tensors
dominate per-device HBM traffic (~69% of a training step).  This kernel keeps
the (block_q, block_kv) score tile, the online-softmax statistics and the
output accumulator in VMEM — HBM traffic reduces to the q/k/v/o tensors, the
same transformation the paper applies at SPM scale with its output buffer.

Supports causal masking, sliding windows and GQA (kv-head indexed per
q-head).  Validated in interpret mode against the dense oracle
(tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, kv_steps: int, block_q: int, block_kv: int, scale: float,
    causal: bool, window: Optional[int], seq_kv: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    s = jax.lax.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_kv
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                       # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v_ref.dtype).astype(jnp.float32), v,
        preferred_element_type=jnp.float32,
    )

    @pl.when(ik == kv_steps - 1)
    def _flush():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,                 # (B, Sq, Hq, D)
    k: jax.Array,                 # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
    scale: Optional[float] = None,          # softmax scale; None: D ** -0.5
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    groups = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale

    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    pad_q = (-Sq) % block_q
    pad_kv = (-Skv) % block_kv

    qt = jnp.moveaxis(q, 2, 1)                            # (B, Hq, Sq, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    nq = qt.shape[2] // block_q
    nk = kt.shape[2] // block_kv

    kernel = functools.partial(
        _flash_kernel, kv_steps=nk, block_q=block_q, block_kv=block_kv,
        scale=scale, causal=causal, window=window, seq_kv=Skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j, g=groups: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j, g=groups: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    if pad_q:
        out = out[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)                        # (B, Sq, Hq, D)
