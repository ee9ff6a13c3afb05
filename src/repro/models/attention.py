"""Attention: GQA/MQA/MHA with qk-norm, sliding windows, cross-attention,
paged-KV decode, and a memory-bounded blockwise (flash-style) prefill path.

The blockwise path scans over KV blocks with an online softmax so 32k-token
prefill never materializes the full (S, S) score matrix — required for the
``prefill_32k`` dry-run shapes to fit per-device HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.parallel.logical import shard

NEG_INF = -2.0e38


def init_attention(key, cfg, *, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = cfg.jax_dtype
    p = {
        "wq": layers._init_dense(ks[0], d, hq * hd, dt),
        "wk": layers._init_dense(ks[1], d, hkv * hd, dt),
        "wv": layers._init_dense(ks[2], d, hkv * hd, dt),
        "wo": layers._init_dense(ks[3], hq * hd, d, dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((hq * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = layers.init_rmsnorm(hd, dt)
        p["k_norm"] = layers.init_rmsnorm(hd, dt)
    return p


def _project_qkv(x, kv_src, p, cfg, positions, *, rope: bool = True):
    B, S, _ = x.shape
    hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = layers.dense(x, p["wq"], p.get("bq")).reshape(B, S, hq, hd)
    k = layers.dense(kv_src, p["wk"], p.get("bk")).reshape(B, kv_src.shape[1], hkv, hd)
    v = layers.dense(kv_src, p["wv"], p.get("bv")).reshape(B, kv_src.shape[1], hkv, hd)
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        kv_pos = positions if kv_src.shape[1] == S else jnp.arange(kv_src.shape[1])
        k = layers.apply_rope(k, kv_pos, cfg.rope_theta)
    # Head-TP plans shard "heads"/"kv_heads" on the model axis; seq-sharded
    # plans map "attn_seq" to it instead (and replicate heads/KV) — the same
    # annotations serve both (see ParallelPlan.attn_seq).
    q = shard(q, "batch", "attn_seq", "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    if groups == 1:
        return k
    B, S, H, D = k.shape
    return jnp.repeat(k, groups, axis=2)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    q_offset: jax.Array | int = 0,
    window: Optional[int] = None,
    prefix_len: int = 0,
    block_kv: int = 1024,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Rematerialized flash-style attention: the KV-block scan's residuals
    are never saved for backward (jax.checkpoint below) — without this, a
    4k-token training step keeps O(S^2 / block) probability tensors alive
    per layer and blows per-device HBM.  `scale` is the softmax scale
    (None: D ** -0.5).

    On TPU the fused Pallas kernel (kernels/flash_attention.py) takes over
    whenever its feature set suffices — it keeps scores/probabilities in
    VMEM, removing the dominant HBM-traffic term of the XLA path (see
    EXPERIMENTS.md §Perf)."""
    from repro.kernels import ops as _ops

    plain_offset = isinstance(q_offset, int) and q_offset == 0
    if (_ops._resolve(None) in ("pallas", "pipelined")
            and plain_offset and not prefix_len and softcap is None):
        from repro.kernels.flash_attention import flash_attention

        f = functools.partial(flash_attention, causal=causal, window=window,
                              scale=scale)
        return jax.checkpoint(lambda a, b, c: f(a, b, c))(q, k, v)

    f = functools.partial(
        _blockwise_attention,
        causal=causal, window=window, prefix_len=prefix_len,
        block_kv=block_kv, softcap=softcap, scale=scale,
    )
    return jax.checkpoint(f)(q, k, v, q_offset)


def _blockwise_attention(
    q, k, v, q_offset, *, causal, window, prefix_len, block_kv, softcap,
    scale=None,
) -> jax.Array:
    """Online-softmax attention scanning KV blocks.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D).  Masks supported:
      causal (with q_offset for caches), sliding window, bidirectional
      prefix (prefix-LM for the VLM arch).
    """
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    groups = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale

    block_kv = min(block_kv, Skv)
    n_blocks = -(-Skv // block_kv)
    pad = n_blocks * block_kv - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # (B, Hkv, G, Sq, D) — GQA groups kept explicit so KV is never repeated.
    # q/k/p stay in the model dtype (bf16 on TPU) as in fused flash kernels;
    # only the softmax statistics and the output accumulator are f32.
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(B, Sq, Hkv, groups, D)
    qf = qf.transpose(0, 2, 3, 1, 4)
    # KV stay in model dtype at (n_blocks, B, block, Hkv, D); each block is
    # upcast inside the scan body, so peak memory is one block, not the cache.
    kb_all = jnp.moveaxis(k.reshape(B, n_blocks, block_kv, Hkv, D), 1, 0)
    vb_all = jnp.moveaxis(v.reshape(B, n_blocks, block_kv, Hkv, D), 1, 0)

    q_pos = jnp.arange(Sq) + q_offset  # (Sq,)

    def body(carry, blk):
        m, l, acc = carry
        kb, vb, b_idx = blk
        s = jnp.einsum(
            "bhgqd,bkhd->bhgqk", qf, kb.astype(qf.dtype),
            preferred_element_type=jnp.float32,
        )  # (B, Hkv, G, Sq, block) f32 scores
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kpos = b_idx * block_kv + jnp.arange(block_kv)  # (block,)
        mask = jnp.ones((Sq, block_kv), bool)
        if causal:
            cm = q_pos[:, None] >= kpos[None, :]
            if prefix_len:
                cm = cm | (kpos[None, :] < prefix_len)
            mask &= cm
        if window is not None:
            mask &= (q_pos[:, None] - kpos[None, :]) < window
        mask &= (kpos < Skv)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        # p in model dtype for the PV matmul (flash-kernel convention).
        pv = jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, groups, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, groups, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, groups, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kb_all, vb_all, jnp.arange(n_blocks))
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    index: jax.Array,
    window: Optional[int] = None,
    prefix_len: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Query-over-whole-cache attention, no KV-block scan.

    `index` is the position of the first query token — a scalar (all slots
    at the same position, classic lock-step decode) or a (B,) vector (paged
    serving: every slot at its own length).  Sq may be > 1 (chunked prefill:
    query t sits at position index + t and attends causally up to itself).
    The paged ``gather`` backend (kernels/flash_decode.py) and the kernel
    tests' reference run it over slot views of the pool.
    """
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    groups = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(B, Sq, Hkv, groups, D)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qf, k, preferred_element_type=jnp.float32
    )  # (B, Hkv, G, Sq, Skv)
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    qpos = idx[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None, :]  # (B, Sq)
    kpos = jnp.arange(Skv)
    mask = kpos[None, None, :] <= qpos[..., None]  # (B, Sq, Skv) — past only
    if window is not None:
        mask &= (qpos[..., None] - kpos[None, None, :]) < window
    if prefix_len:
        mask |= (kpos < prefix_len)[None, None, :]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p_attn = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p_attn, v, preferred_element_type=jnp.float32
    )
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def attention(
    x: jax.Array,
    p,
    cfg,
    *,
    positions: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
    kv_src: Optional[jax.Array] = None,
    cache=None,
    cache_index: Optional[jax.Array] = None,
    block_tables: Optional[jax.Array] = None,
    block_base=0,
):
    """Full attention sublayer.  Returns (out, new_cache).

    Prefill / training: cache is None -> blockwise attention over x itself
    (or kv_src for cross-attention).  Paged decode/prefill: cache is a
    PagedKVCache pool, block_tables (B, max_blocks) addresses it, and
    cache_index is the (B,) per-slot first-token position (x may carry S > 1
    chunk tokens); block_base is the pool row of this layer's block 0 when
    the pool merges several layers' pools (serving/kv_cache.py ``write_kv``).
    """
    cross = kv_src is not None
    src = kv_src if cross else x
    q, k, v = _project_qkv(x, src, p, cfg, positions,
                           rope=not (cross or cfg.nope))
    scale = cfg.attention_multiplier       # None: head_dim ** -0.5

    if cache is not None:
        # Paged decode: write this step's k/v through the block table, then
        # attend over the pool directly (block-table walk) — the
        # gather/blocked/flash backend choice lives in
        # kernels/flash_decode.py and binds at trace time.
        from repro.kernels import flash_decode as _fd
        from repro.serving import kv_cache as paged

        assert block_tables is not None, "paged cache needs block_tables"
        new_cache = paged.write_kv(cache, block_tables, k, v, cache_index,
                                   block_base=block_base)
        out = _fd.paged_decode_attention(
            q, new_cache, block_tables, cache_index,
            window=window, prefix_len=prefix_len, scale=scale,
            block_base=block_base,
        )
    else:
        new_cache = None
        out = blockwise_attention(
            q, k, v, causal=causal and not cross, window=window,
            prefix_len=prefix_len, softcap=cfg.logit_softcap, scale=scale,
        )

    B, Sq = x.shape[:2]
    out = shard(out, "batch", "attn_seq", "heads", None)
    out = out.reshape(B, Sq, cfg.n_heads * cfg.resolved_head_dim)
    out = layers.dense(out, p["wo"])
    return shard(out, "batch", "seq", "embed"), new_cache
