"""Paged flash-decode (Pallas TPU): decode attention straight off the KV pool.

The serving decode path previously materialized every slot's cache view with
``gather_kv`` (a (B, max_blocks * block_size, H, D) gather — the *full* table
extent, mostly null blocks at short lengths) and ran a whole-cache einsum.
This kernel instead walks the per-slot block tables *inside* the grid — the
paper's programmable strided memory access (Sec 3.3) applied to decode: the
block table is the stride program, and each grid step DMAs exactly one pool
block.  HBM traffic per step drops from the table extent to the lived-in
blocks, and nothing is ever materialized per slot.

Shape story (one grid step = one pool block, all kv heads, for one
(slot, split)):

  q            (B, Sq, Hq, D)     -> packed (B, Hkv, G * Sq, D) rows
  k/v pool     (num_blocks, Hkv, block_size, D), addressed via the
               scalar-prefetched block table: block index
               ``tables[b, split * cols_per_split + j]``
  outputs      per-split partial (acc, m, l) — online-softmax state — reduced
               in a cheap second stage (split-K over the sequence dimension,
               so long contexts parallelize across the grid instead of
               serializing one slot's whole table on one core).

GQA is handled by packing the G query heads of a kv head (times the Sq query
positions — Sq > 1 for speculative verify and chunked prefill) into the row
axis of a single (rows, block_size) score tile, so KV is fetched once per
kv head, never repeated.  Per-slot length masking (``kpos <= index[b] + t``)
and sliding windows are applied in-kernel.

int8 KV residency: when the pool carries per-(block, position, kv-head)
scales (``PagedKVCache.k_scale``/``v_scale``, see serving/kv_cache.py), the
kernel fetches int8 K/V blocks and dequantizes them in registers inside the
inner loop — no dequantized copy of the cache ever exists, so the ~4x
byte saving is real end to end.

Also here:

  * ``ref_paged_decode`` — the bounded pure-JAX fallback: a
    ``lax.while_loop`` over block-table column chunks with an online-softmax
    carry, iterating only to the max active length across slots (not the
    table extent).  This is the default decode path on non-TPU hosts.
  * ``paged_decode_attention`` — the backend dispatcher used by
    models/attention.py, with ``set_decode_backend`` / ``decode_backend``
    mirroring kernels/ops.py's backend switch, and a trace-time
    ``set_decode_spec`` hook the serving engine binds tuned
    ``FlashDecodeSpec`` winners through (repro.tuning.decode).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.serving.kv_cache import NULL_BLOCK, gather_kv

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# design point
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlashDecodeSpec:
    """One decode-kernel design point (the analogue of TpuGemmSpec).

    num_splits     split-K factor over the block-table columns: each split
                   produces partial (acc, m, l) reduced in stage 2.  1 = no
                   split (short contexts); long tables want the sequence
                   walk spread across the grid.
    cols_per_iter  table columns the *fallback* path gathers per
                   ``while_loop`` iteration — its chunk/overshoot trade-off
                   (a bigger chunk amortizes iteration overhead but gathers
                   past the needed length by up to a chunk).
    """

    num_splits: int = 1
    cols_per_iter: int = 8

    def __post_init__(self):
        if self.num_splits < 1:
            raise ValueError(f"num_splits must be >= 1, got {self.num_splits}")
        if self.cols_per_iter < 1:
            raise ValueError(
                f"cols_per_iter must be >= 1, got {self.cols_per_iter}")

    def to_json(self) -> dict:
        return {"kind": "flash_decode", "num_splits": self.num_splits,
                "cols_per_iter": self.cols_per_iter}

    @classmethod
    def from_json(cls, d: dict) -> "FlashDecodeSpec":
        return cls(num_splits=int(d["num_splits"]),
                   cols_per_iter=int(d["cols_per_iter"]))


# ---------------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------------

def _decode_kernel(
    bt_ref, idx_ref,                       # scalar-prefetch: tables, index
    q_ref, k_ref, v_ref, *rest,
    cols_per_split: int, block_size: int, sq: int, scale: float,
    window: Optional[int], seq_cap: int, quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, acc_out, m_out, l_out, acc_ref, m_ref, l_ref = rest
    else:
        acc_out, m_out, l_out, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    s = pl.program_id(1)
    j = pl.program_id(2)
    n_heads, rows = q_ref.shape[1], q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Row r packs (group g, query offset t) = (r // sq, r % sq); padding rows
    # past G * Sq carry zero queries and are sliced off after the combine.
    # The mask is the same for every kv head of the block.
    col = s * cols_per_split + j
    t = jax.lax.broadcasted_iota(jnp.int32, (rows, block_size), 0) % sq
    qpos = idx_ref[b] + t
    kpos = col * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (rows, block_size), 1)
    mask = (kpos <= qpos) & (kpos < seq_cap)
    if window is not None:
        mask &= (qpos - kpos) < window

    for h in range(n_heads):                                # static unroll
        q = q_ref[0, h].astype(jnp.float32) * scale         # (rows, D)
        k = k_ref[0, h].astype(jnp.float32)                 # (block_size, D)
        v = v_ref[0, h].astype(jnp.float32)
        scores = jax.lax.dot(q, k.T, preferred_element_type=jnp.float32)
        if quantized:
            # int8 codes times per-position scales: the scale of key i
            # multiplies score column i (and value i weighs p column i), so
            # the (1, block_size) scale row broadcasts over the rows.
            scores = scores * ks_ref[0, h:h + 1, :]
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_ref[h]                                   # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[h] = m_new
        if quantized:
            p = p * vs_ref[0, h:h + 1, :]
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == cols_per_split - 1)
    def _flush():
        acc_out[0, 0] = acc_ref[...]
        for h in range(n_heads):
            m_out[0, 0, h] = m_ref[h][:, 0]
            l_out[0, 0, h] = l_ref[h][:, 0]


def _combine_splits(acc, m, l):
    """Stage 2 of split-K: merge per-split online-softmax partials.

    acc (B, S, H, rows, D); m, l (B, S, H, rows).  A fully-masked split
    carries (acc=0, m=NEG_INF, l=0): its alpha underflows to zero against any
    live split, and when *every* split is masked the l floor keeps the (all
    padding rows / inactive slot) output finite — garbage, but finite, and
    hidden by the caller exactly like the gather path's null-block rows.
    """
    m_g = jnp.max(m, axis=1)                               # (B, H, rows)
    alpha = jnp.exp(m - m_g[:, None])                      # (B, S, H, rows)
    l_g = jnp.sum(l * alpha, axis=1)
    acc_g = jnp.sum(acc * alpha[..., None], axis=1)
    return acc_g / jnp.maximum(l_g, 1e-30)[..., None]      # (B, H, rows, D)


def _pack_q(q, groups: int, Hkv: int):
    """(B, Sq, Hq, D) -> (B, Hkv, rows_padded, D) with rows = G * Sq padded
    to the f32 sublane multiple; row r = g * Sq + t."""
    B, Sq, Hq, D = q.shape
    rows = groups * Sq
    qr = q.reshape(B, Sq, Hkv, groups, D).transpose(0, 2, 3, 1, 4)
    qr = qr.reshape(B, Hkv, rows, D)
    rows_p = -(-rows // 8) * 8
    if rows_p != rows:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))
    return qr, rows, rows_p


def _unpack_out(out, B: int, Sq: int, Hq: int, D: int, groups: int, rows: int):
    """(B, Hkv, rows_padded, D) -> (B, Sq, Hq, D)."""
    Hkv = Hq // groups
    out = out[:, :, :rows].reshape(B, Hkv, groups, Sq, D)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)


def flash_decode_attention(
    q: jax.Array,                  # (B, Sq, Hq, D)
    cache,                         # PagedKVCache (float or int8 + scales)
    block_tables: jax.Array,       # (B, max_blocks) int32 into the pool
    index,                         # scalar or (B,): first query position
    *,
    window: Optional[int] = None,
    spec: Optional[FlashDecodeSpec] = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over the paged pool via the Pallas kernel.

    Every block the kernel touches keeps its last two dims equal to whole
    array dims, so the TPU's (8, 128) tiling rule holds for any kv-head
    count, block size and split factor: K/V come in as (1, Hkv, bs, D) pool
    blocks, scales as (1, Hkv, bs), and the per-split partials leave as
    (1, 1, Hkv, rows, D) and (1, 1, Hkv, rows).
    """
    spec = spec or FlashDecodeSpec()
    B, Sq, Hq, D = q.shape
    nb, Hkv, bs, _ = cache.k.shape
    groups = Hq // Hkv
    max_blocks = block_tables.shape[1]
    seq_cap = max_blocks * bs

    splits = max(1, min(spec.num_splits, max_blocks))
    cps = -(-max_blocks // splits)
    bt = block_tables.astype(jnp.int32)
    pad_cols = splits * cps - max_blocks
    if pad_cols:
        bt = jnp.pad(bt, ((0, 0), (0, pad_cols)),
                     constant_values=NULL_BLOCK)
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))

    qr, rows, rows_p = _pack_q(q, groups, Hkv)
    quantized = cache.k_scale is not None

    def qmap(b, s, j, bt, idx):
        return (b, 0, 0, 0)

    def kvmap(b, s, j, bt, idx, cps=cps):
        return (bt[b, s * cps + j], 0, 0, 0)

    def smap(b, s, j, bt, idx, cps=cps):
        return (bt[b, s * cps + j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hkv, rows_p, D), qmap),
        pl.BlockSpec((1, Hkv, bs, D), kvmap),
        pl.BlockSpec((1, Hkv, bs, D), kvmap),
    ]
    operands = [qr, cache.k, cache.v]
    if quantized:
        in_specs += [pl.BlockSpec((1, Hkv, bs), smap),
                     pl.BlockSpec((1, Hkv, bs), smap)]
        operands += [cache.k_scale, cache.v_scale]

    def out_map4(b, s, j, bt, idx):
        return (b, s, 0, 0)

    def out_map5(b, s, j, bt, idx):
        return (b, s, 0, 0, 0)

    kernel = functools.partial(
        _decode_kernel, cols_per_split=cps, block_size=bs, sq=Sq,
        scale=D ** -0.5, window=window, seq_cap=seq_cap, quantized=quantized,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, splits, cps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, Hkv, rows_p, D), out_map5),
            pl.BlockSpec((1, 1, Hkv, rows_p), out_map4),
            pl.BlockSpec((1, 1, Hkv, rows_p), out_map4),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, rows_p, D), jnp.float32),
            pltpu.VMEM((Hkv, rows_p, 1), jnp.float32),
            pltpu.VMEM((Hkv, rows_p, 1), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, splits, Hkv, rows_p, D), jnp.float32),
            jax.ShapeDtypeStruct((B, splits, Hkv, rows_p), jnp.float32),
            jax.ShapeDtypeStruct((B, splits, Hkv, rows_p), jnp.float32),
        ],
        interpret=interpret,
    )(bt, idx, *operands)
    out = _combine_splits(acc, m, l)
    return _unpack_out(out, B, Sq, Hq, D, groups, rows).astype(q.dtype)


# ---------------------------------------------------------------------------
# bounded pure-JAX fallback (the non-TPU default)
# ---------------------------------------------------------------------------

def ref_paged_decode(
    q: jax.Array,
    cache,
    block_tables: jax.Array,
    index,
    *,
    window: Optional[int] = None,
    cols_per_iter: int = 8,
) -> jax.Array:
    """Online-softmax decode over block-table column chunks, bounded at run
    time to the max active length across slots.

    A ``lax.while_loop`` gathers ``cols_per_iter`` table columns per
    iteration and stops once ``col * block_size`` passes
    ``max(index) + Sq`` — so a batch at length ~100 in a 2048-token table
    touches ~100 tokens of pool, not 2048 (the old ``gather_kv`` extent).
    The iteration count is a *runtime* value: one compiled step serves every
    length, unlike shape-bounded slicing which would recompile per length.
    """
    B, Sq, Hq, D = q.shape
    _, Hkv, bs, _ = cache.k.shape
    groups = Hq // Hkv
    max_blocks = block_tables.shape[1]
    seq_cap = max_blocks * bs
    C = max(1, min(cols_per_iter, max_blocks))
    n_cols = -(-max_blocks // C) * C
    bt = block_tables.astype(jnp.int32)
    if n_cols != max_blocks:
        bt = jnp.pad(bt, ((0, 0), (0, n_cols - max_blocks)),
                     constant_values=NULL_BLOCK)
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))

    qf = (q.astype(jnp.float32) * (D ** -0.5)).reshape(B, Sq, Hkv, groups, D)
    qf = qf.transpose(0, 2, 3, 1, 4)                       # (B, H, G, Sq, D)
    qpos = idx[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None, :]  # (B, Sq)
    # Tokens any slot can attend this step; the loop stops past it.
    bound = jnp.max(idx) + Sq
    span = C * bs

    def cond(carry):
        col = carry[0]
        return (col * bs < bound) & (col < max_blocks)

    def body(carry):
        col, m, l, acc = carry
        blk = jax.lax.dynamic_slice(bt, (0, col), (B, C))  # (B, C)
        k, v = gather_kv(cache, blk)                       # (B, span, H, D)
        s = jnp.einsum(
            "bhgqd,bkhd->bhgqk", qf, k.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )                                                  # (B, H, G, Sq, span)
        kpos = col * bs + jnp.arange(span, dtype=jnp.int32)
        mask = (kpos[None, None, :] <= qpos[:, :, None]) \
            & (kpos < seq_cap)[None, None, :]
        if window is not None:
            mask &= (qpos[:, :, None] - kpos[None, None, :]) < window
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (col + C, m_new, l_new, acc_new)

    m0 = jnp.full((B, Hkv, groups, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, groups, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, groups, Sq, D), jnp.float32)
    _, m, l, acc = jax.lax.while_loop(
        cond, body, (jnp.int32(0), m0, l0, acc0))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# backend dispatch (mirrors kernels/ops.py's switch)
# ---------------------------------------------------------------------------

_BACKENDS = ("auto", "gather", "blocked", "flash", "interpret")
_DECODE_BACKEND: Optional[str] = None
_DECODE_SPEC: Optional[FlashDecodeSpec] = None


def set_decode_backend(backend: Optional[str]) -> None:
    """Process-wide decode backend: "gather" (legacy full-extent baseline),
    "blocked" (bounded while_loop fallback), "flash" (Pallas kernel),
    "interpret" (Pallas under the interpreter — CPU tests), "auto"/None
    (flash on TPU, blocked elsewhere).  Binds at *trace* time: set it before
    a step is jit-traced (the engine does this in warmup)."""
    global _DECODE_BACKEND
    if backend is not None and backend not in _BACKENDS:
        raise ValueError(
            f"unknown decode backend {backend!r}; known: {_BACKENDS}")
    _DECODE_BACKEND = backend


def get_decode_backend() -> Optional[str]:
    return _DECODE_BACKEND


@contextlib.contextmanager
def decode_backend(backend: Optional[str]):
    """Scoped ``set_decode_backend`` (trace steps under it, like
    quant.modes.precision)."""
    prev = _DECODE_BACKEND
    set_decode_backend(backend)
    try:
        yield
    finally:
        set_decode_backend(prev)


def set_decode_spec(spec: Optional[FlashDecodeSpec]) -> None:
    """Bind a tuned design point for spec-less dispatch (trace-time, like
    the backend); the engine binds its autotuned winner here in warmup."""
    global _DECODE_SPEC
    _DECODE_SPEC = spec


def get_decode_spec() -> Optional[FlashDecodeSpec]:
    return _DECODE_SPEC


def _resolve_backend(backend: Optional[str]) -> str:
    b = backend or _DECODE_BACKEND or "auto"
    if b == "auto":
        from repro.kernels import ops as _ops

        r = _ops._resolve(None)
        if r in ("pallas", "pipelined"):
            return "flash"
        if r == "interpret":
            return "interpret"
        return "blocked"
    return b


def _gather_decode(q, cache, block_tables, index, *, window=None,
                   prefix_len: int = 0):
    """The legacy path: materialize the slot views, dense softmax over the
    full table extent.  Kept as the benchmark baseline and the
    ``prefix_len`` fallback (bidirectional prefixes never page in practice —
    VLM/encdec are excluded from paged serving)."""
    from repro.models.attention import decode_attention

    k, v = gather_kv(cache, block_tables)
    return decode_attention(q, k, v, index=index, window=window,
                            prefix_len=prefix_len)


def paged_decode_attention(
    q: jax.Array,
    cache,
    block_tables: jax.Array,
    index,
    *,
    window: Optional[int] = None,
    prefix_len: int = 0,
    backend: Optional[str] = None,
    spec: Optional[FlashDecodeSpec] = None,
) -> jax.Array:
    """Decode attention over a paged KV cache — the dispatch entry the model
    layer calls.  Equivalent to ``gather_kv`` + ``decode_attention`` for
    every backend (tested in tests/test_flash_decode.py); they differ only
    in how much pool they touch."""
    if prefix_len:
        return _gather_decode(q, cache, block_tables, index, window=window,
                              prefix_len=prefix_len)
    b = _resolve_backend(backend)
    spec = spec or _DECODE_SPEC or FlashDecodeSpec()
    if b == "gather":
        return _gather_decode(q, cache, block_tables, index, window=window)
    if b == "blocked":
        return ref_paged_decode(q, cache, block_tables, index, window=window,
                                cols_per_iter=spec.cols_per_iter)
    return flash_decode_attention(q, cache, block_tables, index,
                                  window=window, spec=spec,
                                  interpret=(b == "interpret"))


def make_flash_decode(spec: FlashDecodeSpec, *, interpret: bool = False):
    """Registry factory (kernels/registry.py): specialize the paged decode
    kernel at one ``FlashDecodeSpec`` design point.  Returns
    ``fn(q, cache, block_tables, index, *, window=None)``."""

    def fn(q, cache, block_tables, index, *, window=None):
        return flash_decode_attention(
            q, cache, block_tables, index, window=window, spec=spec,
            interpret=interpret)

    return fn
