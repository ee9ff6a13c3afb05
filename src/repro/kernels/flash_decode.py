"""Paged flash-decode (Pallas TPU): decode attention straight off the KV pool.

The serving decode path previously materialized every slot's cache view with
``gather_kv`` (a (B, max_blocks * block_size, H, D) gather — the *full* table
extent, mostly null blocks at short lengths) and ran a whole-cache einsum.
This kernel instead walks the per-slot block tables — the paper's
programmable strided memory access (Sec 3.3) applied to decode: the block
table is the stride program.  Each (slot, split) program walks only its
row's live steps, so the DMA traffic *and* the compute of a decode step
follow the live context, not the table extent, and nothing is ever
materialized per slot.

Shape story (one grid program per (slot, split); one loop step = 128 key
positions, all kv heads):

  q            (B, Sq, Hq, D)     -> packed (B, Hkv, G * Sq, D) rows
  k/v pool     (num_blocks, Hkv, block_size, D), left in HBM: a step DMAs
               its ``P = blocks_per_step(...)`` blocks by table entry,
               ``tables[b, step * P + p]``, into a double buffer in VMEM,
               the next step's blocks while this one computes
  live steps   ``live_steps(...)``: a step is walked while it starts at or
               before the row's last query and its first table entry is
               not the null block (a released slot reads as empty); the
               engine's ``kv_blocks`` counter reads the same function
  outputs      per-split partial (acc, m, l) — online-softmax state — reduced
               in a cheap second stage (split-K over the sequence steps)

GQA is handled by packing the G query heads of a kv head (times the Sq query
positions — Sq > 1 for speculative verify and chunked prefill) into the row
axis of a single lane-dense (rows, 128) score tile, so KV is fetched once per
kv head, never repeated.  Per-slot length masking (``kpos <= index[b] + t``)
and sliding windows are applied in-kernel.

int8 KV residency: when the pool carries per-(block, position, kv-head)
scales (``PagedKVCache.k_scale``/``v_scale``, see serving/kv_cache.py), the
kernel fetches int8 K/V blocks and dequantizes them in registers inside the
inner loop — no dequantized copy of the cache ever exists.  The scales of
each row's table are gathered lane-dense before the kernel (the chip's
compiler refuses a DMA of a scale block narrower than 128 lanes).

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
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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

    num_splits     split-K factor over the kernel's steps: each split is
                   one more grid program per slot and produces partial
                   (acc, m, l) reduced in stage 2.  1 = no split.
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

STEP_TOKENS = 128   # key positions per kernel step: one lane-dense score tile


def blocks_per_step(block_size: int, max_blocks: int) -> int:
    """Pool blocks one kernel step fetches: enough to cover ``STEP_TOKENS``
    key positions when the block size divides it, cut to a divisor of the
    table width so that every block a step fetches is a table column; else
    one block."""
    if STEP_TOKENS % block_size:
        return 1
    return math.gcd(STEP_TOKENS // block_size, max_blocks)


def live_steps(index, sq: int, tables, block_size: int):
    """Per row, the kernel steps that hold keys the row attends.

    Step g covers table columns ``[g * P, (g + 1) * P)`` with ``P =
    blocks_per_step(...)``.  It is live while its first key position is at
    most the row's last query position (``index + sq - 1``) and its first
    table entry is not ``NULL_BLOCK``: a released slot's row is all null
    while its length stays stale until the slot is reset.  The walk stops at
    the first dead step.  Takes numpy arrays (the engine's ``kv_blocks``
    counter) and jax arrays (the kernel's trip counts) alike.
    """
    P = blocks_per_step(block_size, tables.shape[1])
    first = tables[:, ::P]                                 # (B, steps)
    start = np.arange(first.shape[1]) * (P * block_size)
    live = (index[:, None] + sq > start) & (first != NULL_BLOCK)
    return live.astype(np.int32).cumprod(axis=1).sum(axis=1)


def _unrolled(n: int, body) -> None:
    """``for i in range(n): body(i)``, traced once and unrolled when the
    kernel is lowered: the chip runs the same straight-line code, and each
    step program that holds the kernel traces it in a fraction of the time
    (a warm engine traces every step it loads from the compile cache)."""
    def run(i, carry):
        body(i)
        return carry

    jax.lax.fori_loop(0, n, run, 0, unroll=True)


def _decode_kernel(
    bt_ref, idx_ref, n_ref,                # scalar-prefetch: tables, index,
    q_ref, k_hbm, v_hbm, *rest,            # live steps per row
    steps_per_split: int, blocks: int, block_size: int, sq: int,
    scale: float, window: Optional[int], seq_cap: int, quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, *rest = rest
    acc_out, m_out, l_out, kbuf, vbuf, sem, m_ref, l_ref = rest
    b = pl.program_id(0)
    s = pl.program_id(1)
    n_heads, rows, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    tokens = blocks * block_size
    lo = s * steps_per_split
    hi = jnp.minimum(lo + steps_per_split, n_ref[b])

    def fetch(g, slot):
        """Start the DMAs of step g's K and V blocks, by table entry, into
        one half of the double buffer; all signal that half's semaphore."""
        def block(p):
            blk = bt_ref[b, g * blocks + p]
            for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf)):
                pltpu.make_async_copy(src.at[blk], dst.at[slot, p],
                                      sem.at[slot]).start()

        _unrolled(blocks, block)

    def wait(slot):
        """Wait for one half of the double buffer: a wait counts bytes, so
        one descriptor the size of the half stands for its blocks' DMAs."""
        for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf)):
            pltpu.make_async_copy(src.at[pl.ds(0, blocks)], dst.at[slot],
                                  sem.at[slot]).wait()

    acc_out[...] = jnp.zeros_like(acc_out)     # accumulated in place
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(lo < hi)
    def _first():
        fetch(lo, 0)

    # Row r packs (group g, query offset t) = (r // sq, r % sq); padding rows
    # past G * Sq carry zero queries and are sliced off after the combine.
    # The mask is the same for every kv head of the step.
    t = jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 0) % sq
    qpos = idx_ref[b] + t
    kcol = jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 1)

    def step(g, carry):
        slot = (g - lo) % 2

        @pl.when(g + 1 < hi)
        def _prefetch():
            fetch(g + 1, 1 - slot)

        wait(slot)
        keys = pl.ds(g * tokens, tokens)       # this step's scale columns
        kpos = g * tokens + kcol
        mask = (kpos <= qpos) & (kpos < seq_cap)
        if window is not None:
            mask &= (qpos - kpos) < window

        def head(h):
            q = q_ref[0, h].astype(jnp.float32) * scale     # (rows, D)
            k = kbuf[slot, :, h].astype(jnp.float32).reshape(tokens, D)
            v = vbuf[slot, :, h].astype(jnp.float32).reshape(tokens, D)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # (rows, tokens)
            if quantized:
                # int8 codes times per-position scales: the scale of key i
                # multiplies score column i (and value i weighs p column
                # i), so the (1, tokens) scale row broadcasts over the rows.
                scores = scores * ks_ref[0, pl.ds(h, 1), keys]
            scores = jnp.where(mask, scores, NEG_INF)

            m_prev = m_ref[h]                               # (rows, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=-1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_new
            if quantized:
                p = p * vs_ref[0, pl.ds(h, 1), keys]
            acc_out[0, 0, h] = acc_out[0, 0, h] * alpha + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)

        _unrolled(n_heads, head)
        return carry

    jax.lax.fori_loop(lo, hi, step, 0)

    def flush(h):
        m_out[0, 0, h] = m_ref[h][:, 0]
        l_out[0, 0, h] = l_ref[h][:, 0]

    _unrolled(n_heads, flush)


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
    scale: Optional[float] = None,
    block_base=0,
) -> jax.Array:
    """Decode attention over the paged pool via the Pallas kernel; `scale`
    is the softmax scale (None: D ** -0.5).  ``block_base`` is the pool row
    of this layer's block 0 when the pool merges several layers' pools
    (serving/kv_cache.py ``write_kv``); live steps are counted on the table
    before that offset, so a null entry still reads as empty.

    The pools stay in HBM in their ``(num_blocks, Hkv, bs, D)`` layout; each
    (row, split) program DMAs its live steps' blocks, by table entry, into
    a double buffer in VMEM.  Every block the grid pipelines (q, the int8
    scale rows, the partials) keeps its last two dims equal to whole array
    dims, so the TPU's (8, 128) tiling rule holds for any kv-head count and
    split factor.
    """
    spec = spec or FlashDecodeSpec()
    B, Sq, Hq, D = q.shape
    nb, Hkv, bs, _ = cache.k.shape
    groups = Hq // Hkv
    max_blocks = block_tables.shape[1]
    seq_cap = max_blocks * bs
    P = blocks_per_step(bs, max_blocks)
    n_steps = max_blocks // P

    bt = block_tables.astype(jnp.int32)
    idx = jnp.asarray(index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    n_live = live_steps(idx, Sq, bt, bs)
    bt = bt + block_base

    splits = max(1, min(spec.num_splits, n_steps))
    sps = -(-n_steps // splits)
    pad_cols = splits * sps * P - max_blocks
    if pad_cols:
        bt = jnp.pad(bt, ((0, 0), (0, pad_cols)),
                     constant_values=NULL_BLOCK)

    qr, rows, rows_p = _pack_q(q, groups, Hkv)
    quantized = cache.k_scale is not None

    def qmap(b, s, bt, idx, n):
        return (b, 0, 0, 0)

    def rowmap(b, s, bt, idx, n):
        return (b, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, Hkv, rows_p, D), qmap), hbm, hbm]
    operands = [qr, cache.k, cache.v]
    scratch = [pltpu.VMEM((2, P, Hkv, bs, D), cache.k.dtype),
               pltpu.VMEM((2, P, Hkv, bs, D), cache.v.dtype)]
    if quantized:
        # The chip's compiler refuses a DMA of a (Hkv, bs) slice whose last
        # dim is under 128 lanes, so the scales of each row's table are
        # gathered here, lane-dense, and read whole per row.
        def rows_of(scale):
            r = scale[bt]                                  # (B, C, Hkv, bs)
            return r.transpose(0, 2, 1, 3).reshape(B, Hkv, -1)

        smap = pl.BlockSpec((1, Hkv, bt.shape[1] * bs), rowmap)
        in_specs += [smap, smap]
        operands += [rows_of(cache.k_scale), rows_of(cache.v_scale)]
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((Hkv, rows_p, 1), jnp.float32),
        pltpu.VMEM((Hkv, rows_p, 1), jnp.float32),
    ]

    def out_map4(b, s, bt, idx, n):
        return (b, s, 0, 0)

    def out_map5(b, s, bt, idx, n):
        return (b, s, 0, 0, 0)

    kernel = functools.partial(
        _decode_kernel, steps_per_split=sps, blocks=P, block_size=bs, sq=Sq,
        scale=D ** -0.5 if scale is None else scale, window=window,
        seq_cap=seq_cap, quantized=quantized,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, splits),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, Hkv, rows_p, D), out_map5),
            pl.BlockSpec((1, 1, Hkv, rows_p), out_map4),
            pl.BlockSpec((1, 1, Hkv, rows_p), out_map4),
        ],
        scratch_shapes=scratch,
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
    )(bt, idx, n_live, *operands)
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
    scale: Optional[float] = None,
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

    scale = D ** -0.5 if scale is None else scale
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, groups, D)
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
                   prefix_len: int = 0, scale=None):
    """The legacy path: materialize the slot views, dense softmax over the
    full table extent.  Kept as the benchmark baseline and the
    ``prefix_len`` fallback (bidirectional prefixes never page in practice —
    VLM/encdec are excluded from paged serving)."""
    from repro.models.attention import decode_attention

    k, v = gather_kv(cache, block_tables)
    return decode_attention(q, k, v, index=index, window=window,
                            prefix_len=prefix_len, scale=scale)


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
    scale: Optional[float] = None,
    block_base=0,
) -> jax.Array:
    """Decode attention over a paged KV cache — the dispatch entry the model
    layer calls.  Equivalent to ``gather_kv`` + ``decode_attention`` for
    every backend (tested in tests/test_flash_decode.py); they differ only
    in how much pool they touch.  `scale` is the softmax scale (None:
    D ** -0.5); ``block_base`` is the pool row of this layer's block 0 (see
    ``flash_decode_attention``)."""
    b = "gather" if prefix_len else _resolve_backend(backend)
    spec = spec or _DECODE_SPEC or FlashDecodeSpec()
    if b == "gather":
        return _gather_decode(q, cache, block_tables + block_base, index,
                              window=window, prefix_len=prefix_len,
                              scale=scale)
    if b == "blocked":
        return ref_paged_decode(q, cache, block_tables + block_base, index,
                                window=window,
                                cols_per_iter=spec.cols_per_iter, scale=scale)
    return flash_decode_attention(q, cache, block_tables, index,
                                  window=window, spec=spec,
                                  interpret=(b == "interpret"), scale=scale,
                                  block_base=block_base)


def make_flash_decode(spec: FlashDecodeSpec, *, interpret: bool = False):
    """Registry factory (kernels/registry.py): specialize the paged decode
    kernel at one ``FlashDecodeSpec`` design point.  Returns
    ``fn(q, cache, block_tables, index, *, window=None)``."""

    def fn(q, cache, block_tables, index, *, window=None):
        return flash_decode_attention(
            q, cache, block_tables, index, window=window, spec=spec,
            interpret=interpret)

    return fn
