"""Decode-attention tuning: FlashDecodeSpec search, cached like GeMM tiles.

The GeMM autotuner closes the paper's generator loop for matmuls: enumerate
legal design points, rank (analytic model or wall clock), persist the winner.
This module gives the paged flash-decode kernel (kernels/flash_decode.py) the
same treatment for its two knobs:

  num_splits     split-K factor over the kernel's 128-token steps: each
                 split is one more (row, split) program and one more
                 partial for the combine;
  cols_per_iter  table columns per ``while_loop`` chunk of the bounded
                 pure-JAX fallback (iteration overhead vs gather overshoot).

Winners land in the same ``TuneCache`` registry as GeMM tiles under an
``fd...|flash_decode`` key (see ``decode_cache_key``), so one
REPRO_TUNE_CACHE file carries a deployment's full configuration — GeMM tiles
and decode design points — exactly like the paper's generated CSR image.

The analytic model is deliberately coarse: costs are in MAC-ish units with
fixed per-step / per-program / combine / iteration overheads, enough to rank
the knobs deterministically on any host.  ``mode="wallclock"`` times the
real dispatch path instead — the Pallas kernel on TPU, the bounded fallback
elsewhere.
"""

from __future__ import annotations

import time
from typing import List, NamedTuple, Optional

from repro.kernels.flash_decode import FlashDecodeSpec, blocks_per_step
from repro.tuning.autotuner import (
    Autotuner,
    TuneResult,
    all_candidates_failed,
    get_tuner,
)
from repro.tuning.cache import CacheEntry

# Coarse cost-model constants (dimensionless MAC-ish units).
_STEP_OVERHEAD = 1000.0    # per kernel step: DMA issue and wait, loop, mask
_PROGRAM_OVERHEAD = 4000.0  # per (row, split) program: grid step, a first
                            # fetch nothing overlaps, partial (acc, m, l)
_COMBINE_PER_ELEM = 4.0    # stage-2 rescale/accumulate per partial element
_ITER_OVERHEAD = 4000.0    # while_loop iteration dispatch (fallback path)
_MAX_SPLITS = 16
_MAX_CHUNK_TOKENS = 2048   # fallback gather chunk bound (cols * block_size)


class DecodeShape(NamedTuple):
    """The decode-attention problem, as the tuner keys it."""

    slots: int        # decode batch width B
    kv_heads: int
    groups: int       # Hq // Hkv (GQA fan-in)
    head_dim: int
    sq: int           # query positions per step (1 decode, K+1 verify)
    block_size: int   # pool block tokens
    max_blocks: int   # block-table columns per slot


def decode_cache_key(shape: DecodeShape, dtype, mode: str = "analytic") -> str:
    """Registry key — mirrors ``cache.cache_key``'s shape|dtype|backend form
    (plus the wallclock suffix rule of ``Autotuner.tune``)."""
    name = getattr(dtype, "name", str(dtype))
    key = (f"fd{shape.slots}x{shape.kv_heads}h{shape.groups}g"
           f"{shape.head_dim}d{shape.sq}q"
           f"|bs{shape.block_size}x{shape.max_blocks}|{name}|flash_decode")
    if mode != "analytic":
        key += f"|{mode}"
    return key


def _pow2s(cap: int) -> List[int]:
    out, v = [], 1
    while v <= cap:
        out.append(v)
        v *= 2
    return out or [1]


def enumerate_decode_specs(shape: DecodeShape) -> List[FlashDecodeSpec]:
    """Legal (num_splits, cols_per_iter) design points, default included,
    deterministic order (ascending splits, then cols) — same contract as
    ``candidates.enumerate_tiles``."""
    steps = shape.max_blocks // blocks_per_step(shape.block_size,
                                                shape.max_blocks)
    splits = _pow2s(min(_MAX_SPLITS, steps))
    cols_cap = max(1, min(shape.max_blocks,
                          _MAX_CHUNK_TOKENS // max(1, shape.block_size)))
    cols = _pow2s(cols_cap)
    seen, out = set(), []
    default = FlashDecodeSpec()
    for spec in [default] + [
        FlashDecodeSpec(num_splits=s, cols_per_iter=c)
        for s in splits for c in cols
    ]:
        key = (spec.num_splits, spec.cols_per_iter)
        if key in seen:
            continue
        seen.add(key)
        out.append(spec)
    out.sort(key=lambda s: (s.num_splits, s.cols_per_iter))
    return out


def predict_decode_cost(spec: FlashDecodeSpec, shape: DecodeShape) -> float:
    """Rank a candidate: kernel cost + fallback-path cost.

    The two knobs are independent (each term consumes one), so ranking the
    sum tunes both jointly.  Per kv head, at the full table (every step
    live, as the wall-clock timing runs it): the kernel takes
    ``max_blocks / P`` steps of ``P`` blocks per row (``blocks_per_step``),
    each ``P * block_size * rows * head_dim * 2`` MAC-ish units (QK^T + PV)
    plus ``_STEP_OVERHEAD``.  Every (row, split) program runs in turn on
    the one TensorCore, so splits shorten nothing: each adds
    ``_PROGRAM_OVERHEAD`` and a partial for the combine.  Fallback chunks
    amortize ``_ITER_OVERHEAD`` against an expected half-chunk gather
    overshoot past the live length.
    """
    rows = max(8, shape.groups * shape.sq)
    block_cost = float(shape.block_size * rows * shape.head_dim * 2)
    P = blocks_per_step(shape.block_size, shape.max_blocks)
    steps = shape.max_blocks // P
    splits = max(1, min(spec.num_splits, steps))
    split_cost = shape.slots * (
        steps * (P * block_cost + _STEP_OVERHEAD)
        + splits * (_PROGRAM_OVERHEAD
                    + _COMBINE_PER_ELEM * rows * shape.head_dim))
    cols = max(1, min(spec.cols_per_iter, shape.max_blocks))
    iters = -(-shape.max_blocks // cols)
    ref_cost = iters * _ITER_OVERHEAD + (cols / 2.0) * block_cost
    return split_cost + ref_cost


def _time_candidate(spec: FlashDecodeSpec, shape: DecodeShape, dtype,
                    iters: int = 3) -> float:
    """Wall-clock one candidate through the real dispatch path (flash on
    TPU, the bounded fallback elsewhere) at the worst-case length."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import flash_decode as fd
    from repro.serving.kv_cache import init_paged_kv

    B, mb, bs = shape.slots, shape.max_blocks, shape.block_size
    nb = B * mb + 1
    cache = init_paged_kv(nb, bs, shape.kv_heads, shape.head_dim, dtype)
    bt = (jnp.arange(B * mb, dtype=jnp.int32) + 1).reshape(B, mb)
    index = jnp.full((B,), mb * bs - shape.sq, jnp.int32)
    q = jnp.ones((B, shape.sq, shape.kv_heads * shape.groups, shape.head_dim),
                 cache.k.dtype)
    backend = "flash" if jax.default_backend() == "tpu" else "blocked"
    fn = jax.jit(lambda q, c, t, i: fd.paged_decode_attention(
        q, c, t, i, backend=backend, spec=spec))
    fn(q, cache, bt, index).block_until_ready()      # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(q, cache, bt, index)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def tune_decode(
    shape: DecodeShape,
    dtype="float32",
    *,
    mode: str = "analytic",
    tuner: Optional[Autotuner] = None,
    force: bool = False,
) -> TuneResult:
    """Best FlashDecodeSpec for `shape`, cached in the shared registry.

    Uses the default tuner's ``TuneCache`` (REPRO_TUNE_CACHE honored), so
    decode winners persist next to GeMM tiles.  ``mode`` follows
    ``Autotuner``: "analytic" ranks by ``predict_decode_cost``; "wallclock"
    times each candidate's real dispatch path and — like the GeMM tuner —
    refuses to resolve a wallclock query from an analytic cache entry.
    """
    if mode not in ("analytic", "wallclock"):
        raise ValueError(f"unknown tuning mode {mode!r}")
    t = tuner or get_tuner()
    key = decode_cache_key(shape, dtype, mode)
    if not force:
        hit = t.cache.get(key)
        if hit is not None and (mode == "analytic" or hit.source == mode):
            return TuneResult(spec=hit.spec, score=hit.score,
                              source=hit.source, from_cache=True)
    cands = enumerate_decode_specs(shape)
    best, best_score = None, float("inf")
    if mode == "wallclock":
        failed = []
        for spec in cands:
            try:
                s = _time_candidate(spec, shape, dtype)
            except Exception as e:        # refused or failed here
                failed.append((f"splits={spec.num_splits} "
                               f"cols={spec.cols_per_iter}", e))
                continue
            if s < best_score:
                best, best_score = spec, s
        if best is None:
            raise all_candidates_failed(
                decode_cache_key(shape, dtype, mode), failed)
    else:
        for spec in cands:
            s = predict_decode_cost(spec, shape)
            if s < best_score:            # strict <: ties break to the
                best, best_score = spec, s  # smallest knobs (sorted cands)
    source = mode
    t.cache.put(key, CacheEntry(spec=best, score=best_score, source=source),
                persist=t.persist)
    return TuneResult(spec=best, score=best_score, source=source,
                      from_cache=False, candidates=len(cands))


def serving_decode_shape(cfg, *, slots: int, block_size: int,
                         max_blocks: int, sq: int = 1
                         ) -> Optional[DecodeShape]:
    """The decode-attention problem one serving engine dispatches every
    tick, or None for stacks with no attention layers (pure SSM/xLSTM —
    nothing to tune)."""
    kinds = set(cfg.layer_kinds())
    if not kinds & {"attn", "attn_local"}:
        return None
    return DecodeShape(
        slots=slots, kv_heads=cfg.n_kv_heads,
        groups=cfg.n_heads // cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, sq=sq,
        block_size=block_size, max_blocks=max_blocks)


def tune_decode_for_serving(cfg, *, slots: int, block_size: int,
                            max_blocks: int, mode: str = "analytic",
                            dtype: Optional[str] = None,
                            verbose: bool = False
                            ) -> Optional[FlashDecodeSpec]:
    """Engine-warmup entry: tune the hot Sq=1 decode shape and return the
    winner (None when the stack has no attention).  The engine binds it via
    ``flash_decode.set_decode_spec`` before tracing its steps."""
    shape = serving_decode_shape(cfg, slots=slots, block_size=block_size,
                                 max_blocks=max_blocks)
    if shape is None:
        return None
    r = tune_decode(shape, dtype or cfg.dtype, mode=mode)
    if verbose:
        hit = "cache" if r.from_cache else r.source
        print(f"autotune[decode]: splits={r.spec.num_splits} "
              f"cols={r.spec.cols_per_iter} for {cfg.name} "
              f"(bs{shape.block_size}x{shape.max_blocks}, {hit})")
    return r.spec
