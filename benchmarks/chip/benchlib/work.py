"""Operations and bytes the algorithm needs, from shapes alone.

These count the work of the model's mathematics, not of an implementation:
padding rows, idle slots, tiles and fusions count for nothing, so no change
to the program can move them.  One multiply-add is 2 operations; weights,
activations and the KV pool are 2-byte (bf16) values.

Peaks of each chip are keyed by ``jax.Device.device_kind``; a device that is
not listed is an error, never a default.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Tuple

BYTES = 2          # bf16


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float       # bf16 FLOP/s
    hbm_bw: float      # bytes/s
    hbm_bytes: float
    source: str


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def attn_flops(m, ctx: int) -> float:
    """Score and value products of one query over `ctx` keys, all layers."""
    return 4.0 * m.n_heads * m.head_dim * ctx * m.n_layers


def prefill_chunk_flops(m, start: int, chunk: int) -> float:
    """Useful FLOPs of one prefill chunk of `chunk` tokens at positions
    start .. start+chunk-1: the projections of every token, causal
    attention over each token's context, and one head row."""
    ctx_sum = chunk * start + chunk * (chunk + 1) // 2
    return (2.0 * m.trunk_params * chunk + attn_flops(m, 1) * ctx_sum
            + 2.0 * m.head_params)


def decode_flops(m, contexts: Iterable[int]) -> float:
    """Useful FLOPs of one decode step: each active row runs the trunk and
    the head, and attends over its context."""
    ctx = list(contexts)
    return (2.0 * (m.trunk_params + m.head_params) * len(ctx)
            + sum(attn_flops(m, c) for c in ctx))


def layer_gemms(m, rows: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of one layer's projections over `rows` tokens."""
    d, D, F = m.d_model, m.head_dim, m.d_ff
    return [(rows, d, m.n_heads * D), (rows, d, m.n_kv_heads * D),
            (rows, d, m.n_kv_heads * D), (rows, m.n_heads * D, d),
            (rows, d, F), (rows, d, F), (rows, F, d)]


def step_gemms(m, rows: int, head_rows: int) -> List[Tuple[int, int, int]]:
    """Every GeMM of one step: the layers' projections over `rows` tokens
    and the head over `head_rows`."""
    return (layer_gemms(m, rows) * m.n_layers
            + [(head_rows, m.d_model, m.vocab)])


def gemm_least_s(shape: Tuple[int, int, int], peaks: Peaks) -> float:
    """The least time of one GeMM: operations at peak compute or bytes
    (A and B read once, C written once) at peak bandwidth."""
    M, K, N = shape
    ops = 2.0 * M * K * N
    nbytes = BYTES * (M * K + K * N + M * N)
    return max(ops / peaks.flops, nbytes / peaks.hbm_bw)


def gemms_least_s(shapes, peaks: Peaks) -> float:
    return sum(gemm_least_s(s, peaks) for s in shapes)


def decode_attn_least_s(m, contexts: Iterable[int], peaks: Peaks) -> float:
    """The least time of one step's decode attention, all layers: the K and
    V of each active row's live context read once, its query read and its
    output written, against the score and value products."""
    ctx = list(contexts)
    kv = sum(2 * c * m.n_kv_heads * m.head_dim for c in ctx)
    qo = 2 * len(ctx) * m.n_heads * m.head_dim
    nbytes = BYTES * (kv + qo) * m.n_layers
    ops = sum(attn_flops(m, c) for c in ctx)
    return max(ops / peaks.flops, nbytes / peaks.hbm_bw)
