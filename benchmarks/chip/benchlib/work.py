"""Operations and bytes the algorithm needs, from shapes alone, and the
chips' peaks they are held against.

The counts of each architecture's layers are in its module (arch/*.py);
here are the peaks and the least time of a GeMM.  They count the work of
the model's mathematics, not of an implementation: padding rows, idle
slots, tiles and fusions count for nothing, so no change to the program can
move them.  One multiply-add is 2 operations; weights, activations and the
KV pool are 2-byte (bf16) values.

Peaks of each chip are keyed by ``jax.Device.device_kind``; a device that is
not listed is an error, never a default.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

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


def gemm_least_s(shape: Tuple[int, int, int], peaks: Peaks) -> float:
    """The least time of one GeMM: operations at peak compute or bytes
    (A and B read once, C written once) at peak bandwidth."""
    M, K, N = shape
    ops = 2.0 * M * K * N
    nbytes = BYTES * (M * K + K * N + M * N)
    return max(ops / peaks.flops, nbytes / peaks.hbm_bw)


def gemms_least_s(shapes, peaks: Peaks) -> float:
    return sum(gemm_least_s(s, peaks) for s in shapes)
