"""TPU hardware constants shared across the roofline and tuning models.

Single source of truth (jax-free, so the analytic autotuner path never pays
the jax import): `launch/mesh.py` re-exports these for the mesh-level
roofline, `tuning/model.py` derives its cycle-model units from them, and
`obs/mfu.py` looks the running device up in `PEAKS`.
Retarget the chip here and every consumer moves together.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one TPU generation."""

    bf16_flops: float    # FLOP/s
    int8_ops: float      # OP/s
    hbm_bw: float        # bytes/s
    hbm_bytes: float
    source: str


# Keyed by ``jax.Device.device_kind``.  A device that is not listed has no
# peaks: utilization against it is not measured, never borrowed from another
# chip.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e"'),
}

# The analytic models (tuning/model.py, launch/roofline.py) target v5e.
_TARGET = PEAKS["TPU v5 lite"]
PEAK_FLOPS_BF16 = _TARGET.bf16_flops
HBM_BW = _TARGET.hbm_bw
ICI_BW = 50e9                 # bytes/s per link (~per-axis usable)
CLOCK_HZ = 940e6              # core clock used to convert cycles <-> seconds
