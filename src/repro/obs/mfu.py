"""Live utilization gauges: achieved throughput vs the analytic bound.

The paper's headline number is *measured utilization* — how close the
generated instance runs to its cycle model's prediction (81.89-99.34%
across workloads, Table 2).  This module is that comparison lifted to the
serving stack, computed live per tick instead of after the fact:

  * **utilization** (per phase) = modeled step time / measured step time.
    The modeled time is the analytic bound from the same models the
    autotuner ranks with: the re-targeted cycle model
    (`tuning/model.py::predict` summed over the step's projection GeMMs,
    launch overhead included) vs the roofline terms from `core/hw.py`
    constants (compute at peak FLOP/s, weights streamed once per step at
    HBM bandwidth — the `launch/roofline.py` decomposition), whichever
    binds.  This is the paper's temporal-utilization analogue: 1.0 means
    the step ran exactly as fast as the model says the hardware allows.
  * **mfu** (per phase) = useful model FLOPs / (measured time x peak
    FLOP/s), with useful FLOPs = 2 x active params x committed tokens
    (`launch/roofline.py::model_flops`' inference formula) — the
    cross-paper-comparable Model FLOPs Utilization figure.

Phases are accounted separately (prefill / decode / verify) because their
bounds differ by orders of magnitude: a decode step is weight-bandwidth
bound at M=slots rows, a prefill chunk amortizes the same weight traffic
over C token rows, and a speculative verify step runs M=slots x (K+1).

The gauges are a few float adds per tick (the bound is memoized per
(phase, rows)) — cheap enough to stay on by default; `EngineMetrics` and
`ClusterMetrics` surface them in `summary()`.

Peaks come from `core/hw.py::PEAKS`, looked up by the running device's
``device_kind``.  On a device the table does not list (the CPU among them)
both gauges are not measured: `utilization`/`mfu` return None, never a
figure against another chip's peaks.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Optional

from repro.core.hw import PEAKS

PHASES = ("prefill", "decode", "verify")

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


@dataclasses.dataclass
class PhaseStat:
    """Accumulated measurements for one serving phase."""

    time_s: float = 0.0      # measured wall time in this phase's steps
    flops: float = 0.0       # useful model FLOPs (committed tokens)
    tokens: int = 0          # committed tokens
    rows: int = 0            # executed GeMM rows (padding slots included)
    steps: int = 0
    bound_s: float = 0.0     # accumulated analytic lower-bound time

    def merge(self, other: "PhaseStat") -> None:
        self.time_s += other.time_s
        self.flops += other.flops
        self.tokens += other.tokens
        self.rows += other.rows
        self.steps += other.steps
        self.bound_s += other.bound_s


class MfuMeter:
    """Per-phase utilization/MFU accounting for one model config."""

    def __init__(self, cfg, *, device_kind: Optional[str] = None):
        """`device_kind` defaults to that of ``jax.devices()[0]``."""
        if device_kind is None:
            import jax

            device_kind = jax.devices()[0].device_kind
        self.arch = cfg.name
        self.dtype = cfg.dtype
        self.device_kind = device_kind
        peaks = PEAKS.get(device_kind)
        self.peak_flops = peaks.bf16_flops if peaks else None
        self.hbm_bw = peaks.hbm_bw if peaks else None
        active = cfg.active_param_count()
        self.flops_per_token = 2.0 * active
        self.param_bytes = active * _DTYPE_BYTES.get(cfg.dtype, 2)
        self.phases: Dict[str, PhaseStat] = {p: PhaseStat() for p in PHASES}
        self._cfg = cfg
        self._bound_cache: Dict[int, float] = {}

    # -- recording -----------------------------------------------------------

    def note(self, phase: str, *, tokens: int, rows: int, time_s: float
             ) -> None:
        """Account one step: `tokens` committed, `rows` GeMM rows executed
        (padding slots included), `time_s` measured wall time."""
        st = self.phases[phase]
        st.time_s += time_s
        st.tokens += tokens
        st.rows += rows
        st.steps += 1
        st.flops += tokens * self.flops_per_token
        if self.measurable:
            st.bound_s += self.step_bound_s(rows)

    @property
    def measurable(self) -> bool:
        """True when the device has published peaks to measure against."""
        return self.peak_flops is not None

    def step_bound_s(self, rows: int) -> float:
        """Analytic lower-bound time for one step executing `rows` token
        rows: max of the roofline terms (compute at peak, weights streamed
        once at HBM bandwidth) and the cycle model's predicted time for the
        step's dense-projection GeMMs.  Memoized — the engine only ever
        executes a handful of distinct row counts (slots, chunk buckets,
        verify widths)."""
        cached = self._bound_cache.get(rows)
        if cached is not None:
            return cached
        compute_s = rows * self.flops_per_token / self.peak_flops
        memory_s = self.param_bytes / self.hbm_bw
        bound = max(compute_s, memory_s, self._gemm_step_s(rows))
        self._bound_cache[rows] = bound
        return bound

    def _gemm_step_s(self, rows: int) -> float:
        """Cycle-model time (tuning/model.py) for the step's per-layer
        projection GeMMs at M=rows — launch overhead and tile padding
        included, the same model the autotuner ranks tiles with.  Covers
        only the spec-dispatched dense projections (MoE experts and SSM
        scans do not route through ops.gemm — see
        engine.serving_gemm_shapes); the roofline terms in step_bound_s
        cover the rest, and the bound takes the max."""
        try:
            from repro.core.dataflow import GemmShape
            from repro.core.generator import TpuGemmSpec
            from repro.tuning import model as tmodel

            cfg = self._cfg
            d, ff, vocab = cfg.d_model, cfg.d_ff, cfg.vocab
            hd = cfg.resolved_head_dim
            hq, hkv = cfg.n_heads, cfg.n_kv_heads
            shapes = []
            for kind in cfg.layer_kinds():
                if kind in ("attn", "attn_local"):
                    shapes += [
                        GemmShape(rows, d, hq * hd),   # q
                        GemmShape(rows, d, hkv * hd),  # k
                        GemmShape(rows, d, hkv * hd),  # v
                        GemmShape(rows, hq * hd, d),   # o
                    ]
                if cfg.moe is None:
                    shapes += [GemmShape(rows, d, ff), GemmShape(rows, ff, d)]
            spec = TpuGemmSpec(tm=8, tk=128, tn=128)
            per_group = sum(
                tmodel.predict(spec, s, self.dtype).time_s for s in shapes)
            head = tmodel.predict(
                spec, GemmShape(rows, d, vocab), self.dtype).time_s
            return cfg.n_groups * per_group + head
        except Exception:
            # The cycle-model term is an enrichment of the bound, not a
            # correctness dependency — an exotic config falls back to the
            # roofline terms alone.
            return 0.0

    # -- reporting -----------------------------------------------------------

    def utilization(self, phase: str) -> Optional[float]:
        """Modeled time / measured time for this phase (the paper's
        temporal-utilization analogue; 0.0 before any step ran, None on a
        device without published peaks)."""
        if not self.measurable:
            return None
        st = self.phases[phase]
        return st.bound_s / st.time_s if st.time_s > 0 else 0.0

    def mfu(self, phase: str) -> Optional[float]:
        """Useful model FLOPs / (measured time x peak FLOP/s); None on a
        device without published peaks."""
        if not self.measurable:
            return None
        st = self.phases[phase]
        return (st.flops / (st.time_s * self.peak_flops)
                if st.time_s > 0 else 0.0)

    def active_phases(self) -> Iterable[str]:
        return [p for p in PHASES if self.phases[p].steps]

    def merge(self, other: "MfuMeter") -> "MfuMeter":
        """Fold another meter's phase stats into self (cluster aggregation
        over same-config replicas); returns self."""
        for p in PHASES:
            self.phases[p].merge(other.phases[p])
        return self

    @classmethod
    def merged(cls, meters: Iterable["MfuMeter"]) -> Optional["MfuMeter"]:
        meters = [m for m in meters if m is not None]
        if not meters:
            return None
        out = cls(meters[0]._cfg, device_kind=meters[0].device_kind)
        for m in meters:
            out.merge(m)
        return out

    def summary(self) -> str:
        """Compact per-phase fragment for EngineMetrics.summary():
        ``util[decode]=0.12% mfu[decode]=0.03% ...`` (active phases only;
        "n/m" where the device has no published peaks).
        """
        def pct(v):
            return "n/m" if v is None else f"{v:.2%}"

        parts = []
        for p in self.active_phases():
            parts.append(f"util[{p}]={pct(self.utilization(p))} "
                         f"mfu[{p}]={pct(self.mfu(p))}")
        return " ".join(parts)

    def as_dict(self) -> dict:
        return {
            "arch": self.arch,
            "dtype": self.dtype,
            "device_kind": self.device_kind,
            "peak_flops": self.peak_flops,
            "hbm_bw": self.hbm_bw,
            "phases": {
                p: {
                    "time_s": st.time_s,
                    "flops": st.flops,
                    "tokens": st.tokens,
                    "rows": st.rows,
                    "steps": st.steps,
                    "bound_s": st.bound_s,
                    "utilization": self.utilization(p),
                    "mfu": self.mfu(p),
                }
                for p, st in self.phases.items() if st.steps
            },
        }
