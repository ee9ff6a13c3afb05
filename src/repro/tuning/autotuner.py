"""The autotuner: search tile space per workload, cache the winner.

Closes the paper's generator loop in software.  Where the Chisel generator
elaborates one accelerator per (Mu, Ku, Nu) and the designer picks the point
by DSE, the `Autotuner` elaborates one Pallas kernel per legal (TM, TK, TN)
and picks the point per *workload*:

  1. `candidates.enumerate_tiles`  — the legal design space for (shape, dtype);
  2. ranking                        — analytic (cycle model of
     `core/simulator.py` in TPU units, no device needed: the default) or
     empirical (wall-clock of the generated kernel on the local device);
  3. `cache.TuneCache`              — winners persist across processes,
     LRU-fronted so steady-state dispatch costs one dict lookup.

`tuned_gemm(a, b)` is the user-facing entry: every caller gets the best
known tile for its problem without hand-picking a spec.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import List, Optional, Sequence, Tuple

from repro.core.dataflow import GemmShape
from repro.core.generator import CASE_STUDY, OpenGeMMConfig, TpuGemmSpec, VMEM_BUDGET_BYTES
from repro.tuning import model as tmodel
from repro.tuning.cache import CacheEntry, TuneCache, cache_key
from repro.tuning.candidates import enumerate_tiles

# Backends that name a real kernel specialization.  "interpret" runs the
# "pallas" kernel under the interpreter, so it shares that tuning key.
# "dequant" and "w8a8" are the int8 deployment epilogues (kernels/registry.py):
# their fused scale write-back costs differently from the plain GeMM, so each
# is its own tuning key.
_KERNEL_BACKEND = {
    "pallas": "pallas", "interpret": "pallas", "pipelined": "pipelined",
    "dequant": "dequant", "w8a8": "w8a8",
}


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Outcome of one tuning query."""

    spec: TpuGemmSpec
    score: float                 # predicted clocks (analytic) / seconds (wallclock)
    source: str                  # "analytic" | "wallclock" | "default"
    from_cache: bool = False
    candidates: int = 0


class Autotuner:
    """Tile-shape search with a persistent winner cache.

    mode="analytic"   rank by the simulator-derived cycle model (fast, exact
                      ordering of the model; works on any host).
    mode="wallclock"  time each candidate kernel on the local device; ranks
                      analytically (and records source="analytic") when the
                      backend cannot run here (a pallas kernel on a CPU-only
                      host); raises when every candidate fails where it can.
    """

    def __init__(
        self,
        config: Optional[OpenGeMMConfig] = None,
        cache: Optional[TuneCache] = None,
        *,
        mode: str = "analytic",
        vmem_budget: int = VMEM_BUDGET_BYTES,
        max_candidates: Optional[int] = None,
        persist: bool = True,
        wallclock_iters: int = 3,
    ):
        if mode not in ("analytic", "wallclock"):
            raise ValueError(f"unknown tuning mode {mode!r}")
        self.config = config or CASE_STUDY
        self.cache = cache if cache is not None else TuneCache()
        self.mode = mode
        self.vmem_budget = vmem_budget
        self.max_candidates = max_candidates
        self.persist = persist
        self.wallclock_iters = wallclock_iters

    # -- public API ----------------------------------------------------------

    def tune(
        self,
        shape: GemmShape,
        dtype="int8",
        *,
        backend: str = "pallas",
        depth=None,
        force: bool = False,
    ) -> TuneResult:
        """Best spec for (shape, dtype, backend), cached.

        `depth` follows `candidates.enumerate_tiles`; by default the
        "pipelined" backend sweeps the paper's D_stream axis (2/3/4) since
        its ring buffer really honors the knob.
        """
        kb = _KERNEL_BACKEND.get(backend, backend)
        key = cache_key(shape, dtype, kb)
        # Winners from different ranking modes / budgets are not
        # interchangeable: a wallclock re-run must not resolve to a cached
        # analytic entry (and vice versa).
        if self.mode != "analytic":
            key += f"|{self.mode}"
        if self.vmem_budget != VMEM_BUDGET_BYTES:
            key += f"|vmem{self.vmem_budget}"
        if depth is not None:
            ds = (depth,) if isinstance(depth, int) else tuple(depth)
            key += "|d" + "-".join(map(str, ds))
        if self.max_candidates is not None:
            key += f"|top{self.max_candidates}"
        if not force:
            hit = self.cache.get(key)
            # A wallclock tuner only trusts measured entries: an analytic
            # *fallback* persisted by a host that couldn't measure must not
            # stop a capable host from actually timing kernels.
            if hit is not None and (self.mode == "analytic" or hit.source == self.mode):
                return TuneResult(
                    spec=hit.spec, score=hit.score, source=hit.source,
                    from_cache=True,
                )
        if depth is None and kb == "pipelined":
            depth = (2, 3, 4)
        cands = enumerate_tiles(
            shape, dtype, depth=depth, vmem_budget=self.vmem_budget,
            config=self.config, max_candidates=self.max_candidates,
        )
        if self.mode == "wallclock" and self._can_measure(backend):
            spec, score, source = self._rank_wallclock(cands, shape, dtype, backend)
        else:
            spec, score, source = self._rank_analytic(cands, shape, dtype)
        self.cache.put(key, CacheEntry(spec=spec, score=score, source=source),
                       persist=self.persist)
        return TuneResult(spec=spec, score=score, source=source,
                          from_cache=False, candidates=len(cands))

    def spec_for(self, shape: GemmShape, dtype="int8", *, backend: str = "pallas") -> TpuGemmSpec:
        return self.tune(shape, dtype, backend=backend).spec

    def warmup(
        self, shapes: Sequence[GemmShape], dtype="int8", *, backend: str = "pallas"
    ) -> List[TuneResult]:
        """Pre-tune a workload's shapes (e.g. a model's GeMMs before serving)."""
        return [self.tune(s, dtype, backend=backend) for s in shapes]

    # -- ranking strategies --------------------------------------------------

    def _rank_analytic(
        self, cands: Sequence[TpuGemmSpec], shape: GemmShape, dtype
    ) -> Tuple[TpuGemmSpec, float, str]:
        # `cands` is sorted by tile volume; strict `<` therefore breaks score
        # ties toward the smallest tile (least VMEM pressure), deterministically.
        best, best_clocks = None, float("inf")
        for spec in cands:
            clocks = tmodel.predict_clocks(spec, shape, dtype)
            if clocks < best_clocks:
                best, best_clocks = spec, clocks
        assert best is not None, "no legal tile candidates"
        return best, best_clocks, "analytic"

    def _can_measure(self, backend: str) -> bool:
        if backend == "interpret":
            return True
        import jax

        return jax.default_backend() == "tpu"

    def _rank_wallclock(
        self, cands: Sequence[TpuGemmSpec], shape: GemmShape, dtype, backend: str
    ) -> Tuple[TpuGemmSpec, float, str]:
        from repro.kernels.registry import make_kernel

        interpret = backend == "interpret"
        kb = _KERNEL_BACKEND.get(backend, backend)
        best, best_t = None, float("inf")
        failed = []
        for spec in cands:
            try:
                args = self._bench_args(kb, shape, dtype, spec)
                t = self._time_spec(
                    make_kernel(kb, spec, interpret=interpret), args)
            except Exception as e:  # refused or failed here: not a winner
                failed.append((f"tile=({spec.tm},{spec.tk},{spec.tn})", e))
                continue
            if t < best_t:
                best, best_t = spec, t
        if best is None:
            raise all_candidates_failed(f"{kb} {shape}", failed)
        return best, best_t, "wallclock"

    def _bench_args(self, kb: str, shape: GemmShape, dtype, spec: TpuGemmSpec):
        """Dummy operands for one candidate, pre-padded to its tile grid.

        The epilogue kernels take scale operands on top of A/B: "dequant"
        consumes int8 A/B plus row/column scales, "w8a8" consumes *float*
        activations (it quantizes them in-kernel) plus column scales.
        """
        import jax.numpy as jnp

        name = getattr(dtype, "name", str(dtype))
        pad = lambda v, t: v + (-v) % t
        M, K, N = pad(shape.M, spec.tm), pad(shape.K, spec.tk), pad(shape.N, spec.tn)
        if kb == "w8a8":
            return (
                jnp.zeros((M, K), jnp.float32),
                jnp.zeros((K, N), jnp.int8),
                jnp.ones((1, N), jnp.float32),
            )
        a = jnp.zeros((M, K), name)
        b = jnp.zeros((K, N), name)
        if kb == "dequant":
            return (a.astype(jnp.int8), b.astype(jnp.int8),
                    jnp.ones((M, 1), jnp.float32), jnp.ones((1, N), jnp.float32))
        return (a, b)

    def _time_spec(self, kernel, args) -> float:
        kernel(*args).block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(self.wallclock_iters):
            out = kernel(*args)
        out.block_until_ready()
        return (time.perf_counter() - t0) / self.wallclock_iters


def all_candidates_failed(what: str, failed) -> RuntimeError:
    """The error a wallclock search raises when no candidate ran: it names
    every candidate and why it failed, instead of handing back an untested
    analytic winner."""
    detail = "; ".join(f"{name}: {type(e).__name__}: {str(e)[:200]}"
                       for name, e in failed)
    return RuntimeError(
        f"wallclock tuning of {what}: all {len(failed)} candidates failed "
        f"({detail})")


# ---------------------------------------------------------------------------
# Process-wide default tuner + dispatch switch (consumed by kernels/ops.py)
# ---------------------------------------------------------------------------

_DEFAULT_TUNER: Optional[Autotuner] = None


def env_truthy(value: Optional[str]) -> bool:
    """Shared REPRO_AUTOTUNE parse: '0'/'false'/'no'/'' disable."""
    return (value or "").strip().lower() not in ("", "0", "false", "no", "off")


_ENABLED = env_truthy(os.environ.get("REPRO_AUTOTUNE"))


def get_tuner() -> Autotuner:
    global _DEFAULT_TUNER
    if _DEFAULT_TUNER is None:
        _DEFAULT_TUNER = Autotuner()
    return _DEFAULT_TUNER


def set_tuner(tuner: Optional[Autotuner]) -> None:
    global _DEFAULT_TUNER
    _DEFAULT_TUNER = tuner


def enable() -> None:
    """Route every spec-less `ops.gemm` call through the tuner."""
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def is_enabled() -> bool:
    return _ENABLED


def tuned_spec(shape: GemmShape, dtype="int8", *, backend: str = "pallas") -> TpuGemmSpec:
    """Best known spec for this problem via the default tuner."""
    return get_tuner().spec_for(shape, dtype, backend=backend)


def tuned_gemm(a, b, *, backend: Optional[str] = None, tuner: Optional[Autotuner] = None):
    """C = A @ B with the autotuned tile for (shape, dtype, backend).

    The generator-loop entry point: resolves the best `TpuGemmSpec` from the
    cache (tuning on first sight), then dispatches through `ops.gemm`.
    """
    from repro.kernels import ops

    resolved = ops._resolve(backend)
    if resolved == "xla":
        return ops.gemm(a, b, backend="xla")
    shape = GemmShape(a.shape[0], a.shape[1], b.shape[1])
    t = tuner or get_tuner()
    spec = t.spec_for(shape, a.dtype, backend=resolved)
    return ops.gemm(a, b, spec=spec, backend=resolved)
