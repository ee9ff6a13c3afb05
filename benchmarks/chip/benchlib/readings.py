"""The arithmetic behind the metric readers (metrics/<name>.py).

Each function takes the run's Context and returns a number, or None where
the run has nothing to read (no traced step of that kind, no request due):
a share of a peak or a roofline is never reported as 0 for want of data.
The work counts are the configuration's architecture module's (ctx.arch).
"""

from __future__ import annotations

from typing import Optional

from benchlib import stats, work


def ttft_ms(ctx, q: float) -> Optional[float]:
    w = ctx.window
    v = stats.percentile(stats.ttft_samples(w.records, w.t_start, w.t_end), q)
    return None if v is None else v * 1e3


def itl_ms(ctx, q: float) -> Optional[float]:
    w = ctx.window
    v = stats.percentile(stats.itl_samples(w.records, w.t_start, w.t_end), q)
    return None if v is None else v * 1e3


def queue_wait_ms(ctx, q: float) -> Optional[float]:
    w = ctx.window
    v = stats.percentile(
        stats.queue_wait_samples(w.records, w.t_start, w.t_end), q)
    return None if v is None else v * 1e3


def out_tok_s(ctx) -> float:
    w = ctx.window
    return stats.tokens_in(w.records, w.t_start, w.t_end) / (w.t_end - w.t_start)


def device_idle_pct(ctx) -> Optional[float]:
    tr = ctx.trace
    if tr is None:
        return None
    span = tr.window_s - tr.wait_s
    if span <= 0:
        return None
    return 100.0 * (1.0 - (tr.busy_s - tr.busy_in_wait_s) / span)


def host_ms_per_tick(ctx) -> Optional[float]:
    tr = ctx.trace
    if tr is None or not tr.ticks:
        return None
    host = [t.span_s - t.busy_s for t in tr.ticks]
    return 1e3 * sum(host) / len(host)


def _ticks(ctx, kind):
    tr = ctx.trace
    if tr is None:
        return []
    return [t for t in tr.ticks if t.tick.kind == kind and t.program_s > 0]


def prefill_mfu(ctx) -> Optional[float]:
    ts = _ticks(ctx, "prefill")
    if not ts:
        return None
    flops = sum(ctx.arch.prefill_chunk_flops(ctx.dims, t.tick.start,
                                             t.tick.chunk) for t in ts)
    return 100.0 * flops / (sum(t.program_s for t in ts) * ctx.peaks.flops)


def decode_mfu(ctx) -> Optional[float]:
    ts = _ticks(ctx, "decode")
    if not ts:
        return None
    flops = sum(ctx.arch.decode_flops(ctx.dims, t.tick.contexts) for t in ts)
    return 100.0 * flops / (sum(t.program_s for t in ts) * ctx.peaks.flops)


def _step_gemms(ctx, tick):
    if tick.kind == "prefill":
        return ctx.arch.step_gemms(ctx.dims, tick.chunk, 1)
    rows = len(tick.contexts)
    return ctx.arch.step_gemms(ctx.dims, rows, rows)


def gemm_roofline(ctx, kinds) -> Optional[float]:
    ts = [t for k in kinds for t in _ticks(ctx, k) if t.gemm_s > 0]
    if not ts:
        return None
    least = sum(work.gemms_least_s(_step_gemms(ctx, t.tick), ctx.peaks)
                for t in ts)
    return 100.0 * least / sum(t.gemm_s for t in ts)


def flash_decode_roofline(ctx) -> Optional[float]:
    ts = [t for t in _ticks(ctx, "decode") if t.decode_kernel_s > 0]
    if not ts:
        return None
    least = sum(ctx.arch.decode_attn_least_s(ctx.dims, t.tick.contexts,
                                             ctx.peaks) for t in ts)
    return 100.0 * least / sum(t.decode_kernel_s for t in ts)
