"""The open loop around Engine.submit / Engine.tick, on the host clock.

Before each tick every request that has come due is submitted; after each
tick the live requests' slots, prefill progress and output lengths are
read, and admissions and new tokens are stamped.  With no work in the
engine the loop sleeps until the next request is due.  Each tick is logged
with what it ran (a prefill chunk, or a decode over the active contexts),
for the per-layer readers.

Spans on the profiler's clock (jax.profiler.TraceAnnotation):
bench.submit, bench.tick, bench.poll, bench.wait_arrival, and
bench.trace_window around the part of the window a traced run records
(a stretch in its middle, where the engine holds its steady load).
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

from jax.profiler import TraceAnnotation

clock = time.perf_counter


@dataclasses.dataclass
class Record:
    """One request as the client sees it (times on the host clock)."""

    idx: int
    due: float
    prompt: object
    max_new: int
    req: object = None            # the engine's Request once submitted
    submitted_t: Optional[float] = None
    admitted_t: Optional[float] = None
    finished_t: Optional[float] = None
    token_t: List[float] = dataclasses.field(default_factory=list)
    prefilled: int = 0
    n_out: int = 0

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    kind: str                     # "prefill" | "decode" | "none"
    chunk: int = 0                # prefill: tokens, from position `start`
    start: int = 0
    contexts: tuple = ()          # decode: each active row's context


@dataclasses.dataclass
class Window:
    records: List[Record]
    ticks: List[Tick]
    t_origin: float
    t_start: float
    t_end: float
    trace_start: Optional[float] = None
    trace_tick0: Optional[int] = None   # first tick begun while tracing
    trace_tick1: Optional[int] = None   # first tick begun after it


class Driver:
    """Drives one engine through one run's traffic."""

    def __init__(self, engine, arrivals, make_spec):
        self.engine = engine
        self.make_spec = make_spec
        self.records: List[Record] = []
        self.by_rid = {}
        self.live: List[Record] = []
        self.ticks: List[Tick] = []
        self._arrivals = arrivals

    def _submit_due(self, now: float) -> None:
        recs, i = self.records, self._next
        with TraceAnnotation("bench.submit"):
            while i < len(recs) and recs[i].due <= now:
                r = recs[i]
                r.req = self.engine.submit(self.make_spec(r.prompt, r.max_new))
                if r.req is None:
                    raise RuntimeError(f"request {r.idx} was refused")
                r.submitted_t = clock()
                self.by_rid[r.req.rid] = r
                i += 1
        self._next = i

    def _poll(self, t0: float, t1: float) -> None:
        seen = {id(r): r for r in self.live}
        for q in self.engine.scheduler.slots:
            if q is not None:
                r = self.by_rid[q.rid]
                seen.setdefault(id(r), r)
        chunk = start = 0
        contexts = []
        live = []
        for r in seen.values():
            q = r.req
            if r.admitted_t is None:
                r.admitted_t = t1
            pre, n = q.prefilled, len(q.out_tokens)
            if pre > r.prefilled:
                chunk, start = pre - r.prefilled, r.prefilled
            elif n > r.n_out:
                contexts.append(r.prefilled + r.n_out)
            r.token_t.extend([t1] * (n - r.n_out))
            r.prefilled, r.n_out = pre, n
            if q.phase.name == "FINISHED":
                r.finished_t = t1
            else:
                live.append(r)
        self.live = live
        if chunk:
            self.ticks.append(Tick(t0, t1, "prefill", chunk=chunk, start=start))
        elif contexts:
            self.ticks.append(Tick(t0, t1, "decode", contexts=tuple(contexts)))
        else:
            self.ticks.append(Tick(t0, t1, "none"))

    def run(self, *, warm_in_s: float, seconds: float, trace_s: float = 0.0,
            profiler=None, on_start=None) -> Window:
        """Drive the traffic.  The window opens `warm_in_s` after the
        traffic starts and lasts `seconds`.  With `profiler` (start, stop
        callables), `trace_s` seconds in the middle of the window are
        recorded: the slots have filled by then, and the traffic files fix
        their arrival order, so every seed has the same work there.
        `on_start()` is called as the window opens."""
        t_origin = clock()
        self.records = [Record(idx=i, due=t_origin + a.t, prompt=a.prompt,
                               max_new=a.max_new)
                        for i, a in enumerate(self._arrivals)]
        self.records.sort(key=lambda r: r.due)
        self._next = 0
        sched = self.engine.scheduler
        t_start = t_origin + warm_in_s
        t_end = t_start + seconds
        t_trace = t_start + max(0.0, seconds - trace_s) / 2
        trace_start = trace_tick0 = trace_tick1 = None
        span = None
        started = tracing = False

        def stop_trace():
            nonlocal tracing, trace_tick1
            span.__exit__(None, None, None)
            profiler[1]()
            tracing, trace_tick1 = False, len(self.ticks)

        try:
            while True:
                now = clock()
                if not started and now >= t_start:
                    started = True
                    if on_start is not None:
                        on_start()
                if profiler and trace_start is None and now >= t_trace:
                    profiler[0]()
                    span = TraceAnnotation("bench.trace_window")
                    span.__enter__()
                    tracing = True
                    trace_start, trace_tick0 = clock(), len(self.ticks)
                if tracing and now >= trace_start + trace_s:
                    stop_trace()
                if now >= t_end:
                    break
                if self._next < len(self.records) and \
                        self.records[self._next].due <= now:
                    self._submit_due(now)
                if not sched.has_work:
                    nxt = (self.records[self._next].due
                           if self._next < len(self.records) else None)
                    wake = min(x for x in (
                        nxt, t_end, None if started else t_start,
                        t_trace if profiler and trace_start is None else None,
                        trace_start + trace_s if tracing else None)
                        if x is not None)
                    with TraceAnnotation("bench.wait_arrival"):
                        time.sleep(max(0.0, wake - clock()))
                    continue
                t0 = clock()
                with TraceAnnotation("bench.tick"):
                    self.engine.tick()
                t1 = clock()
                with TraceAnnotation("bench.poll"):
                    self._poll(t0, t1)
        finally:
            if tracing:
                stop_trace()
        return Window(records=self.records, ticks=self.ticks,
                      t_origin=t_origin, t_start=t_start, t_end=t_end,
                      trace_start=trace_start, trace_tick0=trace_tick0,
                      trace_tick1=trace_tick1)
