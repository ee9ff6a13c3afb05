"""The open loop: the window opens after its warm-in, and a traced run
records a stretch in the window's middle, not its ramp."""

import types

import pytest

from benchlib import drive
from benchlib.traffic import Arrival


class FakeEngine:
    """Admits each request into a free slot and serves it one token per
    tick; a tick takes `tick_s` on the host clock."""

    def __init__(self, slots=2, tick_s=0.002):
        self.scheduler = types.SimpleNamespace(slots=[None] * slots,
                                               has_work=False)
        self.queue, self.tick_s, self.rid = [], tick_s, 0

    def submit(self, spec):
        self.rid += 1
        q = types.SimpleNamespace(rid=self.rid, prefilled=0, out_tokens=[],
                                  phase=types.SimpleNamespace(name="PREFILL"),
                                  prompt_len=len(spec[0]), max_new=spec[1])
        self.queue.append(q)
        self.scheduler.has_work = True
        return q

    def tick(self):
        drive.time.sleep(self.tick_s)
        slots = self.scheduler.slots
        for i, q in enumerate(slots):
            if q is None and self.queue:
                slots[i] = self.queue.pop(0)
        for i, q in enumerate(slots):
            if q is None:
                continue
            if q.prefilled < q.prompt_len:
                q.prefilled = q.prompt_len
                q.phase.name = "DECODE"
            q.out_tokens.append(1)
            if len(q.out_tokens) == q.max_new:
                q.phase.name = "FINISHED"
                slots[i] = None
        self.scheduler.has_work = bool(self.queue) or any(slots)


def test_trace_records_the_middle_of_the_window():
    marks = {}
    profiler = (lambda: marks.setdefault("start", drive.clock()),
                lambda: marks.setdefault("stop", drive.clock()))
    arrivals = [Arrival(t=0.0, prompt=[0] * 4, max_new=10_000)
                for _ in range(2)]
    warm, seconds, trace_s = 0.05, 0.4, 0.1
    win = drive.Driver(FakeEngine(), arrivals, lambda p, n: (p, n)).run(
        warm_in_s=warm, seconds=seconds, trace_s=trace_s, profiler=profiler)
    assert win.t_start == pytest.approx(win.t_origin + warm)
    assert win.t_end == pytest.approx(win.t_start + seconds)
    mid = win.t_start + (seconds - trace_s) / 2
    assert mid <= marks["start"] < mid + 0.02
    assert marks["stop"] - marks["start"] == pytest.approx(trace_s, abs=0.02)
    assert win.trace_start == pytest.approx(marks["start"], abs=1e-3)
    assert 0 < win.trace_tick0 < win.trace_tick1 < len(win.ticks)


def test_trace_starts_on_time_while_the_engine_is_empty():
    marks = {}
    profiler = (lambda: marks.setdefault("start", drive.clock()),
                lambda: marks.setdefault("stop", drive.clock()))
    arrivals = [Arrival(t=0.35, prompt=[0] * 4, max_new=5)]
    win = drive.Driver(FakeEngine(), arrivals, lambda p, n: (p, n)).run(
        warm_in_s=0.0, seconds=0.4, trace_s=0.2, profiler=profiler)
    assert marks["start"] - win.t_start == pytest.approx(0.1, abs=0.02)
    assert marks["stop"] - win.t_start == pytest.approx(0.3, abs=0.02)
