"""From the profiler's trace to busy time, idle gaps and per-step kernel
time.

Two stages, so the second can be checked on a recorded trace:

  load_dir(dir)  -> Events     the device's operations and program runs and
                               the harness's bench.* host spans, all on the
                               profiler's clock (ns)
  reduce(events, window)       -> Reduction, matched tick by tick to the
                               driver's log of what each tick ran

Busy time is the union of the intervals in which a device operation runs;
idle share is 1 - busy / window.  A kernel is known by its operands
(kernel_of).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import List, Tuple

Span = Tuple[str, float, float]           # name, start ns, end ns

# Step programs by the name of the jitted function the engine runs.
PROGRAMS = {"prefill_chunk_step": "prefill", "paged_serve_step": "decode"}
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Events:
    ops: List[Tuple[str, float, float, str]]   # name, start, end, kernel
    programs: List[Span]
    host: List[Span]

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        """Events recorded as JSON lists (testdata/)."""
        return cls(ops=[tuple(x) for x in d["ops"]],
                   programs=[tuple(x) for x in d["programs"]],
                   host=[tuple(x) for x in d["host"]])


_SHAPE = re.compile(r"\b(?:bf16|f16|f32|s8|s32|u32|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def kernel_of(hlo: str) -> str:
    """'gemm' or 'flash_decode' for an operation of the two Pallas kernels,
    else ''.  The trace names each operation by its HLO instruction, which
    does not carry the kernel's name, so a kernel is known by its operands:
    the flash-decode kernel reads the K and V pools, two operands of one
    rank-4 shape; the GeMM reads two matrices (the int8 GeMM, two matrices
    and their two scale vectors) into one matrix."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return ""
    head, _, rest = hlo.partition(" custom-call(")
    args = rest.split("), custom_call_target", 1)[0]
    ins = [tuple(x.split(",")) for x in _SHAPE.findall(args)]
    outs = _SHAPE.findall(head)
    rank4 = [s for s in ins if len(s) == 4]
    if len(rank4) >= 3 and rank4[-1] == rank4[-2]:
        return "flash_decode"
    if len(outs) == 1 and len(outs[0].split(",")) == 2 \
            and len(ins) in (2, 4) and all(len(s) == 2 for s in ins):
        return "gemm"
    return ""


def load_dir(trace_dir: str, device: int = 0) -> Events:
    """Read the one .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    pd = ProfileData.from_file(paths[0])
    ops, programs, host = [], [], []
    dev_plane = f"/device:TPU:{device}"
    for plane in pd.planes:
        if plane.name == dev_plane:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                kernel_of(e.name)) for e in line.events)
                elif line.name == PROGRAMS_LINE:
                    programs.extend((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    if not ops:
        raise RuntimeError(f"no device operations on {dev_plane} in the trace")
    return Events(ops=sorted(ops, key=lambda x: x[1]),
                  programs=sorted(programs, key=lambda x: x[1]),
                  host=sorted(host, key=lambda x: x[1]))


def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(merged, a: float, b: float) -> float:
    """Length of [a, b] covered by the sorted disjoint `merged`."""
    tot = 0.0
    i = max(0, bisect.bisect_right(merged, (a, float("inf"))) - 1)
    for x, y in merged[i:]:
        if x >= b:
            break
        if y > a:
            tot += min(y, b) - max(x, a)
    return tot


def clip(intervals, a: float, b: float):
    return [(max(x, a), min(y, b)) for x, y in intervals if y > a and x < b]


@dataclasses.dataclass
class TracedTick:
    tick: object                  # drive.Tick
    span_s: float
    busy_s: float
    program_s: float              # device time of the step program
    gemm_s: float
    decode_kernel_s: float


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    wait_s: float
    busy_in_wait_s: float
    ticks: List[TracedTick]
    top_ops: List[Tuple[str, float]]
    idle_by_host: List[Tuple[str, float]]

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops[:10]],
                "idle_gaps": [list(x) for x in self.idle_by_host[:10]]}


def _inside(spans, a, b):
    """The spans (sorted by start) that lie within [a, b]."""
    i = bisect.bisect_left(spans, a, key=lambda s: s[1])
    out = []
    for s in spans[i:]:
        if s[1] > b:
            break
        if s[2] <= b:
            out.append(s)
    return out


def _step_programs(ev: Events, ticks: List[Span]):
    """Each tick's step programs, by the tick span each overlaps most."""
    out = [[] for _ in ticks]
    starts = [t[1] for t in ticks]
    for p in ev.programs:
        if not any(k in p[0] for k in PROGRAMS):
            continue
        i = bisect.bisect_right(starts, p[1])
        best, most = None, 0.0
        for j in (i - 1, i):
            if 0 <= j < len(ticks):
                ov = min(p[2], ticks[j][2]) - max(p[1], ticks[j][1])
                if ov > most:
                    best, most = j, ov
        if best is not None:
            out[best].append(p)
    return out


def device_skew(ev: Events, ticks: List[Span]) -> float:
    """How far (ns) the device's clock reads behind the host's.  A tick
    dispatches its step, so no step starts before its tick; the largest
    lead of a step over its tick is the skew, and device times are shifted
    by it."""
    lead = [t[1] - p[1] for t, ps in zip(ticks, _step_programs(ev, ticks))
            for p in ps]
    return max([0.0] + lead)


def _shift(ev: Events, dt: float) -> Events:
    return Events(ops=[(n, x + dt, y + dt, k) for n, x, y, k in ev.ops],
                  programs=[(n, x + dt, y + dt) for n, x, y in ev.programs],
                  host=ev.host)


def reduce(ev: Events, window) -> Reduction:
    """Reduce the traced part of `window` (a drive.Window, or None to skip
    matching ticks to the open loop's tick log)."""
    wins = [h for h in ev.host if h[0] == "bench.trace_window"]
    if not wins:
        raise RuntimeError("the trace holds no bench.trace_window span")
    _, a, b = wins[-1]
    spans = [h for h in _inside(ev.host, a, b) if h[0] == "bench.tick"]
    ev = _shift(ev, device_skew(ev, spans))
    ns = 1e-9
    busy = union(clip([(o[1], o[2]) for o in ev.ops], a, b))
    waits = union(clip([(h[1], h[2]) for h in ev.host
                        if h[0] == "bench.wait_arrival"], a, b))
    busy_s = sum(y - x for x, y in busy) * ns
    wait_s = sum(y - x for x, y in waits) * ns
    busy_in_wait = sum(overlap(busy, x, y) for x, y in waits) * ns

    ticks = []
    if window is not None:
        logged = window.ticks[window.trace_tick0:window.trace_tick1]
        if len(spans) != len(logged):
            raise RuntimeError(f"{len(spans)} traced ticks, {len(logged)} "
                               f"logged")
        for (_, x, y), tick, progs in zip(spans, logged,
                                          _step_programs(ev, spans)):
            gemm = dec = 0.0
            for _, px, py in progs:
                for name, ox, oy, kern in _inside(ev.ops, px, py):
                    if kern == "gemm":
                        gemm += oy - ox
                    elif kern == "flash_decode":
                        dec += oy - ox
            ticks.append(TracedTick(
                tick=tick, span_s=(y - x) * ns,
                busy_s=overlap(busy, x, y) * ns,
                program_s=sum(py - px for _, px, py in progs) * ns,
                gemm_s=gemm * ns, decode_kernel_s=dec * ns))

    per_op = collections.Counter()
    for name, x, y, _ in ev.ops:
        if x >= a and y <= b:
            per_op[name] += (y - x) * ns
    return Reduction(window_s=(b - a) * ns, busy_s=busy_s, wait_s=wait_s,
                     busy_in_wait_s=busy_in_wait,
                     ticks=ticks, top_ops=per_op.most_common(10),
                     idle_by_host=idle_by_host(busy, ev.host, a, b))


def _tick_part(busy, tick: Span, x: float, y: float) -> str:
    """Where in its tick the idle stretch [x, y] lies."""
    earlier = overlap(busy, tick[1], x) > 0
    later = overlap(busy, y, tick[2]) > 0
    if later and not earlier:
        return "bench.tick before its first device op"
    if earlier and not later:
        return "bench.tick after its last device op"
    return "bench.tick between device ops"


def idle_by_host(busy, host, a: float, b: float) -> List[Tuple[str, float]]:
    """Idle seconds in [a, b], summed by the innermost bench.* span open
    during each stretch of idle time; a tick's idle time is split into
    before its first device operation, between, and after its last."""
    gaps, t = [], a
    for x, y in busy:
        if x > t:
            gaps.append((t, min(x, b)))
        t = max(t, y)
    if t < b:
        gaps.append((t, b))
    spans = [h for h in host if h[2] > a and h[1] < b
             and h[0] != "bench.trace_window"]
    points = sorted({a, b} | {max(a, min(b, v)) for h in spans
                              for v in (h[1], h[2])})
    bounds = sorted([(h[1], 1, i) for i, h in enumerate(spans)]
                    + [(h[2], 0, i) for i, h in enumerate(spans)])
    out = collections.Counter()
    active, j, g = set(), 0, 0
    for x, y in zip(points, points[1:]):
        while j < len(bounds) and bounds[j][0] <= x:
            _, is_start, i = bounds[j]
            (active.add if is_start else active.discard)(i)
            j += 1
        inner = min(active, key=lambda i: spans[i][2] - spans[i][1],
                    default=None)
        while g < len(gaps) and gaps[g][1] <= x:
            g += 1
        k = g
        while k < len(gaps) and gaps[k][0] < y:
            lo, hi = max(x, gaps[k][0]), min(y, gaps[k][1])
            if hi > lo:
                if inner is None:
                    label = "outside bench spans"
                elif spans[inner][0] == "bench.tick":
                    label = _tick_part(busy, spans[inner], lo, hi)
                else:
                    label = spans[inner][0]
                out[label] += (hi - lo) * 1e-9
            k += 1
    return out.most_common()
