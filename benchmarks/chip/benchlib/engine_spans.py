"""The engine's own spans and the layer kind of each device operation, read
from the profiler's trace of a traced run.

The program writes each part of `Engine.tick` into the profiler's trace
(engine.tick, .admit, .schedule, .stage, .dispatch, .readback, .pick,
.commit; repro.obs.trace), the dispatch span carrying what the step ran
(`rows`, `ctx_tokens` of a decode step; `chunk`, `start` of a prefill
chunk).  Its step programs run each layer kind under a `jax.named_scope`
(embed, attn, mamba, mlstm, slstm, ffn, moe, unembed), which reaches each
device operation as its op_name (op_names).  Two stages, as in trace.py:

  load_dir(dir)   -> Traced      bench.* and engine.* host spans, device
                                 operations with their layer kind, and step
                                 programs, on the profiler's clock (ns)
  reduce(traced)  -> Reading     per tick, the host time under the spans
                                 before and after the step; per decode
                                 step, device time by layer kind; idle
                                 gaps labelled by the innermost span

A trace of a program without these spans or scopes reads as None (no
ticks, no scoped operation), never as 0.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import statistics
from typing import Dict, List, Optional, Tuple

from benchlib import trace as T

ENGINE_PREFIX = "engine."
KINDS = ("embed", "attn", "mamba", "mlstm", "slstm", "ffn", "moe", "unembed")
# Host work before the device step, and after it.
PREP = ("engine.admit", "engine.schedule", "engine.stage", "engine.dispatch")
AFTER = ("engine.readback", "engine.pick", "engine.commit")
DECODE_PROGRAM = next(k for k, v in T.PROGRAMS.items() if v == "decode")

Span = T.Span
EngineSpan = Tuple[str, float, float, dict]   # name, start, end, metadata
Op = Tuple[str, float, float, str]            # name, start, end, layer kind

def kind_of(op_name: str) -> str:
    """The layer kind in an operation's op_name
    ("jit(paged_serve_step)/while/body/closed_call/attn/pallas_call:" ->
    "attn"): the innermost path segment that is a kind, "" where none is."""
    segs = [s for s in re.split(r"[/:]", op_name) if s in KINDS]
    return segs[-1] if segs else ""


@dataclasses.dataclass
class Traced:
    host: List[Span]               # bench.* spans
    engine: List[EngineSpan]       # engine.* spans
    ops: List[Op]
    programs: List[Span]

    @classmethod
    def from_json(cls, d: dict) -> "Traced":
        """A recording as JSON lists (testdata/)."""
        return cls(host=[tuple(x) for x in d["host"]],
                   engine=[(n, a, b, dict(m)) for n, a, b, m in d["engine"]],
                   ops=[tuple(x) for x in d["ops"]],
                   programs=[tuple(x) for x in d["programs"]])

    def to_json(self) -> dict:
        return {"host": [list(x) for x in self.host],
                "engine": [list(x) for x in self.engine],
                "ops": [list(x) for x in self.ops],
                "programs": [list(x) for x in self.programs]}


def _fields(buf: bytes):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            c = buf[i]
            i += 1
            key |= (c & 0x7F) << shift
            shift += 7
            if c < 0x80:
                break
        wire = key & 7
        if wire in (0, 2):
            v = shift = 0
            while True:
                c = buf[i]
                i += 1
                v |= (c & 0x7F) << shift
                shift += 7
                if c < 0x80:
                    break
            if wire == 2:
                v, i = buf[i:i + v], i + v
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def op_names(path: str, plane: str) -> Dict[str, str]:
    """The op_name of each device operation of `plane`, by the operation's
    name, from the .xplane.pb at `path`.  The TPU's profile keeps it as the
    stat "tf_op" of each operation's event metadata, which ProfileData's
    events do not show.  (XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5, map entries key 1 value 2;
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7.)"""
    with open(path, "rb") as f:
        space = f.read()
    for field, raw in _fields(space):
        if field != 1:
            continue
        parts = collections.defaultdict(list)
        for k, v in _fields(raw):
            if k in (2, 4, 5):
                parts[k].append(v)
        if parts[2] != [plane.encode()]:
            continue
        stat_names = {}
        for entry in parts[5]:
            md = dict(_fields(dict(_fields(entry)).get(2, b"")))
            stat_names[md.get(1, 0)] = md.get(2, b"").decode()
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        out = {}
        for entry in parts[4]:
            name, value = "", ""
            for k, v in _fields(dict(_fields(entry)).get(2, b"")):
                if k == 2:
                    name = v.decode(errors="replace")
                elif k == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op:
                        value = (stat[5].decode(errors="replace") if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if value:
                out[name] = value
        return out
    return {}


def load_dir(trace_dir: str, device: int = 0) -> Traced:
    """Read the one .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    pd = ProfileData.from_file(paths[0])
    host, engine, ops, programs = [], [], [], []
    dev_plane = f"/device:TPU:{device}"
    kinds = {name: kind_of(op)
             for name, op in op_names(paths[0], dev_plane).items()}
    for plane in pd.planes:
        if plane.name == dev_plane:
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                kinds.get(e.name, "")) for e in line.events)
                elif line.name == T.PROGRAMS_LINE:
                    programs.extend((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(T.HOST_PREFIX):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
                    elif e.name.startswith(ENGINE_PREFIX):
                        engine.append((e.name, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       {k: v for k, v in e.stats}))
    key = lambda x: (x[1], -x[2])                      # noqa: E731
    return Traced(host=sorted(host, key=key), engine=sorted(engine, key=key),
                  ops=sorted(ops, key=key), programs=sorted(programs, key=key))


@dataclasses.dataclass
class Tick:
    kind: str                      # "decode" | "prefill" | "none"
    meta: dict                     # the dispatch span's metadata
    prep_s: float                  # idle device under PREP spans
    after_s: float                 # idle device under AFTER spans
    after_by_span: Dict[str, float]


@dataclasses.dataclass
class Reading:
    ticks: List[Tick]
    decode_steps: int              # decode programs in the window
    decode_program_s: float        # their device time
    decode_by_kind: Dict[str, float]   # leaf device seconds; "" unscoped
    idle_by_host: List[Tuple[str, float]]


def _host_idle(busy, spans) -> float:
    """Seconds of the union of `spans` with no device operation running."""
    u = T.union((x, y) for _, x, y, *_ in spans)
    return sum((y - x) - T.overlap(busy, x, y) for x, y in u) * 1e-9


def leaf_time(ops: List[Op], lo: float, hi: float) -> Dict[str, float]:
    """Device ns in [lo, hi] by layer kind, each instant counted once, for
    the innermost operation running then (the layer loop's `while`
    contains its body's operations; its own share is the time in which no
    operation of the body runs).  `ops` sorted by start."""
    out: Dict[str, float] = collections.Counter()
    stack: List[Tuple[float, str]] = []     # (end, kind), innermost last
    cur = lo

    def pop_until(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, kind = stack.pop()
            if end > cur:
                out[kind] += end - cur
                cur = end

    for _, s, e, kind in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        pop_until(s)
        if stack and s > cur:
            out[stack[-1][1]] += s - cur
        cur = max(cur, s)
        stack.append((e, kind))
    pop_until(float("inf"))
    return out


def reduce(tr: Traced) -> Reading:
    """Read the last bench.trace_window of `tr`."""
    wins = [h for h in tr.host if h[0] == "bench.trace_window"]
    if not wins:
        raise RuntimeError("the trace holds no bench.trace_window span")
    _, a, b = wins[-1]
    bench_ticks = [h for h in T._inside(tr.host, a, b) if h[0] == "bench.tick"]
    dt = T.device_skew(T.Events(ops=[], programs=tr.programs, host=tr.host),
                       bench_ticks)
    ops = [(n, x + dt, y + dt, k) for n, x, y, k in tr.ops]
    programs = [(n, x + dt, y + dt) for n, x, y in tr.programs]
    busy = T.union(T.clip([(o[1], o[2]) for o in ops], a, b))

    engine = [s for s in tr.engine if s[1] >= a and s[2] <= b]
    starts = [s[1] for s in engine]
    ticks = []
    for _, x, y, _ in (s for s in engine if s[0] == "engine.tick"):
        inner = engine[bisect.bisect_left(starts, x):
                       bisect.bisect_right(starts, y)]
        inner = [s for s in inner if s[2] <= y and s[0] != "engine.tick"]
        meta = next((s[3] for s in inner if s[0] == "engine.dispatch"), {})
        kind = ("decode" if "rows" in meta else
                "prefill" if "chunk" in meta else "none")
        after = {n: _host_idle(busy, [s for s in inner if s[0] == n])
                 for n in AFTER}
        ticks.append(Tick(
            kind=kind, meta=meta,
            prep_s=_host_idle(busy, [s for s in inner if s[0] in PREP]),
            after_s=_host_idle(busy, [s for s in inner if s[0] in AFTER]),
            after_by_span=after))

    by_kind: Dict[str, float] = collections.Counter()
    steps, prog_ns = 0, 0.0
    for name, px, py in programs:
        if DECODE_PROGRAM not in name or px < a or py > b:
            continue
        steps += 1
        prog_ns += py - px
        for k, v in leaf_time(T._inside(ops, px, py), px, py).items():
            by_kind[k] += v * 1e-9
    # Inside a bench.tick, the innermost engine.* span names an idle gap.
    host = sorted(tr.host + [s[:3] for s in engine], key=lambda h: h[1])
    return Reading(ticks=ticks, decode_steps=steps,
                   decode_program_s=prog_ns * 1e-9,
                   decode_by_kind=dict(by_kind),
                   idle_by_host=T.idle_by_host(busy, host, a, b))


def token_host_ms(r: Reading) -> Optional[float]:
    """Median over decode ticks of the idle device time under the spans
    after the step (readback, pick, commit).  The median, not the mean:
    starting the profiler stalls one tick's readback for up to seconds
    (2.5 s in a traced chat run), which would carry a mean ten times over."""
    ts = [t.after_s for t in r.ticks if t.kind == "decode"]
    return 1e3 * statistics.median(ts) if ts else None


def host_prep_ms(r: Reading) -> Optional[float]:
    """Mean over all ticks of the idle device time under the spans before
    the step (admit, schedule, stage, dispatch)."""
    ts = r.ticks
    return 1e3 * sum(t.prep_s for t in ts) / len(ts) if ts else None


def decode_kind_ms(r: Reading, kind: str) -> Optional[float]:
    """Device ms per decode step in leaf operations of layer kind `kind`
    ("" for those under no kind).  None where no operation carries a kind:
    a program without the scopes."""
    if not r.decode_steps or not any(r.decode_by_kind.get(k) for k in KINDS):
        return None
    return 1e3 * r.decode_by_kind.get(kind, 0.0) / r.decode_steps


_CACHE: Dict[str, Tuple[float, Reading]] = {}


def reading(ctx) -> Optional[Reading]:
    """The reading of a traced run's profile, for the metric readers: the
    harness keeps the profile under .bench_trace/<cell> of the checkout.
    None in an untraced run, or where the trace holds no engine span and no
    scoped operation."""
    if getattr(ctx, "trace", None) is None:
        return None
    from benchlib import cells

    trace_dir = os.path.join(cells.ROOT, ".bench_trace", ctx.cell["name"])
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        return None
    stamp = os.path.getmtime(paths[0])
    hit = _CACHE.get(paths[0])
    if hit is None or hit[0] != stamp:
        tr = load_dir(trace_dir)
        r = (reduce(tr) if tr.engine or any(o[3] for o in tr.ops) else None)
        hit = _CACHE[paths[0]] = (stamp, r)
    return hit[1]
