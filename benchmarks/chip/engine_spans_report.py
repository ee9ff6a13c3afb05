#!/usr/bin/env python3
"""Read the profile of a traced run by the engine's spans and the step
programs' layer-kind scopes (benchlib/engine_spans.py), and print one JSON
object: the host time before and after each step, the decode step split by
layer kind, and the idle gaps labelled by the innermost span, beside the
labels the harness's breakdown gives them.

    python3 benchmarks/chip/engine_spans_report.py .bench_trace/<cell> \
        [--fixture out.json]

With --fixture, also writes seven consecutive ticks (six decode ticks and
one prefill chunk) as a recording for the tests (testdata/).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import engine_spans as E, trace as T  # noqa: E402


def report(tr: E.Traced) -> dict:
    r = E.reduce(tr)
    _, a, b = [h for h in tr.host if h[0] == "bench.trace_window"][-1]
    ev = T.Events(ops=[(n, x, y, "") for n, x, y, _ in tr.ops],
                  programs=tr.programs, host=tr.host)
    before = T.reduce(ev, None)
    dec = [t for t in r.ticks if t.kind == "decode"]
    n = {k: sum(1 for t in r.ticks if t.kind == k)
         for k in ("decode", "prefill", "none")}
    steps = max(r.decode_steps, 1)
    after = {s: 1e3 * sum(t.after_by_span[s] for t in dec) / max(len(dec), 1)
             for s in E.AFTER}
    new = dict(r.idle_by_host)
    old = dict(before.idle_by_host)
    gap = "bench.tick after its last device op"
    spans = [h for h in T._inside(tr.host, a, b) if h[0] == "bench.tick"]
    dt = _skew(tr)
    busy = T.union(T.clip([(o[1] + dt, o[2] + dt) for o in tr.ops], a, b))
    host_per_tick = [((y - x) - T.overlap(busy, x, y)) * 1e-9
                     for _, x, y in spans]
    return {
        "ticks": n,
        "token_host_ms": E.token_host_ms(r),
        "token_host_ms_mean": (1e3 * sum(t.after_s for t in dec) / len(dec)
                               if dec else None),
        "host_prep_ms": E.host_prep_ms(r),
        "after_step_ms_per_decode_tick": after,
        "host_ms_per_tick": 1e3 * sum(host_per_tick) / max(len(spans), 1),
        "accounted_ms_per_tick": (
            1e3 * sum(t.after_s * (t.kind == "decode") + t.prep_s
                      for t in r.ticks) / max(len(r.ticks), 1)),
        "decode_steps": r.decode_steps,
        "decode_program_ms": 1e3 * r.decode_program_s / steps,
        "decode_ms_by_kind": {k or "unscoped": 1e3 * v / steps
                              for k, v in sorted(r.decode_by_kind.items())},
        "idle_gaps": r.idle_by_host,
        "idle_gaps_harness": before.idle_by_host,
        "after_last_op_relabelled": (
            1 - new.get(gap, 0.0) / old[gap] if old.get(gap) else None),
    }


def _skew(tr: E.Traced) -> float:
    _, a, b = [h for h in tr.host if h[0] == "bench.trace_window"][-1]
    ticks = [h for h in T._inside(tr.host, a, b) if h[0] == "bench.tick"]
    return T.device_skew(T.Events(ops=[], programs=tr.programs,
                                  host=tr.host), ticks)


def fixture(tr: E.Traced, name_chars: int = 60) -> E.Traced:
    """Seven consecutive ticks, six decode ticks and one prefill chunk, of
    the traced window, under a bench.trace_window of their own."""
    r = E.reduce(tr)
    _, a, b = [h for h in tr.host if h[0] == "bench.trace_window"][-1]
    eticks = [s for s in tr.engine if s[0] == "engine.tick"
              and s[1] >= a and s[2] <= b]
    kinds = [t.kind for t in r.ticks]
    for i in range(len(kinds) - 6):
        run = kinds[i:i + 7]
        if run.count("prefill") == 1 and run.count("decode") == 6:
            break
    else:
        raise SystemExit("no seven ticks with one chunk and six decodes")
    benches = [h for h in tr.host if h[0] == "bench.tick"]
    first = next(h for h in benches if h[1] <= eticks[i][1] <= h[2])
    last = next(h for h in benches if h[1] <= eticks[i + 6][1] <= h[2])
    lo, hi = first[1] - 1e5, last[2] + 1e5
    dt = _skew(tr)
    keep = lambda x, y: x < hi and y > lo                    # noqa: E731
    dev = lambda x, y: keep(x + dt, y + dt)                  # noqa: E731
    return E.Traced(
        host=[("bench.trace_window", lo, hi)]
        + [h for h in tr.host if h[0] != "bench.trace_window"
           and h[1] >= lo and h[2] <= hi],
        engine=[s for s in tr.engine if s[1] >= lo and s[2] <= hi],
        ops=[(n[:name_chars], x, y, k) for n, x, y, k in tr.ops
             if dev(x, y)],
        programs=[p for p in tr.programs if dev(p[1], p[2])])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    tr = E.load_dir(args.trace_dir)
    print(json.dumps(report(tr)))
    if args.fixture:
        with open(args.fixture, "w") as f:
            json.dump(fixture(tr).to_json(), f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
