#!/usr/bin/env python3
"""Find a Poisson cell's knee, and read its seed-to-seed spread, in one
process: one warmed engine's compiled steps drive a fresh engine for each
pair of rate and seed.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds 30 \\
        --rates 0.2,0.3,0.4 --seeds 1,2

For each pair the cell's traffic file is read with `rate_per_s` replaced
(and with --schedule-seed, its order fixed), the window is driven as in a
run, and one JSON line reports the window's TTFT and inter-token tails,
output tokens per second, and the backlog: requests due in the window that
had no first token at its close, and the TTFT medians of requests due in
the window's first and second halves.  A rate the engine sustains ends
with a backlog near zero and like halves; above the knee the backlog grows
through the window.  Run it on the chip; the knee it finds is written into
the traffic file by hand (PERF.md).
"""

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from benchlib import cells, drive, harness, readings, stats  # noqa: E402


def _floats(s):
    return [float(x) for x in s.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Rate sweep of one Poisson cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--schedule-seed", type=int, default=None)
    args = ap.parse_args(argv)

    bench = cells.benchmark()
    cell = cells.workload(args.workload, bench)
    try:
        harness.check_device(int(cell["chips"]))
    except harness.NoChip as e:
        harness.log(f"sweep: {e}")
        return 2
    from benchlib import system

    cache_dir = os.path.join(cells.ROOT, ".jax_cache")
    system.compile_cache(cache_dir)
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(cache_dir, "tunecache.json")
    conf = cells.load_config(cell["config"])
    spec = cells.load_traffic(cell["traffic"])
    if args.schedule_seed is not None:
        spec = dict(spec, schedule_seed=args.schedule_seed)
    cfg = conf.arch.program_config(conf)
    seeds = [int(s) for s in args.seeds.split(",")]
    params = system.make_params(seeds[0], conf, cfg)
    warm = system.make_engine(conf, cfg, params)
    warm.warmup()
    steps = set(warm._warmed)
    warm.state = None             # one KV pool on the chip at a time
    gc.collect()
    kind = cells.traffic_kind(spec["kind"])
    for rate in _floats(args.rates):
        for seed in seeds:
            engine = system.make_engine(conf, cfg, params)
            engine.share_steps_from(warm)
            engine._warmed = set(steps)
            s = dict(spec, rate_per_s=rate)
            arrivals = kind.generate(s, seed, args.seconds, conf.dims.vocab)
            win = drive.Driver(engine, arrivals, system.request_spec).run(
                warm_in_s=float(s["warm_in_s"]), seconds=args.seconds)
            ctx = harness.Context(cell=cell, arch=conf.arch, dims=conf.dims,
                                  window=win, peaks=None, setup_s=0.0)
            mid = (win.t_start + win.t_end) / 2
            due = [r for r in win.records if win.t_start <= r.due < win.t_end]
            half = [stats.percentile(stats.ttft_samples(win.records, a, b), 50)
                    for a, b in ((win.t_start, mid), (mid, win.t_end))]
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "due": len(due),
                "waiting_at_close": sum(1 for r in due if not r.token_t),
                "ttft_p50_ms_halves": [None if h is None else h * 1e3
                                       for h in half],
                "ttft_p50_ms": readings.ttft_ms(ctx, 50),
                "ttft_p90_ms": readings.ttft_ms(ctx, 90),
                "itl_p95_ms": readings.itl_ms(ctx, 95),
                "queue_wait_p90_ms": readings.queue_wait_ms(ctx, 90),
                "out_tok_s": readings.out_tok_s(ctx),
                "ticks": len(win.ticks),
                "cold_compiles": engine.metrics.cold_compiles}), flush=True)
            del engine, win, ctx
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
