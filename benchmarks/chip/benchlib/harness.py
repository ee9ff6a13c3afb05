"""One run of one cell: set-up, the measured window, the traced reading,
the comparison with the reference, and the result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Standard output's last line is one JSON object (correct, attempted, failed,
metrics, device[, breakdown], check).  Lines before it, on standard error,
give set-up by phase, how late the generator ran, compiles in the window,
and last each compared number beside its limit.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import Optional

import numpy as np

from benchlib import cells, drive, work

TRACE_SECONDS = 8.0
CHECK_TOKENS = 512          # served tokens the reference compares, at least


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_age_s() -> Optional[float]:
    """Seconds since this process started (Linux), or None."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


@dataclasses.dataclass
class Context:
    """What the metric readers (metrics/<name>.py) read."""

    cell: dict
    arch: object                  # the configuration's arch/*.py module
    dims: object                  # its arch.dims(raw)
    window: drive.Window
    peaks: work.Peaks
    setup_s: float
    trace: object = None          # benchlib.trace.Reduction in a traced run


def check_device(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


class CompileCounter:
    """Counts XLA backend compiles in this process, and the persistent
    compilation cache's hits and misses (jax.monitoring)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE = {"/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax

        self.n = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1

    def _on_event(self, event, **kw):
        name = self.CACHE.get(event)
        if name is not None:
            setattr(self, name, getattr(self, name) + 1)


def sample_for_check(window: drive.Window, seed: int, target: int) -> list:
    """Requests for the reference, each with every token served by the
    close: the finished request with the most tokens (prompt and served),
    or where none finished the served request with the most, then others
    drawn from the seed, finished ones first, until `target` served tokens
    are in.  A request still decoding at the close is compared over the
    tokens it has been served."""
    served = [r for r in window.records if r.n_out > 0]
    if not served:
        return []
    rng = np.random.default_rng([int(seed), 2])
    done = [r for r in served if r.finished_t is not None]
    longest = max(done or served, key=lambda r: (r.prompt_len + r.n_out,
                                                 -r.idx))
    pick, tokens = [longest], longest.n_out
    order = sorted(rng.permutation(len(served)).tolist(),
                   key=lambda i: served[i].finished_t is None)
    for i in order:
        if tokens >= target:
            break
        r = served[i]
        if r is not longest:
            pick.append(r)
            tokens += r.n_out
    return pick


GAP_NUMBERS = ("max_served_gap", "mean_served_gap")


def gap_numbers(gaps) -> dict:
    """From each compared request's per-token gaps below the reference's
    best logit: the widest, the mean over every compared token, and how
    many tokens are not the reference's own choice."""
    n = sum(g.size for g in gaps)
    return {
        "max_served_gap": max((float(g.max()) for g in gaps),
                              default=float("nan")),
        "mean_served_gap": (sum(float(g.sum()) for g in gaps) / n if n
                            else float("nan")),
        "flips": int(sum(int((g > 0).sum()) for g in gaps)),
    }


def read_metrics(entries, ctx: Context, chip_dir: str) -> dict:
    out = {}
    for m in entries:
        value = cells.metric_reader(m["name"], chip_dir).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(args, root: str = cells.ROOT, chip_dir: str = cells.CHIP_DIR,
        require_chip: bool = True, engine_hook=None) -> dict:
    """One run; returns the result line as a dict.  `engine_hook(engine)`
    may replace parts of the warmed engine (the fault tests)."""
    t_proc = time.perf_counter() - (process_age_s() or 0.0)
    phases = {}
    mark = time.perf_counter()

    def phase(name):
        nonlocal mark
        now = time.perf_counter()
        phases[name] = now - mark
        mark = now

    import jax

    bench = cells.benchmark(root)
    cell = cells.workload(args.workload, bench)
    if require_chip:
        devs = check_device(int(cell["chips"]))
    else:
        devs = jax.devices()
    dev = devs[0]
    from benchlib import system

    cache_dir = os.path.join(root, ".jax_cache")
    system.compile_cache(cache_dir)
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(cache_dir, "tunecache.json")
    compiles = CompileCounter()
    conf = cells.load_config(cell["config"], chip_dir)
    spec = cells.load_traffic(cell["traffic"], chip_dir)
    arrivals = cells.traffic_kind(spec["kind"], chip_dir).generate(
        spec, args.seed, float(args.seconds), conf.dims.vocab)
    cfg = conf.arch.program_config(conf)
    phase("import_s")
    params = system.make_params(args.seed, conf, cfg)
    phase("weights_s")
    engine = system.make_engine(conf, cfg, params)
    del params
    engine.warmup()
    if engine_hook is not None:
        engine_hook(engine)
    phase("warmup_s")

    profiler = None
    trace_dir = os.path.join(root, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        profiler = (lambda: jax.profiler.start_trace(trace_dir),
                    jax.profiler.stop_trace)
    driver = drive.Driver(engine, arrivals, system.request_spec)
    n_compiles = {}

    def at_start():
        n_compiles["start"] = compiles.n

    win = driver.run(warm_in_s=float(spec.get("warm_in_s", 0.0)),
                     seconds=float(args.seconds),
                     trace_s=min(TRACE_SECONDS, float(args.seconds)),
                     profiler=profiler, on_start=at_start)
    in_window = compiles.n - n_compiles.get("start", 0)
    cold = engine.metrics.cold_compiles
    phases["warm_in_s"] = win.t_start - mark
    setup_s = win.t_start - t_proc
    stats_mem = dev.memory_stats() or {}
    peak = int(stats_mem.get("peak_bytes_in_use", 0))

    trace_red = None
    if args.trace:
        from benchlib import trace as T

        trace_red = T.reduce(T.load_dir(trace_dir), win)
    ctx = Context(cell=cell, arch=conf.arch, dims=conf.dims, window=win,
                  peaks=work.peaks_for(dev.device_kind) if require_chip
                  else work.PEAKS["TPU v5 lite"],
                  setup_s=setup_s, trace=trace_red)
    section = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(cells.cell_metrics(bench, cell["name"], section),
                           ctx, chip_dir)

    # The check: free the program's state, then the reference.
    sample = sample_for_check(
        win, args.seed, int(conf.raw["check"].get("tokens", CHECK_TOKENS)))
    # (prompt, served tokens, tokens due: max_new once finished, else None)
    check_in = [(r.prompt, np.asarray(r.req.out_tokens[:r.n_out], np.int32),
                 r.max_new if r.finished_t is not None else None)
                for r in sample]
    setup_compiles = (compiles.n, compiles.cache_hits, compiles.cache_misses)
    attempted = sum(1 for r in win.records if r.submitted_t is not None)
    late = [r.submitted_t - r.due for r in win.records
            if r.submitted_t is not None and win.t_start <= r.due < win.t_end]
    del driver, engine, sample
    gc.collect()

    t_ref = time.perf_counter()
    all_gaps = conf.arch.served_gaps(conf.dims, args.seed,
                                     [(p, s) for p, s, _ in check_in],
                                     control=args.control)
    ref_s = time.perf_counter() - t_ref
    served = sum(len(s) for _, s, _ in check_in)
    short = sum(1 for _, s, n in check_in if n is not None and len(s) != n)
    # The gap numbers the configuration compares, each with its limit.
    limits = {k: float(v) for k, v in conf.raw["check"].items()
              if k in GAP_NUMBERS}
    numbers = gap_numbers(all_gaps[args.control or "served"])
    flips = numbers.pop("flips")
    correct = bool(served > 0 and short == 0 and limits
                   and all(numbers[k] <= v for k, v in limits.items())
                   and in_window + cold == 0)

    log(f"setup_s {setup_s:.3f} by phase: "
        + ", ".join(f"{k} {v:.3f}" for k, v in phases.items()))
    if late:
        log(f"generator lateness over {len(late)} requests due in the window: "
            f"mean {np.mean(late) * 1e3:.3f} ms, max {max(late) * 1e3:.3f} ms")
    log("compiles before the reference: {} backend compiles, persistent "
        "cache {} hits, {} misses".format(*setup_compiles))
    log(f"compiles in the window: {in_window} (engine cold_compiles {cold})")
    log(f"window {args.seconds} s: {len(win.ticks)} ticks, "
        f"{attempted} requests submitted, reference {ref_s:.1f} s over "
        f"{len(check_in)} requests")
    check = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
    check.update({
        "served_tokens": {"value": served, "limit": 1},
        "short_requests": {"value": short, "limit": 0},
        "compiles_in_window": {"value": in_window + cold, "limit": 0},
    })
    what = (f"the {args.control} control's first tokens" if args.control
            else "served tokens")
    log(f"{flips} of {served} {what} differ from the reference's own choice")
    rest = {k: v for k, v in numbers.items() if k not in limits}
    if rest:
        log("not compared: " + ", ".join(f"{k} {v}" for k, v in rest.items()))
    if args.control:
        log("the served tokens of the same requests: " + ", ".join(
            f"{k} {v}" for k, v in gap_numbers(all_gaps["served"]).items()))
    for k, v in check.items():
        log(f"check {k} = {v['value']} (limit {v['limit']})")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if trace_red is not None:
        device["busy_s"] = trace_red.busy_s
        device["window_s"] = trace_red.window_s
        result["breakdown"] = trace_red.breakdown()
    result["check"] = check
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # The control, which must read not correct: the reference computed in
    # int8 in place of the served tokens (PERF.md).
    ap.add_argument("--control", choices=("int8",), default=None,
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        log(f"benchmark: {e}")
        return 2
    except cells.CellError as e:
        log(f"benchmark: {e}")
        return 2
    print(json.dumps(result))
    return 0
